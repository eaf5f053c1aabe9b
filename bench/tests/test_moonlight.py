"""The Moonlight configuration against its plain reference, on the CPU at
the tiny size of ``bench/reference/moonlight.TINY``, on seeded random
weights drawn by the reference's ``init``.

The program runs here with float32 activations (``RunFlags(compute_dtype=
float32)``), so both sides compute in float32 and differ by the order of
their sums alone: the MLA, MoE and whole-model comparisons hold outputs,
losses and gradients to 1e-4 relative (float32 carries 6e-8 a rounding,
and the deepest path sums three layers of a few hundred products each),
far below what a missing term moves (the tests of the share and of the
MoE fault below move them by 1e-2 and more). Choices that
flip between program and reference would show as larger gaps; at these
sizes there are none (``test_program_and_reference_choose_alike``)."""
import copy
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench import manifest as M
from bench.reference import common as C
from bench.reference import moonlight as R

RTOL = 1e-4


@pytest.fixture(scope="module")
def conf():
    c = copy.deepcopy(M.config(M.load(), "moonlight-16b-a3b"))
    c.update(R.TINY)
    return c


def _program(conf, **over):
    from bench.drivers.train import program_config
    from repro.models import transformer as TF
    from repro.models.params import abstract_params
    cfg = dataclasses.replace(program_config(conf), **over)
    shapes = abstract_params(TF.model_defs(cfg))
    return cfg, shapes


def _weights(shapes, seed=5):
    return jax.jit(lambda k: R.init(shapes, k))(C.seed_key(seed))


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    gap = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
    assert gap < rtol, gap
    return gap


def _x(conf, b=2, s=64, seed=7):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (b, s, conf["hidden_size"]))


def _layer(params, stack, i=0):
    return jax.tree.map(lambda a: a[i], params[stack])


def _mm():
    return C.matmul(C.EXACT.product)


def test_mla_matches_reference(conf):
    from repro.models import mla
    cfg, shapes = _program(conf)
    lp = _layer(_weights(shapes), "layers")
    x = _x(conf)
    got = mla.mla_block(lp["attn"], cfg, x, jnp.arange(x.shape[1]))
    with jax.default_matmul_precision("highest"):
        want = R._mla(lp["attn"], x, conf, _mm())
    _close(got, want)


def test_mla_chunks_match_one_block(conf):
    """Query chunks reading their key prefixes equal one causal block."""
    from repro.models import mla
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 64, 4, d))
               for i, d in ((0, 24), (1, 24), (2, 16)))
    a = mla.causal_attention(q, k, v, 0.2, chunk=16)
    b = mla.causal_attention(q, k, v, 0.2, chunk=64)
    _close(a, b, 1e-5)


@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_moe_layer_matches_reference(conf, impl):
    """The dropless layer (grouped products by ``lax.ragged_dot``, or the
    megablox kernel in interpret mode at widths it takes) against the
    reference's dense loop over the held experts."""
    from repro.models import moe
    wide = dict(conf, hidden_size=128, moe_intermediate_size=128)
    cfg, shapes = _program(wide)
    lp = _layer(_weights(shapes), "layers")
    x = _x(wide)
    got, stats = moe.moe_block(lp["moe"], cfg, x, impl=impl)
    with jax.default_matmul_precision("highest"):
        want = R._moe(lp["moe"], x, wide, _mm(), C.EXACT)
        chosen, _ = R.route(lp["moe"], x, wide)
    _close(got, want)
    held = int(jnp.sum(chosen < wide["n_routed_experts"]))
    assert int(stats["moe_assigned"]) == held > 0


def test_expert_shares_sum_to_the_uncut_layer(conf):
    """Each share of 4 held experts out of 16, its router outputs
    rotated so that its experts come first: the routed parts of all
    shares, with the shared experts counted once, are the uncut layer."""
    from repro.models import moe
    e, held = conf["n_routed_experts_published"], conf["n_routed_experts"]
    whole_conf = dict(conf, n_routed_experts=e)
    cfg_all, shapes_all = _program(whole_conf)
    lp = _layer(_weights(shapes_all), "layers")["moe"]
    x = _x(conf)
    with jax.default_matmul_precision("highest"):
        want = R._moe(lp, x, whole_conf, _mm(), C.EXACT)
    cfg, _ = _program(conf, n_shared_experts=0)
    total = 0.0
    for s in range(e // held):
        perm = np.roll(np.arange(e), -s * held)
        share = {"router": lp["router"][:, perm],
                 "router_bias": lp["router_bias"][perm],
                 **{k: lp[k][perm[:held]]
                    for k in ("w_gate", "w_up", "w_down")}}
        total = total + moe.moe_block(share, cfg, x)[0]
    shared = R._mlp(lp["shared"], x, _mm())
    _close(total + shared, want)
    # one share alone is not the layer
    gap = np.linalg.norm(np.asarray(shared + moe.moe_block(
        {k: (v[:held] if k.startswith("w_") else v) for k, v in lp.items()
         if k != "shared"}, cfg, x)[0] - want)) / np.linalg.norm(want)
    assert gap > 1e-2


def _loss_and_grads(conf, cfg, params, batch):
    from repro.dist.train import loss_fn
    from repro.models import transformer as TF
    flags = TF.RunFlags(remat=False, compute_dtype=jnp.float32)
    (loss, _), g = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch, flags), has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        rl, rg = jax.value_and_grad(
            lambda p: R.logits_and_loss(p, batch["tokens"], batch["labels"],
                                        conf))(params)
    return loss, g, rl, rg


def test_smoke_model_loss_and_gradients_match_reference(conf):
    """``moonshot-v1-16b-a3b-smoke`` (the dense layer and one MoE layer,
    every expert held) through the trainer's loss against the reference."""
    from repro.configs import get_config
    cfg = get_config("moonshot-v1-16b-a3b-smoke")
    smoke = dict(conf, num_hidden_layers=cfg.n_layers,
                 hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
                 kv_lora_rank=cfg.kv_lora_rank,
                 qk_nope_head_dim=cfg.qk_nope_head_dim,
                 qk_rope_head_dim=cfg.qk_rope_head_dim,
                 v_head_dim=cfg.v_head_dim, intermediate_size=cfg.d_ff,
                 moe_intermediate_size=cfg.moe_d_ff,
                 n_routed_experts=cfg.n_held,
                 n_routed_experts_published=cfg.n_experts,
                 num_experts_per_tok=cfg.experts_per_token,
                 vocab_size=cfg.vocab_size)
    from repro.models import transformer as TF
    from repro.models.params import abstract_params
    params = _weights(abstract_params(TF.model_defs(cfg)))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    loss, g, rl, rg = _loss_and_grads(smoke, cfg, params, batch)
    assert abs(float(loss) - float(rl)) < RTOL * float(rl)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree.leaves(rg)):
        if "router_bias" in C.path_str(path):
            assert not np.asarray(a).any() and not np.asarray(b).any()
        else:
            _close(a, b)


def test_cell_model_loss_and_gradients_match_reference(conf):
    """The cell's own cut at the tiny size: dense layer, two MoE layers,
    4 of 16 experts held."""
    cfg, shapes = _program(conf)
    params = _weights(shapes)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0,
                                conf["vocab_size"])
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    loss, g, rl, rg = _loss_and_grads(conf, cfg, params, batch)
    assert abs(float(loss) - float(rl)) < RTOL * float(rl)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(rg)):
        if np.asarray(b).any():
            _close(a, b)


@pytest.mark.parametrize("fault", ["routed_scaling_factor", "no_shared"])
def test_moe_fault_moves_the_layer(conf, fault):
    """Leaving out part of the MoE mathematics moves the layer far more
    than the tolerance above: the reference with the routed scale at 1,
    or without its shared experts."""
    cfg, shapes = _program(conf)
    lp = _layer(_weights(shapes), "layers")["moe"]
    x = _x(conf)
    with jax.default_matmul_precision("highest"):
        want = R._moe(lp, x, conf, _mm(), C.EXACT)
        if fault == "no_shared":
            lp = dict(lp, shared=jax.tree.map(jnp.zeros_like, lp["shared"]))
            got = R._moe(lp, x, conf, _mm(), C.EXACT)
        else:
            got = R._moe(lp, x, dict(conf, routed_scaling_factor=1.0),
                         _mm(), C.EXACT)
    gap = np.linalg.norm(np.asarray(got - want)) / np.linalg.norm(want)
    assert gap > 100 * RTOL


def test_program_and_reference_choose_alike(conf):
    from bench.readings_moe import program_choices
    from repro.models import transformer as TF
    cfg, shapes = _program(conf)
    params = _weights(shapes)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0,
                                conf["vocab_size"])
    prog = program_choices(cfg, params, tokens, TF.RunFlags(
        remat=False, compute_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        ref = R.choices(params, tokens, conf)
    assert len(prog) == len(ref) == conf["num_hidden_layers"] - 1
    for a, b in zip(prog, ref):
        b = np.asarray(b).reshape(a.shape)
        assert (np.sort(np.asarray(a), -1) == np.sort(b, -1)).all()


def test_flops_by_hand():
    c = M.config(M.load(), "moonlight-16b-a3b")
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    count = M.load_module(Path(flops.__file__).with_name(
        "flops_moonlight.py"))
    assert count.mla_params(c) == mla == 13_762_560
    moe = 3 * 2048 * 2816 + 2048 * 64 + 6 * 8 / 64 * 3 * 2048 * 1408
    n = 5 * mla + 3 * 2048 * 11264 + 4 * moe + 20480 * 2048
    assert n == 275_644_416
    attn = 3 * 5 * 16 * 320 * 4097
    assert flops.train_flops_per_token(c, 4096) == 6 * n + attn
    # about 2.0 GFLOP a token, attention 16% of it at 4,096
    assert 1.9e9 < 6 * n + attn < 2.0e9


def test_moe_readings_run_and_the_moe_faults_fail(monkeypatch):
    """``bench/readings_moe.py`` at the tiny size: each planted MoE fault
    fails the cell's limits, as the half batch and the unchanged state do
    (bench/tests/test_faults.py); flips are counted per MoE layer."""
    from bench import readings_moe
    from bench.tests.tiny import tiny_ctx
    ctx = tiny_ctx("moonlight_train_exact")
    rows = {r["kind"]: r for r in readings_moe.readings(ctx, control=True)}
    flips = rows["flips"]["per_moe_layer"]
    assert len(flips) == ctx.conf["num_hidden_layers"] - 1
    assert all(0 <= f <= rows["flips"]["of"] for f in flips)
    for kind in ("fault_routed_scale_1", "fault_no_shared_experts",
                 "fault_state_unchanged", "control"):
        assert any(rows[kind][k] > v for k, v in ctx.limits.items()), kind


def test_gmm_roofline_reader_by_hand():
    """The roofline share of the grouped products: 11 products a row of
    2 x 2,048 x 1,408 FLOPs over 6,144 held rows in each of 4 MoE layers,
    at 197 TFLOP/s (compute-bound: their bytes take 5.2 ms at 819 GB/s),
    over the kernels' device time; None for another cell's reading."""
    man = M.load()
    c = M.config(man, "moonlight-16b-a3b")
    reader = M.load_module(M.metric_path("moe_gmm_roofline"))
    r = {"red": {"ops": {"jvp_jit_gmm__.4": 0.010, "tgmm.1": 0.0061,
                         "fusion.3": 1.0}},
         "steps": 1, "chips": 1,
         "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
         "flops_per_step": flops.train_flops_per_token(c, 4096) * 8192}
    gmm_flops = 11 * 2 * 2048 * 1408 * 6144 * 4
    assert reader.read(r) == pytest.approx(
        100 * gmm_flops / 197e12 / 0.0161)
    assert 0 < reader.read(r) < 100
    assert reader.read(dict(r, flops_per_step=1e12)) is None
    assert reader.read(dict(r, red={"ops": {"fusion.3": 1.0}})) is None


def test_gmm_products_are_what_the_step_compiles():
    """``flops_moonlight.GMM_PRODUCTS`` is the number of grouped-product
    kernels (``gmm``, ``tgmm``) in the MoE layer's compiled gradient for a
    described v5e chip."""
    import os
    import re
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import dispatch
    from repro.models import moe as MOE
    from repro.models.params import abstract_params
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    count = M.load_module(Path(flops.__file__).with_name(
        "flops_moonlight.py"))
    c = M.config(M.load(), "moonlight-16b-a3b")
    cfg, _ = _program(c)
    sh = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_params(MOE.moe_defs(cfg)))
    x = jax.ShapeDtypeStruct((1, 256, cfg.d_model), jnp.bfloat16,
                             sharding=sh)
    grad = jax.jit(jax.grad(lambda p, x: jnp.sum(MOE.moe_block(
        p, cfg, x)[0].astype(jnp.float32) ** 2), argnums=(0, 1)))
    old_backend = dispatch.backend
    old_cache = jax.config.jax_enable_compilation_cache
    # a described compile cannot be read back from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    dispatch.backend = lambda: "tpu"
    try:
        text = grad.lower(shapes, x).compile().as_text()
    finally:
        dispatch.backend = old_backend
        jax.config.update("jax_enable_compilation_cache", old_cache)
    kernels = re.findall(r"%?(\S*t?gmm\S*) = \S+ custom-call\(", text)
    assert len(kernels) == count.GMM_PRODUCTS, kernels

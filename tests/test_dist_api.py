"""repro.dist API contract: every symbol the launch/test consumers import
must exist with the expected signature, so the `importorskip` guards in the
older tests can never silently drift back into dead skip-reasons.

Consumers pinned here:
  * repro.launch.train   — sharding.axis_sizes, train.make_train_step,
                           train.make_elastic_train_step
  * repro.launch.dryrun  — sharding.{axis_sizes, data_axes, named,
                           batch_spec, batch_specs, cache_specs,
                           opt_state_specs, make_act_rules},
                           train.{make_train_step, make_elastic_train_step,
                           make_prefill_step, make_decode_step}
  * repro.launch.serve   — train.{make_prefill_step, make_decode_step}
  * tests/test_archs_smoke — train.{loss_fn, make_train_step}
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import async_engine as AE
from repro.dist import sharding as SH
from repro.dist import train as DT


def params_of(fn) -> list:
    return list(inspect.signature(fn).parameters)


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------

def test_sharding_symbols_and_signatures():
    assert params_of(SH.axis_sizes) == ["mesh"]
    assert params_of(SH.data_axes) == ["mesh"]
    assert params_of(SH.named) == ["mesh", "spec_tree"]
    assert params_of(SH.batch_spec) == ["mesh", "global_batch"]
    assert params_of(SH.batch_specs) == ["cfg", "mesh", "batch"]
    assert params_of(SH.cache_specs) == ["cfg", "mesh", "cache"]
    assert params_of(SH.opt_state_specs) == ["opt_state", "pspecs"]
    sig = inspect.signature(SH.make_act_rules)
    assert params_of(SH.make_act_rules)[:2] == ["cfg", "mesh"]
    for kw in ("batch_size", "seq_len", "sequence_parallel", "batch_axes"):
        assert sig.parameters[kw].kind == inspect.Parameter.KEYWORD_ONLY, kw


def test_train_symbols_and_signatures():
    assert params_of(DT.loss_fn) == ["cfg", "params", "batch", "flags"]
    assert params_of(DT.make_train_step) == ["cfg", "opt", "flags",
                                             "grad_accum", "skip_nonfinite"]
    ep = params_of(DT.make_elastic_train_step)
    assert ep[:6] == ["cfg", "opt", "mesh", "scfg", "pspecs", "flags"]
    assert "static_phase" in ep and "grad_accum" in ep
    assert params_of(DT.init_dist_sync_state) == ["scfg", "mesh",
                                                  "params_like"]
    assert params_of(SH.sync_state_specs) == ["sync_state", "pspecs", "mesh"]
    assert params_of(DT.make_prefill_step) == ["cfg", "max_len", "flags",
                                               "sample"]
    assert params_of(DT.make_decode_step) == ["cfg", "flags", "sample"]
    assert params_of(SH.paged_cache_specs) == ["cfg", "mesh", "pool"]


def test_async_engine_symbols_and_signatures():
    assert params_of(AE.make_async_train_step) == [
        "cfg", "opt", "mesh", "acfg", "pspecs", "flags", "grad_accum"]
    assert params_of(AE.init_async_state) == ["acfg", "mesh", "params_like",
                                              "pspecs"]
    acfg = AE.AsyncConfig()
    # the config surface launch/train + bench_async_ef drive
    assert acfg.tau_max == 0 and acfg.schedule == "uniform"
    assert acfg.compressor == "none" and acfg.error_feedback is True
    assert acfg.capacity == 1 and acfg.has_err is False
    # overlap defaults ON but only changes the program with a compressor
    assert acfg.overlap is True and acfg.fused is False
    assert acfg.kernel_impl == "auto"
    # fault-tolerance knobs default OFF (the fast path traces no guards)
    assert acfg.crash_subst is False and acfg.skip_nonfinite is False
    from repro.core.delivery import DROPPED, TAU_SCHEDULES
    assert acfg.schedule in TAU_SCHEDULES and DROPPED == -1
    # per-worker key registry shared between layout and spec builders
    assert "buf" in SH.PER_WORKER_RING_KEYS
    assert params_of(SH.shard_state_specs) == ["state", "head"]


def test_launch_modules_import():
    """The three launchers resolve their repro.dist imports at module load
    (serve/train import lazily inside main, so exercise those paths via
    importlib on dryrun which imports at toplevel)."""
    import repro.launch.serve  # noqa: F401
    import repro.launch.train  # noqa: F401
    # dryrun imports repro.dist at module scope — importing it IS the check
    import repro.launch.dryrun  # noqa: F401


# ---------------------------------------------------------------------------
# spec-builder behaviour (pure, no multi-device mesh needed)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh():
    from repro.jax_compat import make_mesh
    return make_mesh((1, 1), ("data", "model"))


def test_axis_sizes_and_data_axes(mesh):
    assert SH.axis_sizes(mesh) == {"data": 1, "model": 1}
    assert SH.data_axes(mesh) == ("data",)


def test_named_maps_spec_trees(mesh):
    tree = {"a": P(None, "model"), "b": {"c": P()}}
    out = SH.named(mesh, tree)
    assert isinstance(out["a"], NamedSharding)
    assert out["a"].spec == P(None, "model")
    assert out["b"]["c"].spec == P()


def test_batch_spec_divisibility(mesh):
    assert tuple(SH.batch_spec(mesh, 8)) == ("data",)
    # non-divisible batch stays replicated
    from repro.jax_compat import make_mesh
    m3 = make_mesh((1,), ("data",))
    assert tuple(SH.batch_spec(m3, 8)) == ("data",)


def test_opt_state_specs_mirror_params(mesh):
    pspecs = {"w": P(None, "model"), "b": P()}
    like = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32),
            "b": jax.ShapeDtypeStruct((4,), jnp.float32)}
    state = {"count": jax.ShapeDtypeStruct((), jnp.int32), "mu": like}
    out = SH.opt_state_specs(state, pspecs)
    assert out["mu"] == pspecs          # params-shaped entries inherit specs
    assert out["count"] == P()          # scalars replicated


def test_make_act_rules_kinds(mesh):
    from repro.configs import get_config
    cfg = get_config("mixtral-8x7b").reduced()
    rules = SH.make_act_rules(cfg, mesh, batch_size=8, seq_len=64)
    for kind in ("residual", "ffn_hidden", "attn_q", "attn_kv", "logits"):
        assert kind in rules and isinstance(rules[kind], NamedSharding), kind
    # inside shard_map the data axes must be dropped
    inner = SH.make_act_rules(cfg, mesh, batch_size=8, seq_len=64,
                              batch_axes=False)
    for kind, ns in inner.items():
        assert "data" not in jax.tree.leaves(tuple(ns.spec)), kind


# ---------------------------------------------------------------------------
# step-builder behaviour at smoke scale
# ---------------------------------------------------------------------------

def test_elastic_step_runs_on_host_mesh(mesh):
    """One elastic step on the degenerate 1-device mesh: params move, the
    sync state advances, metrics carry the consistency gap."""
    from repro.configs import get_config
    from repro.core.scheduler import SyncConfig
    from repro.data.pipeline import synthetic_batch
    from repro.models import transformer as TF
    from repro.models.params import init_params, param_specs
    from repro.optim import momentum

    cfg = get_config("qwen3-1.7b").reduced()
    flags = TF.RunFlags(remat=False)
    defs = TF.model_defs(cfg)
    pspecs = param_specs(defs, SH.axis_sizes(mesh))
    params = init_params(defs, jax.random.PRNGKey(0))
    opt = momentum(1e-2, 0.9)
    opt_state = opt.init(params)
    scfg = SyncConfig(strategy="elastic", axis_names=("data",), gate="norm")
    sync_state = DT.init_dist_sync_state(scfg, mesh, params)
    # per-worker layout: residual leads with a worker dim of size prod(data)
    lead = jax.tree.leaves(sync_state["residual"])[0].shape[0]
    assert lead == 1  # 1-device mesh
    step = DT.make_elastic_train_step(cfg, opt, mesh, scfg, pspecs, flags)
    batch = synthetic_batch(cfg, 2, 32, seed=0)
    p2, opt_state, sync_state, metrics = jax.jit(step)(
        params, opt_state, sync_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["gap2_over_alpha2"]) >= 0.0
    assert int(sync_state["step"]) == 1
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert delta > 0


def test_serve_steps_roundtrip():
    from repro.configs import get_config
    from repro.data.pipeline import synthetic_batch
    from repro.models import transformer as TF
    from repro.models.params import init_params

    cfg = get_config("qwen3-1.7b").reduced()
    flags = TF.RunFlags(remat=False)
    params = init_params(TF.model_defs(cfg), jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg, 2, 8, seed=0)
    batch.pop("labels")
    tok, cache = jax.jit(DT.make_prefill_step(cfg, 12, flags))(params, batch)
    assert tok.shape == (2,) and tok.dtype == jnp.int32
    decode = jax.jit(DT.make_decode_step(cfg, flags), donate_argnums=(1,))
    tok2, cache = decode(params, cache, tok[:, None])
    assert tok2.shape == (2,)
    assert int(cache["pos"]) == 9  # 8 prefill + 1 decode

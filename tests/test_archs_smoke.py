"""Per-architecture smoke tests (deliverable f): reduced variant of each
assigned arch runs one forward + one train step on CPU; output shapes and
finiteness asserted."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "repro.dist", reason="repro.dist modules not seeded in this snapshot")

from repro.configs import ARCH_IDS, get_config  # noqa: E402
from repro.data.pipeline import synthetic_batch  # noqa: E402
from repro.dist.train import loss_fn, make_train_step  # noqa: E402
from repro.models import transformer as TF
from repro.models.params import count_params, init_params
from repro.optim import momentum

FLAGS = TF.RunFlags(remat=False)
B, S = 2, 64


def _batch(cfg):
    return synthetic_batch(cfg, B, S, seed=0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.is_moe:
        assert cfg.n_experts <= 4
    params = init_params(TF.model_defs(cfg), jax.random.PRNGKey(0))
    logits, aux = jax.jit(
        lambda p, b: TF.forward(cfg, p, b, FLAGS))(params, _batch(cfg))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(aux["aux_loss"]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    cfg = get_config(arch).reduced()
    params = init_params(TF.model_defs(cfg), jax.random.PRNGKey(0))
    opt = momentum(1e-3, 0.9)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt, FLAGS))
    batch = _batch(cfg)
    loss0 = float(loss_fn(cfg, params, batch, FLAGS)[0])
    params2, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually moved
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(params2)))
    assert delta > 0
    loss1 = float(loss_fn(cfg, params2, batch, FLAGS)[0])
    assert np.isfinite(loss1)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_exact_config_matches_assignment(arch):
    """The full (non-reduced) configs carry the exact assigned hyperparams."""
    cfg = get_config(arch)
    expected = {
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "moonshot-v1-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "rwkv6-1.6b": (24, 2048, 0, 0, 7168, 65536),
        "mistral-nemo-12b": (40, 5120, 32, 8, 14336, 131072),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab_size)
    assert got == expected, (arch, got, expected)
    if arch == "grok-1-314b":
        assert (cfg.n_experts, cfg.experts_per_token) == (8, 2)
    if arch == "moonshot-v1-16b-a3b":
        assert (cfg.n_experts, cfg.experts_per_token) == (64, 6)
        assert (cfg.moe_d_ff, cfg.n_shared_experts, cfg.first_k_dense) == \
            (1408, 2, 1)
        assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                cfg.v_head_dim) == (512, 128, 64, 128)
        assert (cfg.router_score, cfg.router_scale) == ("sigmoid", 2.446)
    if arch == "mixtral-8x7b":
        assert (cfg.n_experts, cfg.experts_per_token) == (8, 2)
        assert cfg.sliding_window == 4096
    if arch == "zamba2-7b":
        assert cfg.ssm_state == 64 and cfg.shared_attn_every == 6
    if arch == "gemma3-27b":
        assert cfg.sliding_window == 1024 and cfg.global_every == 6
    if arch == "qwen3-1.7b":
        assert cfg.qk_norm


def test_param_counts_plausible():
    # grok-1 is the 314B-class config
    assert 2.5e11 < get_config("grok-1-314b").param_count() < 4e11
    assert 3.5e10 < get_config("mixtral-8x7b").param_count() < 5.5e10
    assert 1.2e9 < get_config("qwen3-1.7b").param_count() < 2.5e9
    # Moonlight-16B-A3B: 16B in all, 3B active a token, as published
    ms = get_config("moonshot-v1-16b-a3b")
    assert 15e9 < ms.param_count() < 17e9
    assert 2.5e9 < ms.active_param_count() < 3.5e9

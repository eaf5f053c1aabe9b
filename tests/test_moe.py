"""MoE layer invariants: the router (top-k by score, normalised weights,
the load-balancing loss) and the dropless dispatch over the held experts.
Agreement with the plain reference is in bench/tests/test_moonlight.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import dispatch
from repro.models import moe as MOE
from repro.models.params import init_params


def _cfg(arch="moonshot-v1-16b-a3b", **over):
    return dataclasses.replace(get_config(arch).reduced(), **over)


def _params(cfg, seed=0):
    return init_params(MOE.moe_defs(cfg), jax.random.PRNGKey(seed))


def _tokens(cfg, t=64, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, cfg.d_model))


@pytest.mark.parametrize("arch", ["moonshot-v1-16b-a3b", "mixtral-8x7b"])
def test_combine_weights_normalized(arch):
    """The k weights of a token sum to the routed scale, and sit on its k
    highest choice scores (distinct experts)."""
    cfg = _cfg(arch, router_scale=2.5)
    params = _params(cfg)
    if "router_bias" in params:
        params["router_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(3), params["router_bias"].shape)
    scores, chosen, w = MOE.router(params, cfg, _tokens(cfg))
    np.testing.assert_allclose(np.sum(np.asarray(w), -1), 2.5, rtol=1e-6)
    assert (np.sort(np.asarray(chosen), -1)[:, 1:]
            != np.sort(np.asarray(chosen), -1)[:, :-1]).all()
    choice = scores + params.get("router_bias", 0.0)
    kth = np.sort(np.asarray(choice), -1)[:, -cfg.experts_per_token]
    picked = np.take_along_axis(np.asarray(choice), np.asarray(chosen), -1)
    assert (picked >= kth[:, None]).all()


def test_aux_loss_minimized_by_uniform_router():
    e, t, k = 8, 64, 2
    uniform = jnp.full((t, e), 1.0 / e)
    chosen_u = jnp.stack([jnp.arange(t) % e, (jnp.arange(t) + 1) % e], -1)
    skewed = jnp.zeros((t, e)).at[:, 0].set(1.0)
    chosen_s = jnp.zeros((t, k), jnp.int32).at[:, 1].set(1)
    assert float(MOE.aux_loss(skewed, chosen_s, e)) > \
        float(MOE.aux_loss(uniform, chosen_u, e))


def test_sort_by_expert_counts_every_held_assignment():
    chosen = jnp.array([[0, 5], [2, 0], [7, 3], [0, 1]])   # held: 0..3
    order, sizes, n = MOE.sort_by_expert(chosen, 4)
    assert sizes.tolist() == [3, 1, 1, 1] and int(n) == 6
    flat = chosen.reshape(-1)[order]
    assert flat[:6].tolist() == [0, 0, 0, 1, 2, 3]
    assert set(flat[6:].tolist()) == {5, 7}


@pytest.mark.parametrize("held", [2, 4])
def test_moe_block_is_dropless_under_any_imbalance(held):
    """A router forced so that every token picks the same experts: every
    assignment to a held one is computed (counters and output)."""
    cfg = _cfg(n_experts=8, experts_per_token=2, experts_held=held)
    params = _params(cfg)
    params["router"] = jnp.zeros_like(params["router"])
    params["router_bias"] = jnp.zeros((8,)).at[jnp.array([1, 0])].set(1.0)
    x = _tokens(cfg, t=48)[None]
    out, stats = MOE.moe_block(params, cfg, x)
    assert float(stats["moe_assigned"]) == 48 * 2
    assert float(stats["moe_max_load"]) == 48
    # sigmoid(0) = 0.5 on both, normalised: each expert weighs scale / 2
    def ffn(e):
        g = x[0] @ params["w_gate"][e]
        return (jax.nn.silu(g) * (x[0] @ params["w_up"][e])) @ \
            params["w_down"][e]
    sh = params["shared"]
    want = cfg.router_scale / 2 * (ffn(0) + ffn(1)) + \
        (jax.nn.silu(x[0] @ sh["w_gate"]) * (x[0] @ sh["w_up"])) @ sh["w_down"]
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_grouped_matmul_zero_past_the_groups():
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 128, 128))
    sizes = jnp.array([100, 0, 60], jnp.int32)
    for impl in ("ref", "kernel"):
        y = MOE.grouped_matmul(x, w, sizes, impl)
        want = jnp.concatenate([x[:100] @ w[0], x[100:160] @ w[2],
                                jnp.zeros((96, 128))])
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("k,n,tiles", [
    (2048, 1408, (512, 512, 1408)),     # Moonlight's gate and up
    (1408, 2048, (512, 1408, 512)),     # its down product
    (4096, 14336, (512, 512, 512)),     # mixtral's widths
    (256, 384, (512, 256, 384)),
])
def test_gmm_tiles_fit_vmem(k, n, tiles):
    """Whole widths where megablox's blocks fit the dispatch's VMEM
    budget, else 512 wide, the contraction first; every tiling chosen
    fits, and a row count off the 128 grid is refused."""
    got, why = MOE._gmm_tiling(49152, k, n, 2)
    assert (got, why) == (tiles, None)
    assert MOE._gmm_vmem_bytes(*got, 2) <= dispatch.VMEM_BUDGET
    if got[1:] != (k, n):
        assert MOE._gmm_vmem_bytes(512, k, n, 2) > dispatch.VMEM_BUDGET
    assert "not a multiple of 128" in MOE.gmm_why_not(100, k, n)

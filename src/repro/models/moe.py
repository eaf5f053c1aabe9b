"""Dropless Mixture-of-Experts layer over the experts held here.

One layer serves every MoE configuration (Moonlight's sigmoid router with
shared experts, mixtral's and grok's softmax router):

  * ``moe_route``: the router in float32 over all ``n_experts`` outputs,
    scores ``sigmoid(x W_r)`` (chosen by ``score + bias``; the bias takes
    no gradient, it only moves the choice) or ``softmax(x W_r)``; per
    token the top ``experts_per_token``, their scores normalised to sum 1
    and times ``router_scale``. Every (token, choice) whose expert is held
    here (experts ``0..n_held-1``, expert parallelism's share of one chip)
    is kept, at any imbalance: the assignments are sorted by expert into a
    static buffer of ``tokens * k`` rows, the most a token's distinct
    choices can put here, and the rows are gathered.
  * ``moe_experts``: the held experts' SiLU-gated MLPs as grouped products
    over the sorted rows (:func:`grouped_matmul`), whose cost follows the
    rows assigned rather than the buffer; each row's weight is folded in
    before the down product.
  * ``moe_combine``: the rows summed back per token in float32, plus the
    shared experts (one gated MLP every chip computes).

What the absent experts would add is left out: the result is this chip's
part of the layer, which expert parallelism would sum over the chips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.kernels import dispatch
from repro.models.layers import mlp_block, mlp_defs
from repro.models.params import ParamDef

SITE = "moe.gmm"


def moe_defs(cfg) -> dict:
    d, e, h, ff = cfg.d_model, cfg.n_experts, cfg.n_held, cfg.expert_d_ff
    defs = {
        "router": ParamDef((d, e), ("embed", None), scale=d ** -0.5),
        "w_gate": ParamDef((h, d, ff), ("experts", "embed", "ff")),
        "w_up": ParamDef((h, d, ff), ("experts", "embed", "ff")),
        "w_down": ParamDef((h, ff, d), ("experts", "ff", "embed")),
    }
    if cfg.router_score == "sigmoid":
        defs["router_bias"] = ParamDef((e,), (None,), init="zeros")
    if cfg.n_shared_experts:
        defs["shared"] = mlp_defs(d, cfg.n_shared_experts * ff)
    return defs


def router(params, cfg, x: jax.Array):
    """x (T, d) -> (scores (T, E) f32, chosen (T, k) int32, weights (T, k)
    f32): top-k by score (+ bias), ties to the lowest index."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + params["router_bias"]
    elif cfg.router_score == "softmax":
        scores = choice = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"router_score {cfg.router_score!r}")
    _, chosen = jax.lax.top_k(choice, cfg.experts_per_token)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg.router_scale
    return scores, chosen, w


def aux_loss(scores, chosen, n_experts: int):
    """Switch/GShard load-balancing loss: E^2 mean(me * ce), minimised by
    a uniform router."""
    me = jnp.mean(scores, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(chosen, n_experts), axis=1), axis=0)
    return jnp.mean(me * ce) * n_experts * n_experts


def sort_by_expert(chosen, n_held: int):
    """(T, k) choices -> (order, group_sizes, n): ``order`` (T*k,) lists
    the assignments by held expert (stable), those to absent experts
    last; ``group_sizes`` (n_held,) the rows each held expert gets, ``n``
    their sum."""
    flat = chosen.reshape(-1)
    bucket = jnp.where(flat < n_held, flat, n_held)
    order = jnp.argsort(bucket, stable=True)
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[bucket].add(1)[:n_held]
    return order, sizes, jnp.sum(sizes)


#: the row tile, and the width tile where a whole width does not fit VMEM
GMM_TILE = 512


def _gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM of megablox's blocks at tiles (tm, tk, tn): the double-buffered
    (tm, tk), (tk, tn) and (tm, tn) blocks in the operands' dtype and the
    f32 accumulator, (tm, tn) in ``gmm`` and (tk, tn) in ``tgmm`` (the
    weight gradient, which takes the same tiles)."""
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) \
        + 4 * max(tm, tk) * tn


def _gmm_tiling(m: int, k: int, n: int, itemsize: int):
    """-> ((tm, tk, tn), why_not): rows in tiles of 512 where they divide
    the buffer (else 256 or 128); the contraction and output widths whole
    where the blocks fit ``dispatch.VMEM_BUDGET``, else in 512s, the
    contraction first."""
    tm = next((t for t in (GMM_TILE, 256, 128) if m % t == 0), None)
    if tm is None:
        return None, f"{m} rows are not a multiple of 128"
    if k % 128 or n % 128:
        return None, f"widths ({k}, {n}) are not multiples of 128"
    cut_k, cut_n = min(k, GMM_TILE), min(n, GMM_TILE)
    for tk, tn in ((k, n), (cut_k, n), (k, cut_n), (cut_k, cut_n)):
        why = dispatch.vmem_why_not(_gmm_vmem_bytes(tm, tk, tn, itemsize),
                                    f"grouped product tiles ({tm}, {tk}, "
                                    f"{tn})")
        if why is None:
            return (tm, tk, tn), None
    return None, why


def gmm_why_not(m: int, k: int, n: int, itemsize: int = 2) -> str | None:
    return _gmm_tiling(m, k, n, itemsize)[1]


def grouped_matmul(x, w, group_sizes, impl: str = "auto"):
    """x (M, K) rows sorted by group, w (G, K, N) -> (M, N) in x's dtype:
    row block g times w[g]; rows past ``sum(group_sizes)`` come out zero.

    The site ``moe.gmm`` (`repro.kernels.dispatch`): megablox's grouped
    product kernel, whose grid visits only the row tiles the groups hold,
    or ``lax.ragged_dot``."""
    m, k = x.shape
    n = w.shape[-1]
    tiling, why_not = _gmm_tiling(m, k, n, x.dtype.itemsize)
    use_kernel, interpret = dispatch.choose(SITE, impl, why_not)
    if not use_kernel:
        return jax.lax.ragged_dot(x, w, group_sizes,
                                  preferred_element_type=x.dtype)
    # the kernel leaves rows past the groups unwritten, and their input
    # gradient too: zero both through where, whose gradient is masked
    valid = (jnp.arange(m) < jnp.sum(group_sizes))[:, None]
    out = megablox.gmm(jnp.where(valid, x, 0), w, group_sizes, x.dtype,
                       tiling, None, None, False, interpret)
    return jnp.where(valid, out, 0)


def _routed(params, tokens, order, sizes, w, *, k: int, impl: str):
    """The held experts' part for every token: gather the sorted rows,
    the grouped products with each row's weight folded in before the
    down product, and the sum back per token in float32."""
    dt = tokens.dtype
    with jax.named_scope("moe_route"):
        rows = jnp.take(tokens, order // k, axis=0)
        w_rows = jnp.take(w.reshape(-1), order)[:, None]
    with jax.named_scope("moe_experts"):
        gate = grouped_matmul(rows, params["w_gate"].astype(dt), sizes, impl)
        up = grouped_matmul(rows, params["w_up"].astype(dt), sizes, impl)
        h = (jax.nn.silu(gate) * up * w_rows).astype(dt)
        y = grouped_matmul(h, params["w_down"].astype(dt), sizes, impl)
    with jax.named_scope("moe_combine"):
        # back to (token, choice) order; assignments to absent experts
        # read the zero rows past the held ones
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = jnp.take(y, back, axis=0).reshape(-1, k, y.shape[-1])
        return jnp.sum(y.astype(jnp.float32), axis=1).astype(dt)


def moe_block(params, cfg, x: jax.Array, impl: str = "auto"):
    """x (B, S, d) -> ((B, S, d), stats): the held experts' part of the
    layer plus the shared experts; ``stats`` holds the router's
    ``aux_loss``, ``moe_assigned`` (assignments to held experts) and
    ``moe_max_load`` (the most rows one held expert got).

    The routed part is rematerialised in the backward pass: its buffers
    are ``tokens * k`` rows (the dropless bound), of which the held
    experts fill about ``n_held / n_experts``, and the recomputed grouped
    products cost that share alone."""
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    with jax.named_scope("moe_route"):
        scores, chosen, w = router(params, cfg, tokens)
        order, sizes, n = sort_by_expert(chosen, cfg.n_held)
    routed = jax.checkpoint(functools.partial(
        _routed, k=cfg.experts_per_token, impl=impl))
    out = routed({k: params[k] for k in ("w_gate", "w_up", "w_down")},
                 tokens, order, sizes, w)
    if cfg.n_shared_experts:
        with jax.named_scope("moe_combine"):
            out = out + mlp_block(params["shared"], tokens)
    stats = {"aux_loss": aux_loss(scores, chosen, cfg.n_experts),
             "moe_assigned": n.astype(jnp.float32),
             "moe_max_load": jnp.max(sizes).astype(jnp.float32)}
    return out.reshape(b, s, d), stats

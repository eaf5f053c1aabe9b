"""Lossy gradient compression operators (paper §4.1(d), Appendix B.7).

Every operator ``Q`` satisfies the contraction property (Eq. 25):

    ||Q(w) - w||^2 <= gamma * ||w||^2,   0 <= gamma < 1

which is what the elastic-consistency bound for error-feedback methods needs
(Lemma 18: B = sqrt((2-gamma)*gamma/(1-gamma)^3) * M). The ``gamma_bound``
attributes give the per-operator worst-case gamma used by the theory checks.

``ef_compress`` implements one error-feedback round of Algorithm 6:
w = eps + u;  payload = Q(w);  eps' = w - Q(w).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Top-K sparsification (Strom'15 / Aji-Heafield'17 style)
# ---------------------------------------------------------------------------

def topk_compress(w: jax.Array, k: int):
    """Magnitude top-k of a flat vector. Returns (values, indices)."""
    flat = w.reshape(-1)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx


def topk_decompress(values, idx, n: int):
    return jnp.zeros((n,), values.dtype).at[idx].set(values)


def topk_q(w: jax.Array, k: int) -> jax.Array:
    """Dense Q(w) for theory checks."""
    vals, idx = topk_compress(w, k)
    return topk_decompress(vals, idx, w.size).reshape(w.shape)


def topk_gamma(n: int, k: int) -> float:
    """TopK satisfies (25) with gamma = (n-k)/n."""
    return (n - k) / n


# ---------------------------------------------------------------------------
# One-bit quantization (Seide et al.'14, Eq. 30)
# ---------------------------------------------------------------------------

def onebit_q(w: jax.Array) -> jax.Array:
    """[Q(w)]_i = mean of w over the sign class of i."""
    flat = w.reshape(-1).astype(jnp.float32)
    pos = flat >= 0
    n_pos = jnp.maximum(jnp.sum(pos), 1)
    n_neg = jnp.maximum(jnp.sum(~pos), 1)
    mean_pos = jnp.sum(jnp.where(pos, flat, 0.0)) / n_pos
    mean_neg = jnp.sum(jnp.where(~pos, flat, 0.0)) / n_neg
    return jnp.where(pos, mean_pos, mean_neg).reshape(w.shape).astype(w.dtype)


def onebit_compress(w: jax.Array):
    """Wire format: (sign bitmap packed into uint8, mean_pos, mean_neg)."""
    flat = w.reshape(-1)
    pos = (flat >= 0)
    pad = (-flat.size) % 8
    bits = jnp.pad(pos, (0, pad)).reshape(-1, 8)
    packed = jnp.sum(bits.astype(jnp.uint8)
                     * (2 ** jnp.arange(8, dtype=jnp.uint8)), axis=-1,
                     dtype=jnp.uint8)
    n_pos = jnp.maximum(jnp.sum(pos), 1)
    n_neg = jnp.maximum(jnp.sum(~pos), 1)
    flat32 = flat.astype(jnp.float32)
    mean_pos = jnp.sum(jnp.where(pos, flat32, 0.0)) / n_pos
    mean_neg = jnp.sum(jnp.where(~pos, flat32, 0.0)) / n_neg
    return packed, mean_pos, mean_neg


def onebit_decompress(packed, mean_pos, mean_neg, n: int, dtype=jnp.float32):
    bits = (packed[:, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    pos = bits.reshape(-1)[:n].astype(bool)
    return jnp.where(pos, mean_pos, mean_neg).astype(dtype)


def onebit_gamma(n: int) -> float:
    """One-bit quantization satisfies (25) with gamma = 1 - 1/d in the worst
    case (paper App. B.7)."""
    return 1.0 - 1.0 / n


# ---------------------------------------------------------------------------
# QSGD-style unbiased random quantization (Alistarh et al.'17)
# ---------------------------------------------------------------------------

def qsgd_q(w: jax.Array, key: jax.Array, levels: int = 4) -> jax.Array:
    """Stochastic uniform quantization to ``levels`` levels of |w|/||w||.
    Unbiased: E[Q(w)] = w."""
    flat = w.reshape(-1).astype(jnp.float32)
    norm = jnp.linalg.norm(flat) + 1e-30
    scaled = jnp.abs(flat) / norm * levels
    lower = jnp.floor(scaled)
    prob = scaled - lower
    rnd = jax.random.uniform(key, flat.shape)
    q = (lower + (rnd < prob)) / levels
    return (jnp.sign(flat) * q * norm).reshape(w.shape).astype(w.dtype)


# ---------------------------------------------------------------------------
# Error feedback (Algorithm 6)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Compressor:
    """Dense-form compressor with its contraction constant.

    ``kind``/``ratio`` let batched callers (the scan simulator engine) route
    the row-wise EF round through the fused EF kernels' dispatch instead of
    the dense ``q``; ``kind="custom"`` always takes the dense path.
    """

    q: Callable[[jax.Array], jax.Array]
    gamma: Callable[[int], float]
    name: str
    kind: str = "custom"          # topk | onebit | custom
    ratio: float = 0.0            # topk only


def topk_compressor(ratio: float) -> Compressor:
    def q(w):
        k = max(1, int(round(w.size * ratio)))
        return topk_q(w, k)

    return Compressor(q, lambda n: topk_gamma(n, max(1, int(round(n * ratio)))),
                      f"topk{ratio}", kind="topk", ratio=ratio)


def onebit_compressor() -> Compressor:
    return Compressor(onebit_q, onebit_gamma, "onebit", kind="onebit")


def ef_compress(comp: Compressor, update: jax.Array, err: jax.Array):
    """One error-feedback round (Alg 6 lines 2-4).

    update: alpha * gradient;  err: accumulated residual.
    Returns (payload Q(w), new_err)."""
    w = err + update
    payload = comp.q(w)
    return payload, w - payload


def ef_compress_rows(comp: Compressor, updates: jax.Array, errs: jax.Array,
                     impl: str = "auto"):
    """Batched error-feedback round: one row per worker.

    updates/errs: (p, d) — each row is an independent Alg-6 round. The
    topk/onebit compressors go through the fused EF kernels' dispatch
    (`repro.kernels.dispatch`: compiled on TPU where the shape fits, the
    row-wise jnp oracle otherwise; row-local selection == per-worker
    global selection since each worker is one row). Returns
    (payloads (p, d), new_errs (p, d)) with payload = Q(w), w = err + upd.
    """
    w = errs + updates.astype(jnp.float32)
    if comp.kind == "topk":
        from repro.kernels.topk_ef.ops import compress_leaf
        _, _, new_errs = compress_leaf(updates.astype(jnp.float32), errs,
                                       comp.ratio, impl=impl, payload=False)
        return w - new_errs, new_errs
    if comp.kind == "onebit" and w.shape[1] % 8 == 0:
        from repro.kernels.onebit_ef.ops import compress_leaf
        _, _, new_errs = compress_leaf(updates.astype(jnp.float32), errs,
                                       impl=impl)
        return w - new_errs, new_errs
    payloads = jax.vmap(comp.q)(w)
    return payloads, w - payloads

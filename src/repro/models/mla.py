"""Multi-head latent attention (MLA, DeepSeek-V2/V3; Moonlight-16B-A3B) for
training.

With ``q_lora_rank`` null, as Moonlight publishes it, per token x:

  q              = x W_q                       H heads of (nope + rope)
  [c, k_pe]      = x W_kv_a                    kv_lora_rank + rope
  [k_nope, v]    = RMSNorm(c) W_kv_b           H heads of (nope + v)
  q_pe, k_pe     <- RoPE (theta ``rope_theta``), the single k_pe shared by
                    every head
  o              = causal softmax((q . k) / sqrt(nope + rope)) v
  out            = o W_o

The rotary part follows ``modeling_deepseek``: the rope dims come as
interleaved (even, odd) pairs and are regrouped into (evens, odds) halves
before the half-split rotation of :func:`repro.models.layers.apply_rope`.

Attention is causal over query chunks of :data:`CHUNK`, each reading only
the keys up to its own end (static slices, so no masked-out block is
computed), and each chunk is rematerialised in the backward pass, so no
(S x S) score tensor is kept for it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.params import ParamDef

CHUNK = 512


def mla_defs(cfg) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    return {
        "wq": ParamDef((d, h, nope + rope), ("embed", "heads", None)),
        "wkv_a": ParamDef((d, r + rope), ("embed", None)),
        "kv_norm": ParamDef((r,), (None,), init="ones"),
        "wkv_b": ParamDef((r, h, nope + vd), (None, "heads", None)),
        "wo": ParamDef((h, vd, d), ("heads", None, "embed")),
    }


def _rope_pairs(x, positions, theta):
    """RoPE on interleaved pairs: regroup (..., 2i, 2i+1) into halves."""
    *lead, d = x.shape
    x = x.reshape(*lead, d // 2, 2).swapaxes(-1, -2).reshape(*lead, d)
    return L.apply_rope(x, positions, theta)


def _chunk(q, k, v, i: int, c: int, scale: float):
    """Queries i*c..(i+1)*c-1 against keys 0..(i+1)*c-1."""
    hi = (i + 1) * c
    qc = q[:, i * c:hi]
    b, _, h, dq = qc.shape
    out = L.masked_attn_chunk(
        qc.reshape(b, c, h, 1, dq), k[:, :hi], v[:, :hi],
        i * c + jnp.arange(c), jnp.arange(hi), 0, scale)
    return out.reshape(b, c, h, v.shape[-1]).astype(q.dtype)


_chunk_remat = jax.checkpoint(_chunk, static_argnums=(3, 4, 5))


def causal_attention(q, k, v, scale: float, chunk: int = CHUNK):
    """q, k (B, S, H, Dqk), v (B, S, H, Dv) -> (B, S, H, Dv)."""
    s = q.shape[1]
    c = min(chunk, s)
    if s % c:
        raise ValueError(f"sequence {s} is not a multiple of chunk {c}")
    return jnp.concatenate([_chunk_remat(q, k, v, i, c, scale)
                            for i in range(s // c)], axis=1)


@jax.named_scope("mla")
def mla_block(params, cfg, x, positions):
    """x (B, S, d) -> (B, S, d): the attention sub-block's output."""
    dt = x.dtype
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    kva = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"].astype(dt))
    c, k_pe = kva[..., :cfg.kv_lora_rank], kva[..., cfg.kv_lora_rank:]
    c = L.rmsnorm(c, params["kv_norm"], cfg.norm_eps)
    kv = jnp.einsum("bsr,rhk->bshk", c, params["wkv_b"].astype(dt))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rope_pairs(q[..., nope:], positions, cfg.rope_theta)
    k_pe = _rope_pairs(k_pe[:, :, None, :], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:3], rope))], axis=-1)
    o = causal_attention(q, k, v, (nope + rope) ** -0.5)
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))

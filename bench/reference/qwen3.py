"""Plain Qwen3 decoder (hf:Qwen/Qwen3-1.7B), training loss only.

Written from the published architecture, in float32 with every product
at ``Precision.HIGHEST``: token embedding; per layer RMSNorm, q/k/v
projections, per-head RMSNorm of q and k, rotary embedding (half-split,
theta ``rope_theta``), causal grouped-query softmax attention (query head
h reads kv head ``h // (heads / kv_heads)``), output projection and
residual, then RMSNorm, the SiLU-gated MLP and residual; final RMSNorm,
logits against the tied embedding, mean token cross-entropy.

``rnd.product`` rounds both operands of every matrix product
(projections, attention scores, attention-weighted values, the head)
before an exact float32 product, and its result after: identity for the
reference, float8 for the control. Its gradient passes straight through, so the
backward products see the rounded operands and float32 cotangents.

Parameters are read by key from the trainer's parameter tree:
``embed``, ``final_norm`` and per layer (stacked on a leading axis)
``ln_attn``, ``attn/{wq,wk,wv,wo,q_norm,k_norm}``, ``ln_mlp`` and
``mlp/{w_gate,w_up,w_down}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import common as C

# the published keys cut so that the tests run the cell on the CPU in
# seconds (bench/tests/tiny.py)
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512, "num_hidden_layers": 2}


def init(shapes, key):
    """Random weights of the tree ``shapes`` (ShapeDtypeStructs by key),
    drawn from ``key`` with a per-leaf key from the leaf's path."""

    def leaf(path, s):
        name = C.path_str(path)
        last = name.rsplit("/", 1)[-1]
        if last in ("final_norm", "ln_attn", "ln_mlp", "q_norm", "k_norm"):
            return jnp.ones(s.shape, s.dtype)
        k = C.leaf_key(key, name)
        if last == "embed":
            std = s.shape[-1] ** -0.5
        elif last == "wo":                       # (L, H, hd, d)
            std = (s.shape[1] * s.shape[2]) ** -0.5
        else:                                    # (L, fan_in, ...)
            std = s.shape[1] ** -0.5
        return (std * jax.random.normal(k, s.shape, jnp.float32)).astype(
            s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _rope(x, theta):
    """x (B, S, H, D): rotate the (first half, second half) pairs."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits_and_loss(params, tokens, labels, conf: dict, rnd=C.EXACT):
    """Mean cross-entropy of ``labels`` given ``tokens`` (B, S)."""
    eps = conf["rms_norm_eps"]
    heads, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    group = heads // kv
    mm = C.matmul(rnd.product)
    emb = params["embed"]
    x = emb[tokens]
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(conf["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        at = lp["attn"]
        h = C.rmsnorm(x, lp["ln_attn"], eps)
        q = C.rmsnorm(mm("bsd,dhk->bshk", h, at["wq"]), at["q_norm"], eps)
        k = C.rmsnorm(mm("bsd,dhk->bshk", h, at["wk"]), at["k_norm"], eps)
        v = mm("bsd,dhk->bshk", h, at["wv"])
        q = _rope(q, conf["rope_theta"])
        k = _rope(k, conf["rope_theta"])
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        sc = mm("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = mm("bhqk,bkhd->bqhd", p, v)
        x = x + mm("bshk,hkd->bsd", o, at["wo"])
        y = C.rmsnorm(x, lp["ln_mlp"], eps)
        m = lp["mlp"]
        gate = jax.nn.silu(mm("bsd,df->bsf", y, m["w_gate"]))
        up = mm("bsd,df->bsf", y, m["w_up"])
        x = x + mm("bsf,fd->bsd", gate * up, m["w_down"])
    x = C.rmsnorm(x, params["final_norm"], eps)
    logits = mm("bsd,vd->bsv", x, emb)
    return C.cross_entropy(logits, labels)

"""Plain Moonlight-16B-A3B (DeepSeek-V3 block) decoder, training loss only:
one chip's share of each MoE layer, as the configuration file states it.

Written from the published description
(hf:moonshotai/Moonlight-16B-A3B, ``modeling_deepseek``), in float32 with
every product at ``Precision.HIGHEST``: token embedding; per layer
RMSNorm, then latent attention (MLA) with ``q_lora_rank`` null:

  q = h W_q (heads of nope + rope);  [c, k_pe] = h W_kv_a;
  [k_nope, v] = RMSNorm(c) W_kv_b;  RoPE on q_pe and the one shared k_pe
  (the published interleaved pairs regrouped into halves, theta
  ``rope_theta``, no scaling); causal softmax of
  [q_nope, q_pe] . [k_nope, k_pe] / sqrt(nope + rope); heads of v; W_o;

residual; RMSNorm; then the first ``first_k_dense_replace`` layers a
SiLU-gated MLP of ``intermediate_size``, the others the MoE layer:

  s = sigmoid(h W_r) over all ``n_routed_experts_published`` experts, in
  float32; the top ``num_experts_per_tok`` of s + bias; weights s at
  those, normalised to sum 1, times ``routed_scaling_factor``; the routed
  part sum_i w_i down_i(silu(gate_i h) * up_i h) over the chosen experts
  held here (experts 0..n_routed_experts-1), each held expert computed
  densely over every token with its weight (zero where not chosen): no
  sort and no grouped product; plus the shared experts, one SiLU-gated
  MLP of n_shared_experts * moe_intermediate_size;

residual; final RMSNorm, the untied head, mean token cross-entropy.

Departures, as the program makes them (the configuration file lists them
under ``assumed``): the experts not held here are left out, in the layer's
output and its gradient alike (a chip's share of expert parallelism,
without the exchange); the correction bias is a fixed leaf (no
auxiliary-loss-free update) and there is no sequence aux loss.

``rnd.product`` rounds both operands and the result of every matrix
product, as in ``qwen3.py``; ``rnd.f32`` rounds the router's product,
which the program computes in float32 (identity for the reference,
bfloat16 for the control). Each layer is rematerialised in the backward
pass and attention runs over query blocks, so that the reference fits one
chip beside nothing else at 4,096 tokens a row.

Parameters are read by key from the trainer's tree: ``embed``,
``final_norm``, ``lm_head``, and per layer (stacked on a leading axis,
``dense_layers`` then ``layers``) ``ln_attn``,
``attn/{wq,wkv_a,kv_norm,wkv_b,wo}``, ``ln_mlp`` and
``mlp/{w_gate,w_up,w_down}`` or
``moe/{router,router_bias,w_gate,w_up,w_down,shared/{w_gate,w_up,w_down}}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import common as C

# the published keys cut so that the tests run the cell on the CPU in
# seconds (bench/tests/tiny.py)
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 4,
        "n_routed_experts_published": 16, "vocab_size": 512,
        "num_hidden_layers": 3}

_ONES = ("final_norm", "ln_attn", "ln_mlp", "kv_norm")
#: spread of the drawn correction bias: comparable to the gaps between
#: neighbouring sigmoid scores, so that it moves choices
BIAS_STD = 0.05
#: query rows per attention block
Q_BLOCK = 1024


def init(shapes, key):
    """Random weights of the tree ``shapes``, a per-leaf key from the
    leaf's path; each matrix drawn with std fan_in^-1/2."""

    def leaf(path, s):
        name = C.path_str(path)
        last = name.rsplit("/", 1)[-1]
        if last in _ONES:
            return jnp.ones(s.shape, s.dtype)
        k = C.leaf_key(key, name)
        if last == "router_bias":
            std = BIAS_STD
        elif last == "embed":                     # (V, d)
            std = s.shape[1] ** -0.5
        elif last == "lm_head":                   # (d, V)
            std = s.shape[0] ** -0.5
        elif last == "wo":                        # (L, H, v, d)
            std = (s.shape[1] * s.shape[2]) ** -0.5
        elif "/moe/" in name and last in ("w_gate", "w_up", "w_down"):
            std = s.shape[2] ** -0.5              # (L, E, fan_in, out)
        else:                                     # (L, fan_in, ...)
            std = s.shape[1] ** -0.5
        return (std * jax.random.normal(k, s.shape, jnp.float32)).astype(
            s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _rope(x, theta):
    """x (B, S, H, D) in the published interleaved-pair layout: regroup
    (x0, x1, x2, x3, ...) into (x0, x2, ... | x1, x3, ...), then rotate
    the halves (``modeling_deepseek.apply_rotary_pos_emb``)."""
    b, s, h, d = x.shape
    x = x.reshape(b, s, h, d // 2, 2).transpose(0, 1, 2, 4, 3).reshape(
        b, s, h, d)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _mla(at, h, conf, mm):
    heads = conf["num_attention_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    r = conf["kv_lora_rank"]
    q = mm("bsd,dhk->bshk", h, at["wq"])
    kva = mm("bsd,dr->bsr", h, at["wkv_a"])
    c = C.rmsnorm(kva[..., :r], at["kv_norm"], conf["rms_norm_eps"])
    kv = mm("bsr,rhk->bshk", c, at["wkv_b"])
    q_pe = _rope(q[..., nope:], conf["rope_theta"])
    k_pe = _rope(kva[..., None, r:], conf["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.repeat(k_pe, heads, axis=2)], -1)
    v = kv[..., nope:]
    s = h.shape[1]
    blk = min(Q_BLOCK, s)
    outs = []
    for lo in range(0, s, blk):
        sc = mm("bqhd,bkhd->bhqk", q[:, lo:lo + blk], k) \
            * (nope + rope) ** -0.5
        causal = (lo + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        outs.append(mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v))
    return mm("bshk,hkd->bsd", jnp.concatenate(outs, axis=1), at["wo"])


def _mlp(m, y, mm):
    gate = jax.nn.silu(mm("bsd,df->bsf", y, m["w_gate"]))
    return mm("bsf,fd->bsd", gate * mm("bsd,df->bsf", y, m["w_up"]),
              m["w_down"])


def route(m, y, conf, rnd=C.EXACT):
    """-> (chosen (B, S, k) expert ids, weights (B, S, k)), in float32."""
    logits = C.matmul(rnd.f32)("bsd,de->bse", y, m["router"])
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + m["router_bias"],
                              conf["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * conf["routed_scaling_factor"]


def _moe(m, y, conf, mm, rnd):
    chosen, w = route(m, y, conf, rnd)
    out = _mlp(m["shared"], y, mm) if conf["n_shared_experts"] else 0.0
    for e in range(conf["n_routed_experts"]):
        we = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        ex = {k: m[k][e] for k in ("w_gate", "w_up", "w_down")}
        out = out + we[..., None] * _mlp(ex, y, mm)
    return out


def _layer(lp, x, conf, rnd):
    mm = C.matmul(rnd.product)
    eps = conf["rms_norm_eps"]
    x = x + _mla(lp["attn"], C.rmsnorm(x, lp["ln_attn"], eps), conf, mm)
    y = C.rmsnorm(x, lp["ln_mlp"], eps)
    if "moe" in lp:
        return x + _moe(lp["moe"], y, conf, mm, rnd)
    return x + _mlp(lp["mlp"], y, mm)


_layer_remat = jax.checkpoint(_layer, static_argnums=(2, 3))


def _stacks(params):
    for stack in ("dense_layers", "layers"):
        n = jax.tree.leaves(params[stack])[0].shape[0]
        for i in range(n):
            yield jax.tree.map(lambda a: a[i], params[stack])


def _hashable(conf: dict) -> tuple:
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "kv_lora_rank", "rms_norm_eps", "rope_theta",
            "num_experts_per_tok", "routed_scaling_factor",
            "n_routed_experts", "n_shared_experts")
    return tuple((k, conf[k]) for k in keys)


class _Conf(dict):
    """A configuration that a static argument can hold (hashable)."""

    def __hash__(self):
        return hash(_hashable(self))

    def __eq__(self, other):
        return _hashable(self) == _hashable(other)


def logits_and_loss(params, tokens, labels, conf: dict, rnd=C.EXACT):
    """Mean cross-entropy of ``labels`` given ``tokens`` (B, S)."""
    conf = _Conf({k: conf[k] for k, _ in _hashable(conf)})
    x = params["embed"][tokens]
    for lp in _stacks(params):
        x = _layer_remat(lp, x, conf, rnd)
    x = C.rmsnorm(x, params["final_norm"], conf["rms_norm_eps"])
    logits = C.matmul(rnd.product)("bsd,dv->bsv", x, params["lm_head"])
    return C.cross_entropy(logits, labels)


def choices(params, tokens, conf: dict) -> list:
    """The experts each MoE layer chooses for ``tokens``, (B, S, k) a
    layer, as the reference computes them."""
    mm = C.matmul(C.EXACT.product)
    eps = conf["rms_norm_eps"]
    x = params["embed"][tokens]
    out = []
    for lp in _stacks(params):
        x = x + _mla(lp["attn"], C.rmsnorm(x, lp["ln_attn"], eps), conf, mm)
        y = C.rmsnorm(x, lp["ln_mlp"], eps)
        if "moe" in lp:
            out.append(route(lp["moe"], y, conf)[0])
            x = x + _moe(lp["moe"], y, conf, mm, C.EXACT)
        else:
            x = x + _mlp(lp["mlp"], y, mm)
    return out

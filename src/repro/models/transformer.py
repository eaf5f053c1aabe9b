"""Model assembly: embedding, scanned layer stacks, LM head, serve caches.

Supports four stack kinds driven by ``ArchConfig.block_type``:
  * ``attn``   — transformer blocks (attention + MLP/MoE), uniform window or
    gemma3-style grouped local:global pattern,
  * ``mamba2`` — Mamba2 SSD stack, optionally with a *shared* attention block
    every N layers (zamba2),
  * ``rwkv6``  — RWKV-6 stack.

Layers are stacked and iterated with ``lax.scan`` so the HLO stays compact
(we compile ~60 (arch x shape x mesh) artifacts on one host). Architectures
with a non-uniform per-layer attention window (gemma3) scan over *groups*
(one global + N-1 local layers unrolled inside the body) because the window
size is a static slicing parameter.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ArchConfig, BLOCK_ATTN, BLOCK_MAMBA2,
                                BLOCK_RWKV6, FRONTEND_AUDIO, FRONTEND_NONE,
                                FRONTEND_VISION)
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rwkv6 as R6
from repro.models import scan_utils as SU
from repro.models.params import ParamDef, stack_defs
from repro.models.layers import (COMPUTE_DTYPE, attention_block,
                                 attention_defs, mlp_block, mlp_defs, rmsnorm,
                                 rmsnorm_def)


@dataclass(frozen=True)
class RunFlags:
    """Execution policy knobs (orthogonal to the architecture)."""

    remat: bool = True                       # activation checkpoint each layer
    act_sharding: Any = None                 # NamedSharding for the residual
                                             # stream (sequence parallelism)
    kv_cache_dtype: Any = jnp.bfloat16
    compute_dtype: Any = COMPUTE_DTYPE      # float32: the reference forward


DEFAULT_FLAGS = RunFlags()


def _constrain(x, flags: RunFlags):
    if x.ndim != 3:
        return x
    if flags.act_sharding is not None:
        return jax.lax.with_sharding_constraint(x, flags.act_sharding)
    from repro.models.actx import constrain
    return constrain(x, "residual")


# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------

def _attn_layer_defs(cfg: ArchConfig, moe: bool) -> dict:
    d = {
        "ln_attn": rmsnorm_def(cfg.d_model),
        "attn": MLA.mla_defs(cfg) if cfg.is_mla else attention_defs(cfg),
        "ln_mlp": rmsnorm_def(cfg.d_model),
    }
    if moe:
        d["moe"] = MOE.moe_defs(cfg)
    else:
        d["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff)
    return d


def _mamba_layer_defs(cfg: ArchConfig) -> dict:
    return {"ln": rmsnorm_def(cfg.d_model), "mamba": M2.mamba2_defs(cfg)}


def model_defs(cfg: ArchConfig) -> dict:
    """Full ParamDef tree for an architecture."""
    d, v = cfg.d_model, cfg.vocab_size
    defs: dict = {
        "embed": ParamDef((v, d), ("vocab", "embed"), scale=d ** -0.5),
        "final_norm": rmsnorm_def(d),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, v), ("embed", "vocab"))

    n_layers = cfg.n_layers
    if cfg.block_type == BLOCK_ATTN:
        layer = _attn_layer_defs(cfg, cfg.is_moe)
        if cfg.first_k_dense:
            # the leading dense layers: a stack of their own before the
            # MoE stack (each stack scans one kind of layer)
            n_layers -= cfg.first_k_dense
            defs["dense_layers"] = stack_defs(_attn_layer_defs(cfg, False),
                                              cfg.first_k_dense)
    elif cfg.block_type == BLOCK_MAMBA2:
        layer = _mamba_layer_defs(cfg)
    elif cfg.block_type == BLOCK_RWKV6:
        layer = R6.rwkv6_defs(cfg)
    else:
        raise ValueError(cfg.block_type)
    defs["layers"] = stack_defs(layer, n_layers)

    if cfg.shared_attn_every:
        defs["shared_attn"] = {
            "ln_attn": rmsnorm_def(d),
            "attn": attention_defs(cfg),
            "ln_mlp": rmsnorm_def(d),
            "mlp": mlp_defs(d, cfg.d_ff),
        }
    return defs


# ---------------------------------------------------------------------------
# Embedding / frontends
# ---------------------------------------------------------------------------

def embed_input(cfg: ArchConfig, params, batch: dict,
                flags: RunFlags = DEFAULT_FLAGS) -> jax.Array:
    """Token/frontend embedding -> (B, S, d) in compute dtype.

    The audio/vision frontends are stubs per the brief: ``batch`` carries
    precomputed frame/patch embeddings of the right shape.
    """
    emb = params["embed"]
    if cfg.frontend == FRONTEND_AUDIO and "frame_embeds" in batch:
        return batch["frame_embeds"].astype(flags.compute_dtype)
    x = jnp.take(emb, batch["tokens"], axis=0).astype(flags.compute_dtype)
    if cfg.frontend == FRONTEND_VISION and "patch_embeds" in batch:
        p = batch["patch_embeds"].shape[1]
        x = jnp.concatenate(
            [batch["patch_embeds"].astype(flags.compute_dtype), x[:, p:]],
            axis=1)
    return x


@jax.named_scope("lm_head")
def lm_logits(cfg: ArchConfig, params, x: jax.Array) -> jax.Array:
    from repro.models.actx import constrain
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return constrain(jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype),
                                preferred_element_type=jnp.float32),
                     "logits")


# ---------------------------------------------------------------------------
# Attention stacks
# ---------------------------------------------------------------------------

def serving_refusal(cfg: ArchConfig) -> Optional[str]:
    """Why the serving paths (dense cache and paged) cannot run ``cfg``,
    or None: latent attention has no latent cache here."""
    if cfg.is_mla:
        return (f"{cfg.name}: latent attention (MLA) has no serving cache "
                "here (a latent paged cache is later work)")
    return None


def _check_servable(cfg: ArchConfig) -> None:
    why = serving_refusal(cfg)
    if why:
        raise NotImplementedError(why)


def _zero_stats(moe: bool) -> dict:
    """The per-layer statistics a stack carries: the router's aux loss, and
    for MoE layers the assignments to held experts and the largest
    per-expert load."""
    if moe:
        return {"aux_loss": 0.0, "moe_assigned": 0.0, "moe_max_load": 0.0}
    return {"aux_loss": 0.0}


def _add_stats(acc: dict, new: dict) -> dict:
    return {k: (jnp.maximum(v, new[k]) if k == "moe_max_load"
                else v + new[k]) for k, v in acc.items()}


def attn_half(cfg, flags, lp, x, positions, window, kv_cache=None,
               cache_index=None):
    """A block's attention and residual: (x, kv)."""
    y = rmsnorm(x, lp["ln_attn"], cfg.norm_eps)
    if cfg.is_mla:
        h, kv = MLA.mla_block(lp["attn"], cfg, y, positions), None
    else:
        h, kv = attention_block(
            lp["attn"], cfg, y, positions,
            window=window, kv_cache=kv_cache, cache_index=cache_index)
    return _constrain(x + h, flags), kv


def _attn_block_body(cfg, flags, lp, x, positions, window,
                     kv_cache=None, cache_index=None, collect_kv=False):
    """One transformer block. Returns (x, stats, kv)."""
    x, kv = attn_half(cfg, flags, lp, x, positions, window,
                       kv_cache=kv_cache, cache_index=cache_index)
    stats = {"aux_loss": jnp.zeros((), jnp.float32)}
    y = rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
    if "moe" in lp:
        out, stats = MOE.moe_block(lp["moe"], cfg, y)
    else:
        out = mlp_block(lp["mlp"], y)
    x = _constrain(x + out, flags)
    if not collect_kv and kv_cache is None:
        kv = None
    return x, stats, kv


def _maybe_remat(fn, flags: RunFlags):
    if flags.remat:
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.nothing_saveable)
    return fn


def _split_groups(cfg: ArchConfig):
    """gemma3 grouping: (n_groups, group, remainder_windows)."""
    g = cfg.global_every
    windows = cfg.layer_window_sizes()
    n_groups = cfg.n_layers // g
    rem = cfg.n_layers - n_groups * g
    return n_groups, g, windows[:g], windows[n_groups * g:]


def _tree_slice(tree, start, size):
    return jax.tree.map(lambda a: jax.lax.slice_in_dim(a, start, start + size,
                                                       axis=0), tree)


def _tree_index(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def attn_stack(cfg: ArchConfig, flags: RunFlags, stacked, x, positions,
               kv_caches=None, cache_index=None, collect_kv=False):
    """Run the full attention stack.

    stacked: layer params with leading L dim. kv_caches: None or
    (k (L,B,T,K,D), v (L,B,T,K,D)). Returns (x, stats, kv_out) where
    kv_out is stacked (L, ...) when collect_kv or decoding.
    """
    windows = cfg.layer_window_sizes()
    if not windows:
        # zero-layer variant (dry-run base-cost isolation): run the uniform
        # scan with trip count 0 so output structures (kv caches) survive.
        windows = [0]
    uniform = len(set(windows)) == 1
    zero = _zero_stats("moe" in stacked)

    def body_for(window):
        def body(carry, scanned):
            x, aux = carry
            if kv_caches is None:
                lp = scanned
                cache = None
            else:
                lp, cache = scanned
            x, a, kv = _attn_block_body(
                cfg, flags, lp, x, positions, window,
                kv_cache=cache, cache_index=cache_index,
                collect_kv=collect_kv)
            if kv is None:
                kv = ()
            return (x, _add_stats(aux, a)), kv
        return _maybe_remat(body, flags)

    if uniform:
        xs = stacked if kv_caches is None else (stacked, kv_caches)
        (x, aux), kvs = SU.scan(body_for(windows[0]), (x, zero), xs)
        return x, aux, kvs

    # grouped local:global pattern (gemma3): scan over groups of `g` layers
    # with the per-layer windows unrolled inside the body, plus a remainder.
    n_groups, g, group_windows, rem_windows = _split_groups(cfg)

    def reshape_groups(tree):
        return jax.tree.map(
            lambda a: a[: n_groups * g].reshape(n_groups, g, *a.shape[1:]),
            tree)

    def group_body(carry, scanned):
        x, aux = carry
        kvs = []
        for j, w in enumerate(group_windows):
            if kv_caches is None:
                lp = _tree_index(scanned, j)
                cache = None
            else:
                lp = _tree_index(scanned[0], j)
                cache = _tree_index(scanned[1], j)
            x, a, kv = _attn_block_body(
                cfg, flags, lp, x, positions, w, kv_cache=cache,
                cache_index=cache_index, collect_kv=collect_kv)
            aux = _add_stats(aux, a)
            kvs.append(kv if kv is not None else ())
        if kvs and kvs[0] != ():
            kvs = jax.tree.map(lambda *xs: jnp.stack(xs), *kvs)
        else:
            kvs = ()
        return (x, aux), kvs

    head_params = reshape_groups(_tree_slice(stacked, 0, n_groups * g))
    xs = head_params if kv_caches is None else (
        head_params, reshape_groups(_tree_slice(kv_caches, 0, n_groups * g)))
    (x, aux), kvs = SU.scan(_maybe_remat(group_body, flags), (x, zero), xs)
    if kvs != ():
        kvs = jax.tree.map(
            lambda a: a.reshape(n_groups * g, *a.shape[2:]), kvs)

    rem_kvs = []
    for j, w in enumerate(rem_windows):
        i = n_groups * g + j
        lp = _tree_index(stacked, i)
        cache = None if kv_caches is None else _tree_index(kv_caches, i)
        x, a, kv = _attn_block_body(
            cfg, flags, lp, x, positions, w, kv_cache=cache,
            cache_index=cache_index, collect_kv=collect_kv)
        aux = _add_stats(aux, a)
        rem_kvs.append(kv if kv is not None else ())
    if rem_kvs and rem_kvs[0] != ():
        rem_kvs = jax.tree.map(lambda *xs: jnp.stack(xs), *rem_kvs)
        kvs = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), kvs, rem_kvs)
    return x, aux, kvs


# ---------------------------------------------------------------------------
# SSM stacks (mamba2 / rwkv6), optional shared attention (zamba2)
# ---------------------------------------------------------------------------

def ssm_stack(cfg: ArchConfig, flags: RunFlags, params, x, positions,
              states=None, attn_caches=None, cache_index=None,
              collect_state=False):
    """Mamba2/RWKV6 stack; zamba2 additionally applies the shared attention
    block before layers 0, every, 2*every, ... (unrolled segments around
    scans so attention caches stay compact).

    states: None or stacked per-layer block states. attn_caches: None or
    (k, v) stacked over shared-attn invocations. Returns
    (x, aux, new_states, new_attn_caches).
    """
    stacked = params["layers"]
    is_rwkv = cfg.block_type == BLOCK_RWKV6

    def block(lp, x, st):
        if is_rwkv:
            delta, new_st = R6.rwkv6_block(lp, cfg, x, state=st)
            return _constrain(x + delta, flags), new_st
        h, new_st = M2.mamba2_block(
            lp["mamba"], cfg, rmsnorm(x, lp["ln"], cfg.norm_eps), state=st)
        return _constrain(x + h, flags), new_st

    def scan_segment(x, seg_params, seg_states):
        def body(carry, scanned):
            x = carry
            lp, st = scanned
            x, new_st = block(lp, x, st)
            return x, (new_st if (collect_state or states is not None) else ())
        body = _maybe_remat(body, flags)
        if seg_states is None:
            n = jax.tree.leaves(seg_params)[0].shape[0]
            seg_states = jax.tree.map(
                lambda _: None, jnp.zeros((n,)))  # placeholder
            # build explicit zero states so scan xs have uniform structure
            init = (R6.rwkv6_init_state(cfg, x.shape[0]) if is_rwkv
                    else M2.mamba2_init_state(cfg, x.shape[0]))
            seg_states = jax.tree.map(
                lambda a: jnp.zeros((n, *a.shape), a.dtype), init)
        x, new_states = SU.scan(body, x, (seg_params, seg_states))
        return x, new_states

    aux = jnp.zeros((), jnp.float32)
    if not cfg.shared_attn_every:
        st = states
        if st is None and not collect_state:
            pass
        x, new_states = scan_segment(x, stacked, states)
        return x, aux, new_states, ()

    # zamba2: the shared attention block runs before layers 0, every,
    # 2*every, ... Since its weights are SHARED, full segments (attn +
    # `every` mamba layers) are identical programs -> scan over segments
    # with the mamba params reshaped (n_full, every, ...); only the
    # remainder segment is unrolled. This keeps the 81-layer HLO at
    # ~one-segment size (the naive unrolled form took >25min to compile).
    every = cfg.shared_attn_every
    n_full = cfg.n_layers // every
    rem = cfg.n_layers - n_full * every
    sp = params["shared_attn"]
    want_state = collect_state or states is not None
    want_kv = collect_state or attn_caches is not None

    def attn_and_mlp(x, cache):
        h, kv = attention_block(
            sp["attn"], cfg, rmsnorm(x, sp["ln_attn"], cfg.norm_eps),
            positions, window=cfg.sliding_window,
            kv_cache=cache, cache_index=cache_index)
        x = _constrain(x + h, flags)
        x = _constrain(
            x + mlp_block(sp["mlp"], rmsnorm(x, sp["ln_mlp"], cfg.norm_eps)),
            flags)
        return x, kv

    def zero_states(n):
        init = (R6.rwkv6_init_state(cfg, x.shape[0]) if is_rwkv
                else M2.mamba2_init_state(cfg, x.shape[0]))
        return jax.tree.map(
            lambda a: jnp.zeros((n, *a.shape), a.dtype), init)

    def reshape_seg(tree, n, e):
        return jax.tree.map(
            lambda a: a[: n * e].reshape(n, e, *a.shape[1:]), tree)

    head = reshape_seg(_tree_slice(stacked, 0, n_full * every), n_full, every)
    head_states = (reshape_seg(_tree_slice(states, 0, n_full * every),
                               n_full, every) if states is not None
                   else reshape_seg(zero_states(n_full * every),
                                    n_full, every))

    def seg_body(carry, scanned):
        x, aux = carry
        seg_params, seg_states, seg_cache = scanned
        x, kv = attn_and_mlp(x, seg_cache if attn_caches is not None
                             else None)
        new_sts = []
        for j in range(every):
            x, st = block(_tree_index(seg_params, j), x,
                          _tree_index(seg_states, j) if states is not None
                          else None)
            new_sts.append(st if want_state else ())
        outs = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_sts)
                if want_state else (),
                kv if (kv is not None and want_kv) else ())
        return (x, aux), outs

    seg_caches = (_tree_slice(attn_caches, 0, n_full)
                  if attn_caches is not None else jnp.zeros((n_full, 0)))
    xs = (head, head_states, seg_caches)
    (x, aux), (seg_new_states, seg_kvs) = SU.scan(
        _maybe_remat(seg_body, flags), (x, aux), xs)
    new_states_parts = []
    if want_state and seg_new_states != ():
        new_states_parts.append(jax.tree.map(
            lambda a: a.reshape(n_full * every, *a.shape[2:]),
            seg_new_states))
    new_kvs = [seg_kvs] if (want_kv and seg_kvs != ()) else []

    if rem:
        cache = (_tree_index(attn_caches, n_full)
                 if attn_caches is not None else None)
        x, kv = attn_and_mlp(x, cache)
        if want_kv and kv is not None:
            new_kvs.append(jax.tree.map(lambda a: a[None], kv))
        seg_params = _tree_slice(stacked, n_full * every, rem)
        seg_states = (None if states is None
                      else _tree_slice(states, n_full * every, rem))
        x, seg_new = scan_segment(x, seg_params, seg_states)
        if want_state and seg_new != ():
            new_states_parts.append(seg_new)

    new_states = (jax.tree.map(lambda *xs: jnp.concatenate(xs),
                               *new_states_parts)
                  if new_states_parts else ())
    new_kv = (jax.tree.map(lambda *xs: jnp.concatenate(xs), *new_kvs)
              if new_kvs else ())
    return x, aux, new_states, new_kv


# ---------------------------------------------------------------------------
# Public API: forward / prefill / decode
# ---------------------------------------------------------------------------

def forward(cfg: ArchConfig, params, batch: dict,
            flags: RunFlags = DEFAULT_FLAGS):
    """Training forward: returns (logits (B,S,V) fp32, stats) with stats
    ``{"aux_loss"}``, and for MoE ``moe_assigned`` (assignments to held
    experts, summed over layers) and ``moe_max_load`` (the most rows one
    held expert got in any layer)."""
    x = embed_input(cfg, params, batch, flags)
    s = x.shape[1]
    positions = jnp.arange(s)
    if cfg.block_type == BLOCK_ATTN:
        if "dense_layers" in params:
            x, dense, _ = attn_stack(cfg, flags, params["dense_layers"], x,
                                     positions)
        x, aux, _ = attn_stack(cfg, flags, params["layers"], x, positions)
        if "dense_layers" in params:
            aux = dict(aux, aux_loss=aux["aux_loss"] + dense["aux_loss"])
    else:
        x, aux, _, _ = ssm_stack(cfg, flags, params, x, positions)
        aux = {"aux_loss": aux}
    return lm_logits(cfg, params, x), aux


def init_cache(cfg: ArchConfig, batch_size: int, max_len: int,
               flags: RunFlags = DEFAULT_FLAGS):
    """Zero-initialized serving cache (shape donor for decode dry-runs)."""
    _check_servable(cfg)
    hd = cfg.resolved_head_dim
    cache: dict = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.block_type == BLOCK_ATTN:
        kv_shape = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads, hd)
        cache["kv"] = (jnp.zeros(kv_shape, flags.kv_cache_dtype),
                       jnp.zeros(kv_shape, flags.kv_cache_dtype))
    else:
        init = (R6.rwkv6_init_state(cfg, batch_size)
                if cfg.block_type == BLOCK_RWKV6
                else M2.mamba2_init_state(cfg, batch_size))
        cache["state"] = jax.tree.map(
            lambda a: jnp.zeros((cfg.n_layers, *a.shape), a.dtype), init)
        if cfg.shared_attn_every:
            n_seg = -(-cfg.n_layers // cfg.shared_attn_every)
            kv_shape = (n_seg, batch_size, max_len, cfg.n_kv_heads, hd)
            cache["attn_kv"] = (jnp.zeros(kv_shape, flags.kv_cache_dtype),
                                jnp.zeros(kv_shape, flags.kv_cache_dtype))
    return cache


def prefill(cfg: ArchConfig, params, batch: dict, max_len: int,
            flags: RunFlags = DEFAULT_FLAGS):
    """Prefill: run the prompt, return (last-token logits (B,1,V), cache)."""
    _check_servable(cfg)
    x = embed_input(cfg, params, batch, flags)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.arange(s)
    cache = {"pos": jnp.asarray(s, jnp.int32)}
    if cfg.block_type == BLOCK_ATTN:
        x, _, kvs = attn_stack(cfg, flags, params["layers"], x, positions,
                               collect_kv=True)
        # pad caches out to max_len
        def pad(a):
            pad_len = max_len - a.shape[2]
            return jnp.pad(a, ((0, 0), (0, 0), (0, pad_len), (0, 0), (0, 0))
                           ).astype(flags.kv_cache_dtype)
        cache["kv"] = jax.tree.map(pad, kvs)
    else:
        x, _, states, kvs = ssm_stack(cfg, flags, params, x, positions,
                                      collect_state=True)
        cache["state"] = states
        if cfg.shared_attn_every:
            def pad(a):
                pad_len = max_len - a.shape[2]
                return jnp.pad(a, ((0, 0), (0, 0), (0, pad_len), (0, 0),
                                   (0, 0))).astype(flags.kv_cache_dtype)
            cache["attn_kv"] = jax.tree.map(pad, kvs)
    logits = lm_logits(cfg, params, x[:, -1:])
    return logits, cache


def decode_step(cfg: ArchConfig, params, cache: dict, tokens: jax.Array,
                flags: RunFlags = DEFAULT_FLAGS):
    """One decode step: tokens (B, 1) at position cache['pos'].

    Returns (logits (B,1,V), new_cache)."""
    _check_servable(cfg)
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(flags.compute_dtype)
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    new_cache = {"pos": pos + 1}
    if cfg.block_type == BLOCK_ATTN:
        x, _, kvs = attn_stack(cfg, flags, params["layers"], x, positions,
                               kv_caches=cache["kv"], cache_index=pos)
        new_cache["kv"] = kvs
    else:
        x, _, states, kvs = ssm_stack(
            cfg, flags, params, x, positions, states=cache["state"],
            attn_caches=cache.get("attn_kv"), cache_index=pos)
        new_cache["state"] = states
        if cfg.shared_attn_every:
            new_cache["attn_kv"] = kvs
    return lm_logits(cfg, params, x), new_cache

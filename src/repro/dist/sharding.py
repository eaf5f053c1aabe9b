"""Mesh-axis helpers and sharding-spec builders for the launch path.

Everything here is a pure function of (mesh axis sizes, array shapes): no
device state is touched, so the same builders serve the 1-device host mesh,
the 256-chip single pod and the 512-chip multi-pod mesh, and they are unit
testable without any mesh at all.

Conventions
-----------
  * ``data`` (and the outer ``pod`` axis on multi-pod meshes) are the
    data-parallel axes: batch dims shard over them, parameters are
    replicated over them (unless FSDP specs say otherwise),
  * ``model`` is the tensor-parallel axis: `repro.models.params` resolves
    which parameter dim it shards; the activation rules here mirror that
    choice at the canonical Megatron constraint points (`repro.models.actx`),
  * a dim is only ever sharded when its size divides the axis (XLA would
    pad otherwise, which the dry-run memory accounting must not hide).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def _is_spec(x) -> bool:
    return isinstance(x, P)


def axis_sizes(mesh) -> dict:
    """``{axis_name: size}`` for a mesh (the input `repro.models.params`
    spec resolution wants)."""
    return dict(mesh.shape)


def data_axes(mesh) -> tuple:
    """The data-parallel mesh axes, outermost first: ``("pod", "data")`` on
    multi-pod meshes, ``("data",)`` otherwise."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _data_size(mesh) -> int:
    sizes = axis_sizes(mesh)
    return math.prod(sizes[a] for a in data_axes(mesh))


def _model_ok(mesh, n: int) -> bool:
    m = axis_sizes(mesh).get("model", 1)
    return m > 1 and n >= m and n % m == 0


def named(mesh, spec_tree):
    """Map a tree of ``PartitionSpec`` to ``NamedSharding`` on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=_is_spec)


# ---------------------------------------------------------------------------
# batch / cache / optimizer-state specs
# ---------------------------------------------------------------------------

def batch_spec(mesh, global_batch: int) -> P:
    """Spec for a leading batch dim: sharded over the data axes when the
    global batch divides them, replicated otherwise (degenerate meshes)."""
    da = data_axes(mesh)
    if da and global_batch % _data_size(mesh) == 0:
        return P(da if len(da) > 1 else da[0])
    return P(None)


def _leading_batch_spec(mesh, leaf) -> P:
    b = leaf.shape[0] if leaf.ndim else 0
    head = tuple(batch_spec(mesh, b)) if leaf.ndim else ()
    return P(*(head + (None,) * (leaf.ndim - len(head))))


def batch_specs(cfg: ArchConfig, mesh, batch) -> dict:
    """Specs for a model-input batch dict (tokens/labels + frontend
    embeddings): dim 0 is the global batch, everything else replicated."""
    del cfg  # uniform rule: every input leads with the batch dim
    return jax.tree.map(lambda leaf: _leading_batch_spec(mesh, leaf), batch)


def cache_specs(cfg: ArchConfig, mesh, cache) -> dict:
    """Specs for a serve cache (`repro.models.transformer.init_cache`
    structure): ``pos`` replicated; kv caches (L, B, T, K, hd) shard batch
    over data and kv-heads over ``model`` when divisible; SSM states
    (L, B, ...) shard batch over data."""
    del cfg
    da = data_axes(mesh)
    dsize = _data_size(mesh)

    def kv_spec(leaf) -> P:
        _, b, _, k, _ = leaf.shape
        return P(None,
                 (da if len(da) > 1 else da[0]) if b % dsize == 0 else None,
                 None,
                 "model" if _model_ok(mesh, k) else None,
                 None)

    def state_spec(leaf) -> P:
        spec = [None] * leaf.ndim
        if leaf.ndim >= 2 and leaf.shape[1] % dsize == 0:
            spec[1] = da if len(da) > 1 else da[0]
        return P(*spec)

    out: dict = {}
    for key, sub in cache.items():
        if key == "pos":
            out[key] = P()
        elif key in ("kv", "attn_kv"):
            out[key] = jax.tree.map(kv_spec, sub)
        else:  # "state" (and any future per-layer recurrent state)
            out[key] = jax.tree.map(state_spec, sub)
    return out


def paged_cache_specs(cfg: ArchConfig, mesh, pool) -> P:
    """Spec for one paged KV pool (`repro.serve.paged_cache.init_page_pool`
    leaf, (L, P+1, page_size, K, hd)): kv-heads shard over ``model`` when
    divisible, everything else replicated.  The page dim is deliberately NOT
    sharded — the pool is one global resource indexed by per-request page
    tables, and sharding pages over data would turn every table gather into
    an all-to-all; replicating pages keeps gathers local (the serving
    analogue of the dense cache's replicated T dim)."""
    del cfg
    _, _, _, k, _ = pool.shape
    return P(None, None, None, "model" if _model_ok(mesh, k) else None, None)


# sync/async-state entries that are genuinely per-worker (one EF/residual
# accumulator per data shard) vs replicated scalars — see
# `dist.train.init_dist_sync_state` / `dist.async_engine.init_async_state`
# for the layouts.  RING keys additionally carry a delay-ring dim of size
# ``tau_max + 1`` between the worker dim and the param dims.
PER_WORKER_STATE_KEYS = ("err", "residual")
PER_WORKER_RING_KEYS = ("buf",)


def sync_state_specs(sync_state, pspecs, mesh) -> dict:
    """Specs for the distributed sync/async-state layouts
    (`dist.train.init_dist_sync_state`, `dist.async_engine.init_async_state`):
    per-worker entries shard their leading worker dim over the data axes
    (each shard holds only its own accumulator) and keep the param specs'
    ``model`` sharding on the trailing dims; ring entries replicate the ring
    dim between the two; everything else (step counters, tau schedule
    tables) replicates."""
    da = data_axes(mesh)
    head = da if len(da) > 1 else da[0]
    out = {}
    for key, val in sync_state.items():
        if key in PER_WORKER_RING_KEYS:
            out[key] = jax.tree.map(
                lambda spec: P(head, None, *tuple(spec)), pspecs,
                is_leaf=_is_spec)
        elif key in PER_WORKER_STATE_KEYS:
            out[key] = jax.tree.map(
                lambda spec: P(head, *tuple(spec)), pspecs, is_leaf=_is_spec)
        else:
            out[key] = jax.tree.map(lambda _: P(), val)
    return out


def replicated_specs(tree):
    """``P()`` for every leaf — the in-``shard_map`` spec of a replicated
    tree (params, optimizer state, scalar metrics)."""
    return jax.tree.map(lambda _: P(), tree)


def batch_shard_specs(tree, head):
    """Leading-dim-over-``head`` specs for a batch tree inside
    ``shard_map`` (shared by `dist.train` and `dist.async_engine` so the
    batch-sharding rule cannot drift between the two step builders)."""
    return jax.tree.map(
        lambda a: P(head, *((None,) * (a.ndim - 1))), tree)


def shard_state_specs(state, head) -> dict:
    """In-``shard_map`` specs for a per-worker state dict: entries named in
    the per-worker/ring key lists shard their leading worker dim over
    ``head`` (the manual data axes), the rest replicate.  Built per-leaf
    from ndim, so one builder serves every strategy/engine state layout
    (used by `dist.train` and `dist.async_engine`)."""
    worker_keys = PER_WORKER_STATE_KEYS + PER_WORKER_RING_KEYS
    return {key: (batch_shard_specs(val, head) if key in worker_keys
                  else replicated_specs(val))
            for key, val in state.items()}


def opt_state_specs(opt_state, pspecs):
    """Specs for an optimizer-state tree: entries that mirror the param tree
    (momentum ``mu``, Adam ``m``/``v``) inherit the param specs; scalars and
    anything else are replicated."""
    ptree = jax.tree.structure(pspecs, is_leaf=_is_spec)
    out = {}
    for key, val in opt_state.items():
        if jax.tree.structure(val) == ptree:
            out[key] = pspecs
        else:
            out[key] = jax.tree.map(lambda _: P(), val)
    return out


# ---------------------------------------------------------------------------
# activation rules (the `repro.models.actx` constraint points)
# ---------------------------------------------------------------------------

def make_act_rules(cfg: ArchConfig, mesh, *, batch_size: int, seq_len: int,
                   sequence_parallel: bool = False,
                   batch_axes: bool = True) -> dict:
    """kind -> ``NamedSharding`` rules for `repro.models.actx.constrain`.

    ``batch_axes=False`` drops the data axes from every rule — required when
    the forward runs *inside* a ``shard_map`` over the data axes (the batch
    dim is already local there and manual axes may not appear in
    ``with_sharding_constraint`` specs).
    """
    da = data_axes(mesh)
    dsize = _data_size(mesh)
    batch = (da if len(da) > 1 else da[0]) \
        if (batch_axes and da and batch_size % dsize == 0) else None

    def model_if(n: int):
        return "model" if _model_ok(mesh, n) else None

    heads = cfg.n_heads or 1
    seq = "model" if (sequence_parallel and _model_ok(mesh, seq_len)) else None
    rules = {
        # (B, S, d): sequence parallelism shards S over model between blocks
        "residual": P(batch, seq, None),
        # (B, S, ff)
        "ffn_hidden": P(batch, None, model_if(cfg.d_ff)),
        # (B, S, H, hd) / (B, S, K, hd)
        "attn_q": P(batch, None, model_if(heads), None),
        "attn_kv": P(batch, None, model_if(cfg.n_kv_heads or 1), None),
        # (B, S, V)
        "logits": P(batch, None, model_if(cfg.vocab_size)),
    }
    return {k: NamedSharding(mesh, s) for k, s in rules.items()}

"""Train/serve step builders for the launch path.

Two training paths share one loss:

  * :func:`make_train_step` — plain data parallelism: one jitted program,
    GSPMD inserts the gradient all-reduce. This is the perfectly-consistent
    baseline (the paper's synchronous model) and the per-arch smoke path.
  * :func:`make_elastic_train_step` — the paper's relaxed-consistency path:
    the forward/backward and optimizer run *inside* a ``shard_map`` over the
    data-parallel mesh axes, so each shard holds its LOCAL gradient and
    `repro.core.scheduler.sync_gradients` decides what actually crosses the
    wire (dense pmean, top-k/1-bit error feedback, or the elastic
    norm/static-gated partial sync). Tensor parallelism over the ``model``
    axis stays automatic (GSPMD) via the shard-map ``auto`` axes, so the same
    step builder serves the 1-device host mesh and the 256/512-chip meshes.

Serving is two thin builders over `repro.models.transformer`'s prefill /
decode_step with greedy sampling: :func:`make_prefill_step` and
:func:`make_decode_step` (used by ``repro.launch.serve`` and the decode
dry-run shapes).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.scheduler import SyncConfig, init_sync_state, sync_gradients
from repro.dist.sharding import (PER_WORKER_RING_KEYS, PER_WORKER_STATE_KEYS,
                                 batch_shard_specs, replicated_specs,
                                 shard_state_specs)
from repro.jax_compat import shard_map
from repro.models import transformer as TF
from repro.models import scan_utils as SU
from repro.optim import apply_updates, global_norm


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def loss_fn(cfg: ArchConfig, params, batch: dict,
            flags: TF.RunFlags = TF.DEFAULT_FLAGS):
    """Token-level cross entropy (+ weighted MoE router aux loss).

    Returns ``(loss, metrics)`` where metrics carries the unweighted parts
    and an MoE model's counters (`TF.forward`); differentiable in
    ``params`` (use with ``value_and_grad(has_aux=True)``).

    An optional ``batch["loss_scale"]`` (shape (B,), normally all-ones)
    multiplies the loss — the fault-injection channel: a NaN/Inf scale
    poisons every gradient leaf, which the ``skip_nonfinite`` guard must
    then reject (`repro.faults`).  Shaped (B,) rather than scalar so the
    batch stays uniformly shardable over the data axes.
    """
    logits, stats = TF.forward(cfg, params, batch, flags)
    with jax.named_scope("lm_head"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
        ce = jnp.mean(nll)
    loss = ce + cfg.router_aux_weight * stats["aux_loss"]
    if "loss_scale" in batch:
        loss = loss * jnp.mean(batch["loss_scale"].astype(jnp.float32))
    return loss, {"ce": ce, **stats}


def _value_and_grad(cfg, flags):
    return jax.value_and_grad(
        lambda p, b: loss_fn(cfg, p, b, flags), has_aux=True)


def _microbatch(batch, n: int):
    """(B, ...) -> (n, B//n, ...) for gradient accumulation."""
    return jax.tree.map(
        lambda a: a.reshape(n, a.shape[0] // n, *a.shape[1:]), batch)


@jax.named_scope("model_fwd_bwd")
def mean_grads(cfg, flags, params, batch, grad_accum: int):
    """Loss + mean gradient, optionally accumulated over ``grad_accum``
    microbatches with a ``lax.scan`` (keeps the HLO one-microbatch sized).
    Shared by every train-step builder here and in `dist.async_engine`."""
    vg = _value_and_grad(cfg, flags)
    if grad_accum <= 1:
        (loss, parts), grads = vg(params, batch)
        return loss, parts, grads

    micro = _microbatch(batch, grad_accum)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    parts_shape = jax.eval_shape(vg, params, jax.tree.map(lambda a: a[0],
                                                          micro))[0][1]
    init = (jnp.zeros(()), jax.tree.map(lambda _: jnp.zeros(()), parts_shape),
            zeros)

    def body(carry, mb):
        loss_acc, parts_acc, g_acc = carry
        (loss, parts), g = vg(params, mb)
        return (loss_acc + loss,
                jax.tree.map(lambda a, b: a + b, parts_acc, parts),
                jax.tree.map(lambda a, b: a + b, g_acc, g)), None

    (loss, parts, grads), _ = SU.scan(body, init, micro)
    inv = 1.0 / grad_accum
    return (loss * inv, jax.tree.map(lambda a: a * inv, parts),
            jax.tree.map(lambda g: g * inv, grads))


# ---------------------------------------------------------------------------
# non-finite gradient guard (fault tolerance)
# ---------------------------------------------------------------------------

def tree_all_finite(tree) -> jax.Array:
    """Scalar bool: every leaf of ``tree`` is finite everywhere."""
    checks = [jnp.all(jnp.isfinite(l)) for l in jax.tree.leaves(tree)]
    out = checks[0]
    for c in checks[1:]:
        out = jnp.logical_and(out, c)
    return out


@jax.named_scope("optimizer")
def guarded_update(opt, grads, opt_state, params, *, skip_nonfinite: bool):
    """Optimizer update with an optional skip-step guard: when
    ``skip_nonfinite`` and any gradient leaf is NaN/Inf, params and
    optimizer state pass through unchanged (the poisoned step is dropped,
    not applied).  Returns ``(params, opt_state, nonfinite)`` where
    ``nonfinite`` is the 0/1 skip indicator (a counter metric for the
    launcher).  With the guard off the program is exactly the unguarded
    update — no finiteness reduction is traced."""
    updates, new_opt = opt.update(grads, opt_state, params)
    new_params = apply_updates(params, updates)
    if not skip_nonfinite:
        return new_params, new_opt, jnp.zeros(())
    finite = tree_all_finite(grads)
    sel = lambda new, old: jnp.where(finite, new, old)  # noqa: E731
    return (jax.tree.map(sel, new_params, params),
            jax.tree.map(sel, new_opt, opt_state),
            1.0 - finite.astype(jnp.float32))


# ---------------------------------------------------------------------------
# training steps
# ---------------------------------------------------------------------------

def make_train_step(cfg: ArchConfig, opt, flags: TF.RunFlags = TF.DEFAULT_FLAGS,
                    grad_accum: int = 1, *, skip_nonfinite: bool = False):
    """Exact-sync step: ``(params, opt_state, batch) -> (params, opt_state,
    metrics)``. Pure single-program data parallelism — when the batch is
    sharded over the data axes, GSPMD inserts the dense gradient all-reduce
    (the BytePS-semantics baseline every relaxation is compared against).

    ``skip_nonfinite`` arms the :func:`guarded_update` skip-step guard and
    adds a ``nonfinite`` 0/1 metric; off (the default) the program is
    unchanged."""

    def step(params, opt_state, batch):
        loss, parts, grads = mean_grads(cfg, flags, params, batch, grad_accum)
        params, opt_state, nonfinite = guarded_update(
            opt, grads, opt_state, params, skip_nonfinite=skip_nonfinite)
        metrics = {"loss": loss, "grad_norm": global_norm(grads), **parts}
        if skip_nonfinite:
            metrics["nonfinite"] = nonfinite
        return params, opt_state, metrics

    return step


# strategy-state entries that hold one accumulator PER data shard (EF error,
# elastic residual, async delay rings) — everything else (step counters) is
# replicated; shared with `dist.sharding.sync_state_specs` so step layout
# and specs can't drift
_PER_WORKER_KEYS = PER_WORKER_STATE_KEYS + PER_WORKER_RING_KEYS


def squeeze_worker_dim(state: dict) -> dict:
    """Inside ``shard_map``: per-worker entries arrive as this shard's
    (1, ...) slice of the global worker-dim layout — drop the dim."""
    return {k: (jax.tree.map(lambda a: jnp.squeeze(a, 0), v)
                if k in _PER_WORKER_KEYS else v)
            for k, v in state.items()}


def add_worker_dim(state: dict) -> dict:
    """Inverse of :func:`squeeze_worker_dim` before leaving the shard_map."""
    return {k: (jax.tree.map(lambda a: a[None], v)
                if k in _PER_WORKER_KEYS else v)
            for k, v in state.items()}


def init_dist_sync_state(scfg: SyncConfig, mesh, params_like) -> dict:
    """Global layout of the strategy state consumed by
    :func:`make_elastic_train_step`.

    Error-feedback/residual accumulators are genuinely per-worker data
    (Alg 6 keeps one eps_i per worker), so those entries carry a leading
    worker dim of size prod(data axes) — globally the state IS p different
    residuals, and `dist.sharding.sync_state_specs` shards that dim over
    the data axes so each device stores only its own slice. Declaring them
    replicated instead would silently collapse all workers' residuals to
    device 0's copy on any host fetch or checkpoint.
    """
    base = init_sync_state(
        scfg, jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                           params_like))
    sizes = dict(mesh.shape)
    n = math.prod(sizes[a] for a in scfg.axis_names)
    return {k: (jax.tree.map(lambda a: jnp.zeros((n, *a.shape), a.dtype), v)
                if k in _PER_WORKER_KEYS else v)
            for k, v in base.items()}


def make_elastic_train_step(cfg: ArchConfig, opt, mesh, scfg: SyncConfig,
                            pspecs, flags: TF.RunFlags = TF.DEFAULT_FLAGS,
                            static_phase: int = 0, grad_accum: int = 1):
    """Relaxed-sync step: ``(params, opt_state, sync_state, batch) ->
    (params, opt_state, sync_state, metrics)``.

    ``sync_state`` must use the :func:`init_dist_sync_state` layout:
    per-worker accumulators carry a leading worker dim sharded over the data
    axes (truthful sharding — each shard's EF residual is distinct data).

    The body runs inside a ``shard_map`` whose manual axes are
    ``scfg.axis_names`` (the data-parallel axes): each shard computes the
    gradient of ITS batch slice, then ``sync_gradients`` runs the configured
    strategy's collectives by hand — that is what makes partial/compressed
    synchronization expressible at all (GSPMD would always emit the dense
    all-reduce). Remaining mesh axes (``model``) are left to the compiler, so
    ``pspecs``-sharded parameters keep their tensor parallelism; ``pspecs``
    is also what the compressed strategies use to compress only along
    non-model dims.

    ``static_phase`` is the compile-time phase for the elastic static gate
    (each phase is its own program so skipped buckets emit no collective).
    """
    manual = tuple(scfg.axis_names)
    auto = frozenset(a for a in mesh.axis_names if a not in manual
                     and dict(mesh.shape)[a] > 1)

    head = manual if len(manual) > 1 else manual[0]

    def local_step(params, opt_state, sync_state, batch):
        # jax 0.4.x: a while loop inside a partial-auto shard_map hits a
        # fatal XLA SPMD-partitioner check, so unroll the model scans
        # whenever auto (tensor-parallel) axes are present (see scan_utils)
        with SU.unrolled(bool(auto)):
            loss, parts, grads = mean_grads(cfg, flags, params, batch,
                                            grad_accum)
        # per-worker state arrives as this shard's (1, ...) slice of the
        # global worker-dim layout (init_dist_sync_state)
        local = squeeze_worker_dim(sync_state)
        synced, local, smetrics = sync_gradients(
            scfg, grads, local, specs=pspecs, static_phase=static_phase)
        sync_state = add_worker_dim(local)
        updates, opt_state = opt.update(synced, opt_state, params)
        params = apply_updates(params, updates)
        metrics = {
            "loss": jax.lax.pmean(loss, axis_name=manual),
            "gap2_over_alpha2": smetrics.get("gap2_over_alpha2",
                                             jnp.zeros(())),
        }
        return params, opt_state, sync_state, metrics

    def step(params, opt_state, sync_state, batch):
        # specs are built per-call from the actual arg trees, so one builder
        # serves every optimizer/strategy state layout
        in_specs = (replicated_specs(params), replicated_specs(opt_state),
                    shard_state_specs(sync_state, head),
                    batch_shard_specs(batch, head))
        out_specs = (replicated_specs(params), replicated_specs(opt_state),
                     shard_state_specs(sync_state, head),
                     {"loss": P(), "gap2_over_alpha2": P()})
        fn = shard_map(local_step, mesh, in_specs, out_specs,
                       check=False, auto=auto)
        return fn(params, opt_state, sync_state, batch)

    return step


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------

def _greedy(logits) -> jax.Array:
    """(B, 1, V) last-position logits -> (B,) int32 greedy tokens."""
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)


def make_prefill_step(cfg: ArchConfig, max_len: int,
                      flags: TF.RunFlags = TF.DEFAULT_FLAGS, sample=None):
    """``(params, batch) -> (tokens (B,), cache)``: run the prompt, allocate
    a ``max_len`` cache, emit the first continuation token.

    ``sample`` is an optional `repro.serve.sampling.SampleConfig`; None or a
    greedy config keeps the exact legacy signature, a sampled config makes
    the step ``(params, batch, key) -> ...``."""

    if sample is not None and not sample.is_greedy:
        from repro.serve.sampling import sample_tokens

        def sampled_prefill_step(params, batch, key):
            logits, cache = TF.prefill(cfg, params, batch, max_len, flags)
            return sample_tokens(logits[:, -1, :], sample, key), cache

        return sampled_prefill_step

    def prefill_step(params, batch):
        logits, cache = TF.prefill(cfg, params, batch, max_len, flags)
        return _greedy(logits), cache

    return prefill_step


def make_decode_step(cfg: ArchConfig, flags: TF.RunFlags = TF.DEFAULT_FLAGS,
                     sample=None):
    """``(params, cache, tokens (B, 1)) -> (tokens (B,), cache)``: one
    batched decode step at position ``cache['pos']`` (donate the cache — it
    is updated in place).

    ``sample`` as in :func:`make_prefill_step`: sampled configs add a
    trailing ``key`` argument, greedy/None keeps the legacy signature."""

    if sample is not None and not sample.is_greedy:
        from repro.serve.sampling import sample_tokens

        def sampled_decode_step(params, cache, tokens, key):
            logits, cache = TF.decode_step(cfg, params, cache, tokens, flags)
            return sample_tokens(logits[:, -1, :], sample, key), cache

        return sampled_decode_step

    def decode_step(params, cache, tokens):
        logits, cache = TF.decode_step(cfg, params, cache, tokens, flags)
        return _greedy(logits), cache

    return decode_step

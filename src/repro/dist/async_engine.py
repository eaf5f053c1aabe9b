"""Emulated-asynchrony trainer: bounded-staleness SGD on the real models.

The simulator (`repro.core.sim_engine`) exercises the paper's asynchronous
relaxations on the Quadratic testbed only; this module runs the same
bounded-delay delivery semantics against the *real* architectures, at the
same hot-path speed as the synchronous trainer (everything stays inside one
jitted program — asynchrony is emulated with device-resident state, never
with host-side threads).

Semantics (the bounded-delay model of §B.4 / "The Convergence of SGD in
Asynchronous Shared Memory", arXiv:1803.08841):

  * every step, every worker (data shard) computes a gradient at the
    *current* parameters and broadcasts it with a per-(step, worker) delay
    ``tau(t, w)`` drawn from an oblivious-adversary schedule
    (`repro.core.delivery.make_tau_schedule`), ``0 <= tau <= tau_max``;
  * the shared model applies, at step ``t``, exactly the messages whose
    delivery lands at ``t`` — a gradient produced at step ``s`` and applied
    at step ``t = s + tau`` *is* a stale gradient: it was computed at the
    ``tau``-steps-old iterate, which is what makes the emulation faithful
    without keeping parameter history;
  * gradients can be sparsified before "transmission" (top-k / one-bit with
    or without error feedback) — the combination the paper's headline
    empirical claim is about (EF may not help *asynchronous* sparsified
    SGD; see ``benchmarks/bench_async_ef.py``);
  * crashed workers (schedule entries of :data:`repro.core.delivery.DROPPED`)
    deliver nothing — their gradient mass is lost, like the simulator's
    crash model without substitution.

Delivery is realized one of two ways, selected by ``AsyncConfig.overlap``:

**Fused / overlapped path** (``overlap=True`` with a compressor — the
default): each worker's compact wire payload (top-k ``(vals, idx)`` or
one-bit ``(sign bitmap, means)`` from
`repro.core.scheduler.ef_compress_leaf_compact`) is all-gathered over the
data axes, and every gathered message is routed exactly once by
`delivery.delivery_plan`.  The step splits into two halves:

  * *consume-delivery half* — messages due now from EARLIER steps were
    decompressed into the dense *delivery-indexed* accumulator ring
    (``acc``, slot ``(s + tau) % capacity``) back when they arrived, so
    delivery is a take of slot ``t % capacity`` — a read of carried
    state, issued before the forward/backward and overlapped with it;
  * *launch-reduce half* — the fresh payload's all-gather is issued as
    soon as the backward finishes, and the WHOLE gathered panel is
    deposited by one fused masked decompress-scatter
    (`repro.kernels.cr_reduce` deposit ops: every live message lands in
    its slot, weights folding the aliveness mask).  ``tau == 0``
    self-deliveries land in the freshly-zeroed slot ``t % capacity`` and
    are taken right back, so delivery costs exactly one panel scatter
    per step regardless of ``tau_max``, and the collective's latency
    hides behind the optimizer and the NEXT step's forward/backward.

Compressed payloads therefore never round-trip through a dense ``pmean``:
the wire is the compact all-gather (the jaxpr audit's
``bytes_on_wire_async_tau*`` rows now sit ~8x below dense sync at
ratio 1/8, pinned by the golden inventory).

**Densified path** (``compressor="none"``, or ``overlap=False`` as the
escape hatch / trajectory reference): per-worker fixed-capacity delay
rings of dense f32 payloads (capacity ``tau_max + 1``), deposit at
``(t + tau) % cap``, take at ``t % cap``, one full-width ``pmean`` of the
taken slot.  The take is double-buffered — messages from earlier steps
are consumed before the fresh deposit, the ``tau == 0`` remainder after —
which is bitwise the single-take slot content (the dense wire cannot be
split into two collectives without doubling its bytes, so the dense path
keeps exactly the synchronous all-reduce volume).

Both paths deliver the same per-step mass, so their trajectories match
step for step (``tests/test_dist_parity.py``); with ``tau_max = 0`` every
message is delivered in the step it was produced and the engine reduces
exactly to synchronous data-parallel SGD — the parity tests pin it
against :func:`repro.dist.train.make_train_step` bitwise.

Like :func:`repro.dist.train.make_elastic_train_step`, the step body runs
inside a ``shard_map`` manual over the data axes with the ``model`` axis
left to GSPMD, so tensor parallelism is untouched.  (Caveat shared with
the compressed sync strategies: jax-0.4.x's SPMD partitioner rejected
``all_gather`` under partial-auto shard_map on tensor-parallel meshes, and
that has not been retested on jax 0.9, so the launcher keeps the fused
path to ``model == 1``; ``overlap=False`` keeps compressed async
available on those meshes.)
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import delivery as DLV
from repro.core.scheduler import (ef_compress_leaf, ef_compress_leaf_compact,
                                  leaf_rows_geometry, _from_rows)
from repro.dist.sharding import (batch_shard_specs, replicated_specs,
                                 shard_state_specs)
from repro.dist.train import (add_worker_dim, guarded_update, mean_grads,
                              squeeze_worker_dim, tree_all_finite)
from repro.jax_compat import shard_map
from repro.kernels.cr_reduce import ops as CR
from repro.models import transformer as TF
from repro.models import scan_utils as SU


@dataclass(frozen=True)
class AsyncConfig:
    """Knobs of the emulated-asynchrony engine.

    ``horizon`` is the length of the pre-drawn tau schedule table; steps
    beyond it wrap around (set it >= the planned step count for faithful
    crash schedules).

    ``overlap`` selects the fused compress-then-reduce delivery (compact
    payload rings + `kernels.cr_reduce`; see the module docstring).  It
    only changes the program when a compressor is configured — dense
    delivery is a single ``pmean`` either way — and never changes the
    trajectory, only how/when the reduction runs.
    """

    tau_max: int = 0              # staleness bound (0 == synchronous)
    schedule: str = "uniform"     # repro.core.delivery.TAU_SCHEDULES
    axis_names: tuple = ("data",)
    compressor: str = "none"      # none | topk | onebit
    error_feedback: bool = True   # EF residuals (only with a compressor)
    topk_ratio: float = 1.0 / 64.0
    horizon: int = 1024           # tau schedule table length
    seed: int = 0                 # schedule RNG (oblivious adversary)
    track_gap: bool = True        # stale_gap2 metric costs a 2nd pmean
    crash_subst: bool = False     # renormalize dead-worker mass (see below)
    skip_nonfinite: bool = False  # drop NaN/Inf gradients + skip the step
    overlap: bool = True          # fused compress-then-reduce delivery
    kernel_impl: str = "auto"     # cr_reduce dispatch: auto | kernel | ref

    @property
    def capacity(self) -> int:
        """Delay-ring capacity: a message delayed by ``tau <= tau_max``
        is always consumed (densified rings) or still resident (payload
        rings) when its delivery step arrives."""
        return self.tau_max + 1

    @property
    def has_err(self) -> bool:
        return self.compressor != "none" and self.error_feedback

    @property
    def fused(self) -> bool:
        """The overlapped compact-payload delivery path is active."""
        return self.overlap and self.compressor != "none"


def _acc_rings_like(acfg: AsyncConfig, params_like, pspecs):
    """Zeroed (cap, M, R) delivery-indexed accumulator rings, per leaf."""
    cap = acfg.capacity
    flat_p, treedef = jax.tree.flatten(params_like)
    flat_s = treedef.flatten_up_to(pspecs)
    rings = [jnp.zeros((cap,) + leaf_rows_geometry(jnp.shape(a), sp)[:2],
                       jnp.float32) for a, sp in zip(flat_p, flat_s)]
    return jax.tree.unflatten(treedef, rings)


def init_async_state(acfg: AsyncConfig, mesh, params_like,
                     pspecs=None) -> dict:
    """Global layout of the state consumed by :func:`make_async_train_step`.

    Densified path: ``buf`` (the stale-gradient delay rings) and ``err``
    (EF residuals, only when compressing with error feedback) lead with a
    worker dim of size prod(data axes) — per-worker data, sharded over the
    data axes by `dist.sharding.sync_state_specs` exactly like
    ``init_dist_sync_state``'s accumulators.

    Fused path (``acfg.fused``; requires ``pspecs`` for the row-space
    payload geometry): ``acc`` holds the dense delivery-indexed
    accumulator rings of *gathered* messages — (cap, M, R) f32 per leaf,
    the same on every worker (each worker has received and decompressed
    every message), so the entries are replicated, not worker-sharded.
    In a real deployment this is each worker's local stale-gradient
    accumulator fed by received compressed messages; the emulation pays
    the replication to keep everything in one SPMD program.  ``err``
    stays per-worker.

    ``taus`` is the replicated (horizon, n_workers) delay table; ``step``
    the replicated step counter.
    """
    if acfg.schedule not in DLV.TAU_SCHEDULES:
        raise ValueError(f"unknown schedule {acfg.schedule!r}")
    sizes = dict(mesh.shape)
    n = math.prod(sizes[a] for a in acfg.axis_names)
    state = {
        "step": jnp.zeros((), jnp.int32),
        "taus": jnp.asarray(DLV.make_tau_schedule(
            acfg.schedule, n, acfg.horizon, acfg.tau_max, acfg.seed)),
    }
    if acfg.fused:
        if pspecs is None:
            raise ValueError(
                "the fused (overlap) path sizes its delivery accumulator "
                "rings from the param PartitionSpecs — pass pspecs, or "
                "set overlap=False")
        state["acc"] = _acc_rings_like(acfg, params_like, pspecs)
    else:
        state["buf"] = jax.tree.map(
            lambda a: jnp.zeros((n, acfg.capacity, *a.shape), jnp.float32),
            params_like)
    if acfg.has_err:
        state["err"] = jax.tree.map(
            lambda a: jnp.zeros((n, *a.shape), jnp.float32), params_like)
    return state


def make_async_train_step(cfg: ArchConfig, opt, mesh, acfg: AsyncConfig,
                          pspecs, flags: TF.RunFlags = TF.DEFAULT_FLAGS,
                          grad_accum: int = 1):
    """Bounded-staleness step: ``(params, opt_state, async_state, batch) ->
    (params, opt_state, async_state, metrics)``.

    ``async_state`` must use the :func:`init_async_state` layout.  Metrics:
    ``loss`` (mean over workers), ``stale_gap2`` (||applied - fresh mean
    gradient||^2 — zero when ``tau_max == 0``, the engine's realized
    staleness gap), ``mean_tau`` (mean effective delay this step) and
    ``nonfinite`` (0/1: the step was skipped by the non-finite guard).
    The gap needs a second full-gradient pmean, so it is only computed when
    ``acfg.track_gap`` — turn it off to keep the hot path at exactly the
    configured wire volume (the metric then reports 0).

    Fault tolerance (both off by default — the hot path is byte-identical
    to the unguarded program):

      * ``acfg.crash_subst`` — the paper's crash-with-substitution
        semantics as mass *renormalization*: the mean divides by all ``n``
        workers even when crashed/delayed workers delivered nothing, so
        dead mass shrinks the effective step and a fully-crashed step still
        "applies" a zero gradient.  With the flag on, the applied mean is
        rescaled by ``n / delivered(t)`` (computable from the replicated
        tau table alone — the adversary is oblivious), so surviving
        workers' gradients carry full weight and training continues at the
        intended step size instead of stalling; ``delivered(t) == 0`` steps
        apply nothing.
      * ``acfg.skip_nonfinite`` — a worker whose local gradient has NaN/Inf
        leaves transmits *zeros* (its mass is dropped for that step, like a
        one-step crash — EF residuals keep draining but never absorb the
        poison), and the optimizer update is additionally guarded by
        `repro.dist.train.guarded_update` so a poisoned mean never reaches
        the params.
    """
    from jax.sharding import PartitionSpec as P

    manual = tuple(acfg.axis_names)
    sizes = dict(mesh.shape)
    auto = frozenset(a for a in mesh.axis_names if a not in manual
                     and sizes[a] > 1)
    head = manual if len(manual) > 1 else manual[0]
    cap = acfg.capacity

    flat_specs = None
    geoms = None

    def _leaf_specs(grads):
        nonlocal flat_specs
        flat_g, treedef = jax.tree.flatten(grads)
        if flat_specs is None:
            flat_specs = treedef.flatten_up_to(pspecs)
        return flat_g, treedef

    @jax.named_scope("compress")
    def _compress_dense(grads, err):
        flat_g, treedef = _leaf_specs(grads)
        flat_e = treedef.flatten_up_to(err)
        outs = [ef_compress_leaf(g, e, sp, acfg.compressor, acfg.topk_ratio)
                for g, e, sp in zip(flat_g, flat_e, flat_specs)]
        return (jax.tree.unflatten(treedef, [o[0] for o in outs]),
                jax.tree.unflatten(treedef, [o[1] for o in outs]))

    def pmean(tree):
        return jax.tree.map(
            lambda a: jax.lax.pmean(a.astype(jnp.float32), axis_name=manual),
            tree)

    def _gather(x):
        """Wire: all-gather one compact payload array over the data axes
        -> (n, ...) in worker order (matches the tau-table columns)."""
        g = jax.lax.all_gather(x, axis_name=manual, tiled=False)
        return g.reshape(-1, *x.shape)

    def _crash_subst_scale(tab, step):
        # delivered(t): how many messages land this step, read off the
        # replicated tau table (a message from step t-d with tau == d
        # arrives now).  Static unroll over the d <= tau_max window.
        horizon = tab.shape[0]
        cnt = jnp.zeros((), jnp.float32)
        for d in range(cap):
            src = step - d
            cnt += jnp.sum(((tab[src % horizon] == d) & (src >= 0))
                           .astype(jnp.float32))
        n_total = jnp.float32(tab.shape[1])
        return jnp.where(cnt > 0, n_total / cnt, 0.0)

    def _deposit(acc, panel, w_live, slots):
        """Fused masked decompress-deposit of the whole gathered panel:
        every live message is decompressed ONCE, straight into its
        delivery-indexed accumulator slot, by a single scatter
        (`kernels.cr_reduce` deposit ops — a zero weight makes a DROPPED
        message a no-op).  Never a collective."""
        if acfg.compressor == "topk":
            return CR.topk_deposit(acc, panel["vals"], panel["idx"],
                                   slots, w_live, impl=acfg.kernel_impl)
        return CR.onebit_deposit(acc, panel["pos"], panel["means"],
                                 slots, w_live, impl=acfg.kernel_impl)

    def local_step(params, opt_state, state, batch):
        nonlocal geoms
        local = squeeze_worker_dim(state)
        step = local["step"]
        tab = local["taus"]
        n_total = jnp.float32(tab.shape[1])

        if acfg.fused:
            # ---- consume-delivery half (state-only): every message due
            # now from EARLIER steps was decompressed into the
            # delivery-indexed accumulator when it arrived, so delivery
            # is a take of slot t % cap — issued before the
            # forward/backward, it overlaps the compute; no collective,
            # and each message was decompressed exactly once.
            w_live, slots = DLV.delivery_plan(tab, step, cap)
            flat_p, treedef = _leaf_specs(params)
            if geoms is None:
                geoms = [leaf_rows_geometry(p.shape, sp)
                         for p, sp in zip(flat_p, flat_specs)]
            prior_rows, accs = [], []
            with jax.named_scope("deliver"):
                for acc in treedef.flatten_up_to(local["acc"]):
                    prior_rows.append(acc[step % cap])
                    accs.append(acc.at[step % cap].set(0.0))
        else:
            # densified rings, double-buffered take: consume earlier
            # steps' deliveries before the fresh deposit lands
            with jax.named_scope("deliver"):
                prior, buf = DLV.tree_ring_take(local["buf"], step % cap)

        # ---- compute half -------------------------------------------------
        # jax 0.4.x partial-auto shard_map: unroll model scans (scan_utils)
        with SU.unrolled(bool(auto)):
            loss, _parts, grads = mean_grads(cfg, flags, params, batch,
                                             grad_accum)

        # this worker's delay for the gradient it just produced
        widx = jnp.int32(0)
        for a in manual:
            widx = widx * sizes[a] + jax.lax.axis_index(a)
        tau = tab[step % tab.shape[0], widx]
        alive = (tau >= 0).astype(jnp.float32)     # DROPPED == crashed
        d_eff = jnp.clip(tau, 0, acfg.tau_max)

        # poisoned local gradient -> transmit nothing (a one-step crash);
        # zeroing BEFORE compression keeps the EF residual finite forever
        if acfg.skip_nonfinite:
            g_finite = tree_all_finite(grads)
            grads = jax.tree.map(
                lambda g: jnp.where(g_finite, g, jnp.zeros_like(g)), grads)
            local_bad = 1.0 - g_finite.astype(jnp.float32)
        else:
            local_bad = jnp.zeros(())

        if acfg.fused:
            flat_g, treedef = _leaf_specs(grads)
            err_tree = local["err"] if acfg.has_err else jax.tree.map(
                lambda g: jnp.zeros_like(g, jnp.float32), grads)
            flat_e = treedef.flatten_up_to(err_tree)

            new_accs, new_errs, delivered = [], [], []
            for g, e, sp, geom, acc, prior in zip(
                    flat_g, flat_e, flat_specs, geoms, accs, prior_rows):
                # local sparsification to the compact wire form
                with jax.named_scope("compress"):
                    payload, new_err = ef_compress_leaf_compact(
                        g, e, sp, acfg.compressor, acfg.topk_ratio,
                        impl=acfg.kernel_impl)
                new_errs.append(new_err)
                # ---- launch-reduce half: the wire is this all-gather of
                # the compact payload; ONE fused scatter deposits every
                # live message into its slot — tau == 0 self-deliveries
                # land in the just-zeroed slot t and are taken right back
                with jax.named_scope("deliver"):
                    gathered = {key: _gather(v)
                                for key, v in payload.items()}
                    acc = _deposit(acc, gathered, w_live, slots)
                    delivered.append(prior + acc[step % cap])
                    new_accs.append(acc.at[step % cap].set(0.0))
            local["acc"] = jax.tree.unflatten(treedef, new_accs)
            if acfg.has_err:
                local["err"] = jax.tree.unflatten(treedef, new_errs)
            scale = 1.0 / n_total
            if acfg.crash_subst:
                scale = scale * _crash_subst_scale(tab, step)
            synced = jax.tree.unflatten(treedef, [
                _from_rows(rows * scale, geom[2], geom[3])
                for rows, geom in zip(delivered, geoms)])
        else:
            # local sparsification before "transmission"
            if acfg.compressor != "none":
                err = local["err"] if acfg.has_err else jax.tree.map(
                    lambda g: jnp.zeros_like(g, jnp.float32), grads)
                payload, new_err = _compress_dense(grads, err)
                if acfg.has_err:
                    local["err"] = new_err
            else:
                payload = jax.tree.map(lambda g: g.astype(jnp.float32),
                                       grads)

            # fresh payload lands tau steps ahead; the own-step (tau == 0)
            # remainder joins the pre-consumed deliveries — bitwise the
            # single-take slot content, one full-width pmean either way
            with jax.named_scope("deliver"):
                buf = DLV.tree_ring_deposit(
                    buf, (step + d_eff) % cap,
                    jax.tree.map(lambda v: v * alive, payload))
                own, buf = DLV.tree_ring_take(buf, step % cap)
            local["buf"] = buf
            stale = jax.tree.map(lambda a, b: a + b, prior, own)

            # the shared model applies the mean of everything delivered at t
            synced = pmean(stale)
            if acfg.crash_subst:
                s = _crash_subst_scale(tab, step)
                synced = jax.tree.map(lambda a: a * s, synced)

        if acfg.track_gap:
            fresh = pmean(grads)
            gap2 = sum(jnp.sum(jnp.square(a - b)) for a, b in
                       zip(jax.tree.leaves(synced), jax.tree.leaves(fresh)))
        else:
            gap2 = jnp.zeros(())

        params, opt_state, _skipped = guarded_update(
            opt, synced, opt_state, params,
            skip_nonfinite=acfg.skip_nonfinite)
        local["step"] = step + 1
        metrics = {
            "loss": jax.lax.pmean(loss, axis_name=manual),
            "stale_gap2": gap2,
            "mean_tau": jax.lax.pmean(d_eff.astype(jnp.float32),
                                      axis_name=manual),
            # fraction of workers whose local gradient was poisoned this
            # step (the launcher's skipped-step counter); the delivered
            # mean itself is re-guarded above
            "nonfinite": jax.lax.pmean(local_bad, axis_name=manual),
        }
        return params, opt_state, add_worker_dim(local), metrics

    def step(params, opt_state, state, batch):
        in_specs = (replicated_specs(params), replicated_specs(opt_state),
                    shard_state_specs(state, head),
                    batch_shard_specs(batch, head))
        out_specs = (replicated_specs(params), replicated_specs(opt_state),
                     shard_state_specs(state, head),
                     {"loss": P(), "stale_gap2": P(), "mean_tau": P(),
                      "nonfinite": P()})
        fn = shard_map(local_step, mesh, in_specs, out_specs,
                       check=False, auto=auto)
        return fn(params, opt_state, state, batch)

    return step

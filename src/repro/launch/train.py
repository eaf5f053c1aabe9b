"""End-to-end training driver (CPU-scale; same code path as the pod).

Runs a reduced (or full, on real hardware) architecture with a real data
pipeline, optimizer, checkpointing and any gradient-sync strategy:

  python -m repro.launch.train --arch qwen3-1.7b-smoke --steps 200 \
      --sync elastic --devices 8 --ckpt-dir /tmp/ckpt

``--devices N`` forces N host (CPU) devices (set before jax initializes)
so the data-parallel sync strategies are exercised with real cross-shard
traffic; without it the mesh spans every device JAX finds.

``--profile-dir DIR --profile-steps A:B`` writes a profiler trace of steps
A..B-1 under DIR, and the compiled step's HLO text as ``DIR/step.hlo.txt``.
Each step is a ``train_step`` step annotation holding the host spans
``batch.device_put``, ``step.call``, ``loss.fetch`` and ``ckpt.save``
(the data pipeline adds ``pipeline.batch``); the step's device ops carry
the named scopes of ``repro.dist``. Reduce the trace with
``python3 bench/trace_reduce.py <trace> train_step`` (busy, idle gaps) and
``python3 -m bench.scopes DIR`` (device time per scope). A profiled run
compiles its programs afresh rather than from the persistent cache.

:func:`train` is the trainer itself: it takes parsed arguments and an
``ArchConfig``, so a caller in the same process (``chip_smoke.py``) can
drive a depth-cut configuration through the same code as the CLI.
"""
import argparse
import os
import time
from dataclasses import dataclass, field
from typing import Any


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b-smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--sync", default="exact",
                    choices=["exact", "topk_ef", "onebit_ef", "elastic",
                             "async"])
    ap.add_argument("--beta", type=float, default=0.9)
    ap.add_argument("--budget-b", type=float, default=0.0)
    ap.add_argument("--topk-ratio", type=float, default=1 / 16)
    # --sync async: the bounded-staleness engine (repro.dist.async_engine)
    ap.add_argument("--tau-max", type=int, default=4)
    ap.add_argument("--async-schedule", default="uniform",
                    choices=["constant", "uniform", "roundrobin",
                             "straggler", "crash", "rejoin"])
    ap.add_argument("--compressor", default="none",
                    choices=["none", "topk", "onebit"])
    ap.add_argument("--ef", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="error feedback for --compressor (async path)")
    ap.add_argument("--crash-subst", action="store_true",
                    help="async: renormalize dead-worker mass so survivors "
                         "keep the full step size (paper crash_subst)")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="async + --compressor: the fused overlapped "
                         "compress-then-reduce delivery (compact wire); "
                         "--no-overlap keeps the densified sync-wire "
                         "delivery (also the fallback when tensor "
                         "parallelism is on)")
    # fault injection (repro.faults): a plan path or inline JSON; the
    # supervisor forwards --fault-attempt so kill events fire exactly once
    ap.add_argument("--fault-plan", default="")
    ap.add_argument("--fault-attempt", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--model-shards", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile-dir", default="",
                    help="write a profiler trace of --profile-steps here")
    ap.add_argument("--profile-steps", type=profile_steps, default=None,
                    metavar="A:B", help="trace steps A..B-1")
    args = ap.parse_args(argv)
    if bool(args.profile_dir) != (args.profile_steps is not None):
        ap.error("--profile-dir and --profile-steps go together")
    return args


def profile_steps(text: str) -> tuple:
    """``"A:B"`` -> ``(A, B)``, the steps A..B-1 to trace."""
    a, sep, b = text.partition(":")
    if not (sep and a.isdigit() and b.isdigit() and int(a) < int(b)):
        raise argparse.ArgumentTypeError(
            f"want A:B with 0 <= A < B, got {text!r}")
    return int(a), int(b)


@dataclass
class StepProfile:
    """Traces the loop's steps ``first..stop-1`` into ``directory`` with
    the benchmark's profiler options (host spans on, Python tracer off),
    then writes the compiled step's HLO text beside the trace so device
    ops can be mapped to their named scopes."""

    directory: str
    first: int
    stop: int
    on: bool = False

    def before(self, t: int) -> None:
        import jax
        if not self.on and self.first <= t < self.stop:
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 2
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.directory, profiler_options=opts)
            self.on = True

    def after(self, t: int, compiled) -> None:
        if self.on and t + 1 >= self.stop:
            self.finish(compiled)

    def finish(self, compiled) -> None:
        import jax
        if not self.on:
            return
        jax.profiler.stop_trace()
        self.on = False
        with open(os.path.join(self.directory, "step.hlo.txt"), "w") as f:
            f.write(compiled.as_text())


def force_host_devices(n: int) -> None:
    """Emulate ``n`` devices on the CPU backend (before jax initializes)."""
    if n:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}")


@dataclass
class TrainRun:
    """What :func:`train` leaves behind: per-step losses, the compiled step
    program (HLO text, memory analysis), its compile seconds and each
    step's wall-clock seconds (ended by the host fetch of the loss)."""

    losses: list
    compiled: Any = None
    compile_s: float = 0.0
    step_s: list = field(default_factory=list)
    state: tuple = ()          # (params, opt_state, sync_state) at the end


def main(argv=None):
    args = parse_args(argv)
    force_host_devices(args.devices)
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    if args.profile_dir:
        fresh_compiles()
    else:
        enable_compile_cache()
    return train(args, get_config(args.arch)).losses


def fresh_compiles() -> None:
    """Compile every program of this process afresh. The persistent
    cache's key leaves out the ops' metadata, so a cached step carries
    the ``op_name`` scopes of whatever code first compiled it, which a
    profile is read through."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


def train(args, cfg) -> TrainRun:
    """Run ``args.steps`` steps of ``args.sync`` on ``cfg``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.checkpoint import save_checkpoint, latest_step, load_checkpoint
    from repro.core.scheduler import SyncConfig
    from repro.data.pipeline import SyntheticLMDataset
    from repro.dist import sharding as SH
    from repro.dist.async_engine import (AsyncConfig, init_async_state,
                                         make_async_train_step)
    from repro.dist.train import (init_dist_sync_state,
                                  make_elastic_train_step, make_train_step)
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as TF
    from repro.models.params import init_params, param_specs
    from repro.optim import momentum

    injector = None
    if args.fault_plan:
        from repro.faults import FaultPlan, TrainFaultInjector
        injector = TrainFaultInjector(FaultPlan.load(args.fault_plan),
                                      attempt=args.fault_attempt)
    guard = injector is not None and injector.has_poison
    mesh = make_host_mesh(model=args.model_shards)
    flags = TF.RunFlags(remat=False)
    defs = TF.model_defs(cfg)
    pspecs = param_specs(defs, SH.axis_sizes(mesh))
    params = init_params(defs, jax.random.PRNGKey(args.seed))
    opt = momentum(args.lr, 0.9)
    opt_state = opt.init(params)

    data = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch,
                              seed=args.seed)

    # the poison guard only arms the paths that implement it; a poison plan
    # with --sync elastic would corrupt params silently, so refuse it
    if guard and args.sync not in ("exact", "async"):
        raise SystemExit("--fault-plan with grad_poison events needs "
                         "--sync exact or async (the skip-step guard)")

    if args.sync == "exact":
        sync_state = {"step": jnp.zeros((), jnp.int32)}
        base = make_train_step(cfg, opt, flags, skip_nonfinite=guard)

        def exact_step(params, opt_state, sync_state, batch):
            params, opt_state, metrics = base(params, opt_state, batch)
            return params, opt_state, sync_state, metrics

        jstep = jax.jit(exact_step, donate_argnums=(0, 1))
    elif args.sync == "async":
        # horizon is decoupled from --steps (up to 1024) so resuming with a
        # larger --steps reuses the checkpointed tau table unchanged and
        # never wraps it.  The crash/rejoin schedules are the exception:
        # their outage points are horizon fractions, so their tables must be
        # run-length-aligned for workers to actually die mid-run — extending
        # such a run needs the original --steps (the resume shape guard
        # enforces this).
        horizon = max(args.steps, 1) \
            if args.async_schedule in ("crash", "rejoin") \
            else max(args.steps, 1024)
        overlap = args.overlap
        if overlap and args.compressor != "none" and args.model_shards > 1:
            # the compact-wire all_gather under a partial-auto shard_map on
            # a tensor-parallel mesh has not been retested on jax 0.9
            # (ROADMAP Queue 2)
            print("overlap: disabled (compact-wire all_gather needs "
                  "--model-shards 1); using the densified delivery",
                  flush=True)
            overlap = False
        acfg = AsyncConfig(
            tau_max=args.tau_max, schedule=args.async_schedule,
            axis_names=("data",), compressor=args.compressor,
            error_feedback=args.ef, topk_ratio=args.topk_ratio,
            horizon=horizon, seed=args.seed,
            crash_subst=args.crash_subst, skip_nonfinite=guard,
            overlap=overlap)
        sync_state = init_async_state(acfg, mesh, params,
                                      pspecs if acfg.fused else None)
        if injector is not None and injector.plan.has_tau_events:
            # scheduled crash/rejoin/delay/drop faults rewrite the pre-drawn
            # tau table — the engine then runs them with no new code, and a
            # resume restores the SAME rewritten table from the checkpoint
            sync_state["taus"] = jnp.asarray(injector.plan.apply_to_taus(
                np.asarray(sync_state["taus"]), args.tau_max))
        astep = make_async_train_step(cfg, opt, mesh, acfg, pspecs, flags)
        jstep = jax.jit(astep, donate_argnums=(0, 1, 2))
    else:
        scfg = SyncConfig(
            strategy=args.sync, axis_names=("data",),
            topk_ratio=args.topk_ratio, beta=args.beta,
            budget_b=args.budget_b,
            gate="norm")
        sync_state = init_dist_sync_state(scfg, mesh, params)
        estep = make_elastic_train_step(cfg, opt, mesh, scfg, pspecs, flags)
        jstep = jax.jit(estep, donate_argnums=(0, 1, 2))

    step_idx = 0
    if args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            restored = load_checkpoint(args.ckpt_dir, last)
            if len(restored) == 3:
                # delay rings / EF residuals / tau-table position resume
                # with the params — a mid-flight stale gradient survives
                # the restart (tests/test_ckpt_roundtrip.py)
                params, opt_state, ckpt_state = restored
                if jax.tree.map(np.shape, sync_state) != \
                        jax.tree.map(np.shape, ckpt_state):
                    raise ValueError(
                        "checkpointed sync/async state does not match the "
                        "current --sync configuration (different strategy, "
                        "--tau-max, --compressor, --ef, --overlap, or a "
                        "--steps change that resized the tau table?) — "
                        "delay rings and tau schedules cannot be "
                        "reinterpreted; resume with the original flags or "
                        "use a fresh --ckpt-dir")
                sync_state = ckpt_state
            else:  # legacy (params, opt_state) checkpoints
                params, opt_state = restored
            step_idx = last
            print(f"resumed from step {last}")

    # state goes onto the mesh explicitly (params and optimizer state by
    # their specs, per-worker sync state over the data axes), so the step
    # is compiled for — and runs on — every device of the mesh
    params = jax.device_put(params, SH.named(mesh, pspecs))
    opt_state = jax.device_put(opt_state, SH.named(
        mesh, SH.opt_state_specs(opt_state, pspecs)))
    sync_state = jax.device_put(sync_state, SH.named(
        mesh, SH.sync_state_specs(sync_state, pspecs, mesh)))

    def batch_at(t):
        batch = data.batch(t)
        if guard:
            # the loss_scale channel: all-ones normally, NaN/Inf on
            # grad_poison steps; (B,)-shaped so the batch stays uniformly
            # shardable.  Present on EVERY step once armed — one program,
            # and a benign scale of 1.0 is bitwise-neutral
            batch = dict(batch, loss_scale=np.full(
                (args.batch,), injector.loss_scale(t), np.float32))
        with jax.profiler.TraceAnnotation("batch.device_put"):
            return jax.device_put(batch, SH.named(
                mesh, SH.batch_specs(cfg, mesh, batch)))

    result = TrainRun(losses=[])
    losses = result.losses
    skipped = 0
    profile = (StepProfile(args.profile_dir, *args.profile_steps)
               if args.profile_dir else None)
    for t in range(step_idx, args.steps):
        if profile:
            profile.before(t)
        with jax.profiler.StepTraceAnnotation("train_step", step_num=t):
            batch = batch_at(t)
            if result.compiled is None:
                t0 = time.perf_counter()
                result.compiled = jstep.lower(params, opt_state, sync_state,
                                              batch).compile()
                result.compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("step.call"):
                params, opt_state, sync_state, metrics = result.compiled(
                    params, opt_state, sync_state, batch)
            with jax.profiler.TraceAnnotation("loss.fetch"):
                losses.append(float(metrics["loss"]))
                result.step_s.append(time.perf_counter() - t0)
                skipped += int(float(metrics.get("nonfinite", 0.0)) > 0)
            if t % args.log_every == 0:
                gap = float(metrics.get("gap2_over_alpha2",
                                        metrics.get("stale_gap2", 0.0)))
                tau = ""
                if "mean_tau" in metrics:
                    tau = f"  tau {float(metrics['mean_tau']):.2f}"
                print(f"step {t:5d}  loss {losses[-1]:.4f}  gap2/a2 "
                      f"{gap:.4g}{tau}", flush=True)
            if args.ckpt_dir and args.ckpt_every and \
                    (t + 1) % args.ckpt_every == 0:
                try:
                    if injector is not None:
                        injector.check_ckpt_io(t + 1)
                    with jax.profiler.TraceAnnotation("ckpt.save"):
                        save_checkpoint(args.ckpt_dir, t + 1,
                                        (params, opt_state, sync_state))
                except OSError as e:
                    # checkpointing is best-effort: warn and keep training
                    # — the next save (or the torn-ckpt skip in
                    # latest_step) covers recovery
                    print(f"ckpt save failed at step {t + 1}: {e}",
                          flush=True)
            if injector is not None:
                injector.maybe_kill(t)
        if profile:
            profile.after(t, result.compiled)
    if profile:
        profile.finish(result.compiled)
    if injector is not None:
        print(f"faults: poisoned={injector.poisoned_steps} "
              f"skipped={skipped} ckpt_errors={injector.ckpt_errors}",
              flush=True)
        finite = [l for l in losses[-10:] if np.isfinite(l)]
        print(f"final loss {np.mean(finite if finite else losses[-10:]):.4f}")
    else:
        print(f"final loss {np.mean(losses[-10:]):.4f}")
    result.state = (params, opt_state, sync_state)
    return result


if __name__ == "__main__":
    main()

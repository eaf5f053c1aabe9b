"""Training cells: the trainer's step loop, timed over a window.

``repro.launch.train.train`` runs a fixed number of steps and gives no
hook between them, so neither a time budget nor the state after a given
step can be had from it without changing it. :class:`Trainer` therefore
calls the builders ``train()`` calls, with the cell's argv through the
trainer's own ``parse_args``: ``make_host_mesh``,
``model_defs`` / ``param_specs``, ``momentum``, ``SyntheticLMDataset``,
``make_train_step`` (exact) or ``init_async_state`` +
``make_async_train_step`` (async), ``jax.jit`` with the same donation,
the ``SH.named`` placements and one AOT compile. Its :meth:`Trainer.step`
is the body of ``train()``'s loop: build the batch on the host, place it,
call the compiled step, fetch the loss (and the non-finite flag, and
every ``log_every`` steps the logged metrics). What it leaves out of that
loop: the fault-injection and checkpoint branches, off in every cell.
One departure: the weights come from the reference's ``init`` (one
jitted call from the seed, placed by the program's specs), so that the
reference draws the same weights without reading the program's.

A run: set-up (build, compile, ``check_steps`` steps that the reference
follows, ``warm_steps`` more), then either the window (``--trace 0``)
or ``trace_steps`` profiled steps (``--trace 1``), then the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import shutil
import sys
import time

import numpy as np

from bench import correct, trace_reduce
from bench.reference import common as C
from bench.reference import markov, sgd

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def program_config(conf: dict):
    """The trainer's ``ArchConfig``: the registered architecture with the
    file's values put in for the keys it maps, and ``set`` values for
    fields no published key names."""
    from repro.configs import get_config
    prog = conf["program"]
    over = {field: conf[key] for key, field in prog["fields"].items()}
    over.update(prog.get("set", {}))
    return dataclasses.replace(get_config(prog["arch"]), **over)


def build_program(args, cfg, mesh):
    """What ``train()`` builds before its loop, for parsed ``args``:
    ``(jstep, pspecs, opt, sync_state_of, horizon)`` with
    ``sync_state_of(params_like)`` the initial sync state."""
    import jax
    import jax.numpy as jnp

    from repro.dist import sharding as SH
    from repro.dist.async_engine import (AsyncConfig, init_async_state,
                                         make_async_train_step)
    from repro.dist.train import make_train_step
    from repro.models import transformer as TF
    from repro.models.params import param_specs
    from repro.optim import momentum

    if args.sync not in ("exact", "async"):
        raise ValueError(f"no training cell drives --sync {args.sync}")
    flags = TF.RunFlags(remat=False)
    pspecs = param_specs(TF.model_defs(cfg), SH.axis_sizes(mesh))
    opt = momentum(args.lr, 0.9)
    if args.sync == "exact":
        base = make_train_step(cfg, opt, flags)

        def exact_step(params, opt_state, sync_state, batch):
            params, opt_state, metrics = base(params, opt_state, batch)
            return params, opt_state, sync_state, metrics

        return (jax.jit(exact_step, donate_argnums=(0, 1)), pspecs, opt,
                lambda _: {"step": jnp.zeros((), jnp.int32)}, None)
    horizon = max(args.steps, 1) \
        if args.async_schedule in ("crash", "rejoin") \
        else max(args.steps, 1024)
    acfg = AsyncConfig(
        tau_max=args.tau_max, schedule=args.async_schedule,
        axis_names=("data",), compressor=args.compressor,
        error_feedback=args.ef, topk_ratio=args.topk_ratio,
        horizon=horizon, seed=args.seed, crash_subst=args.crash_subst,
        overlap=args.overlap)
    astep = make_async_train_step(cfg, opt, mesh, acfg, pspecs, flags)
    return (jax.jit(astep, donate_argnums=(0, 1, 2)), pspecs, opt,
            lambda p: init_async_state(acfg, mesh, p,
                                       pspecs if acfg.fused else None),
            horizon)


class Trainer:
    def __init__(self, cfg, argv, seed: int, init, chips: int):
        import jax

        from repro.data.pipeline import SyntheticLMDataset
        from repro.dist import sharding as SH
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import parse_args
        from repro.models import transformer as TF
        from repro.models.params import abstract_params

        self.args = args = parse_args(list(argv) + ["--seed", str(seed)])
        self.cfg = cfg
        # the cell's chips: all of them on the chip (the harness checks
        # the count); the first of several emulated CPU devices in tests
        self.mesh = mesh = make_host_mesh(model=args.model_shards,
                                          devices=jax.devices()[:chips])
        jstep, pspecs, opt, sync_state_of, self.horizon = build_program(
            args, cfg, mesh)
        self.shapes = abstract_params(TF.model_defs(cfg))
        self.key = C.seed_key(seed)
        self.init = jax.jit(lambda k: init(self.shapes, k),
                            out_shardings=SH.named(mesh, pspecs))
        params = self.init(self.key)
        opt_state = opt.init(params)
        self.data = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch,
                                       seed=args.seed)
        sync_state = sync_state_of(params)
        self.params = jax.device_put(params, SH.named(mesh, pspecs))
        self.opt_state = jax.device_put(opt_state, SH.named(
            mesh, SH.opt_state_specs(opt_state, pspecs)))
        self.sync_state = jax.device_put(sync_state, SH.named(
            mesh, SH.sync_state_specs(sync_state, pspecs, mesh)))
        del params, opt_state, sync_state
        self._named = SH.named
        self._batch_specs = SH.batch_specs
        self.jstep = jstep
        self.compiled = None
        self.tokens = {}          # host tokens of the followed steps

    def batch_at(self, t: int):
        import jax
        with jax.profiler.TraceAnnotation("data.batch"):
            batch = self.data.batch(t)
        if t in self.tokens:
            self.tokens[t] = (np.asarray(batch["tokens"]),
                              np.asarray(batch["labels"]))
        with jax.profiler.TraceAnnotation("batch.device_put"):
            return jax.device_put(batch, self._named(
                self.mesh, self._batch_specs(self.cfg, self.mesh, batch)))

    def compile(self):
        self.compiled = self.jstep.lower(
            self.params, self.opt_state, self.sync_state,
            self.batch_at(0)).compile()

    def step(self, t: int) -> float:
        import jax
        batch = self.batch_at(t)
        with jax.profiler.TraceAnnotation("step.call"):
            self.params, self.opt_state, self.sync_state, metrics = \
                self.compiled(self.params, self.opt_state, self.sync_state,
                              batch)
        with jax.profiler.TraceAnnotation("loss.fetch"):
            loss = float(metrics["loss"])
            float(metrics.get("nonfinite", 0.0))
        if t % self.args.log_every == 0:
            gap = float(metrics.get("gap2_over_alpha2",
                                    metrics.get("stale_gap2", 0.0)))
            tau = ""
            if "mean_tau" in metrics:
                tau = f"  tau {float(metrics['mean_tau']):.2f}"
            print(f"step {t:5d}  loss {loss:.4f}  gap2/a2 {gap:.4g}{tau}",
                  flush=True)
        return loss

    def follow(self, n: int, first_at: int) -> dict:
        """Steps 0..n-1, reading what the reference is compared on while
        the next step has not yet overwritten it."""
        out = {"losses": []}
        self.tokens = {t: None for t in range(n)}
        for t in range(n):
            out["losses"].append(self.step(t))
            if t == first_at:
                out["first"] = [float(x) for x in
                                C.leaf_norms(self.opt_state["mu"])]
                out["first_host"] = C.host_leaves(self.opt_state["mu"])
        p0 = self.init(self.key)
        out["change"] = [float(x) for x in C.diff_norms(self.params, p0)]
        return out

    def close(self):
        self.params = self.opt_state = self.sync_state = None
        self.compiled = self.jstep = None


def window(tr: Trainer, t: int, seconds: float):
    """Steps from ``t`` until ``seconds`` pass; the window ends with the
    last step that finished inside it. Also returns the host's
    garbage-collection pauses (seconds, generation) inside it, to tell a
    collection from other stalls."""
    pauses, began = [], []

    def on_gc(phase, info):
        if phase == "start":
            began.append(time.perf_counter())
        elif began:
            pauses.append((time.perf_counter() - began.pop(),
                           info["generation"]))

    gc.callbacks.append(on_gc)
    start = time.perf_counter()
    deadline = start + seconds
    ends, losses = [], []
    while True:
        loss = tr.step(t)
        now = time.perf_counter()
        t += 1
        if now > deadline:
            break
        ends.append(now)
        losses.append(loss)
    gc.callbacks.remove(on_gc)
    return start, ends, losses, pauses


def traced(tr: Trainer, t: int, n: int, directory):
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    losses = [tr.step(t)]                   # lead step, outside the window
    for i in range(1, n + 1):
        with jax.profiler.StepTraceAnnotation("bench_step", step_num=t + i):
            losses.append(tr.step(t + i))
    jax.profiler.stop_trace()
    return losses[1:]


def peak_bytes() -> int:
    """The fullest chip's peak, read after the window: the allocator's
    peak of live buffers, or its live buffers now together with what it
    holds reserved for the step program's temporaries, whichever is more
    (``peak_bytes_in_use`` alone leaves the temporaries out, and the two
    peaks need not fall together)."""
    import jax

    def peak(d):
        st = d.memory_stats() or {}
        return max(int(st.get("peak_bytes_in_use", 0)),
                   int(st.get("bytes_in_use", 0))
                   + int(st.get("bytes_reserved", 0)))
    return max(peak(d) for d in jax.local_devices())


def reference_batches(ctx, step: dict) -> list:
    """The reference's own tokens of the followed steps."""
    tabs = markov.tables(ctx.conf["vocab_size"], ctx.seed)
    return [markov.batch(ctx.conf["vocab_size"], step["seq"], step["batch"],
                         ctx.seed, t, _tables=tabs)
            for t in range(ctx.traffic["check_steps"])]


def reference_run(ctx, step: dict, rnd=C.EXACT, fault=None,
                  batches=None) -> dict:
    """The reference's own weights, tokens and first steps."""
    import jax
    ref = importlib.import_module(f"bench.reference.{ctx.conf['reference']}")
    shapes = ctx.shapes
    init = jax.jit(lambda k: ref.init(shapes, k))
    batches = batches or reference_batches(ctx, step)
    with jax.default_matmul_precision("highest"):
        return sgd.follow(ref, ctx.conf, step, init, C.seed_key(ctx.seed),
                          batches, ctx.seed, workers=ctx.chips, rnd=rnd,
                          block=ctx.traffic["reference_rows"], fault=fault)


def check_args(args, step: dict, horizon) -> None:
    """The traffic file's explicit step settings are what the trainer's
    parser made of its argv."""
    got = {"sync": args.sync, "batch": args.batch, "seq": args.seq,
           "lr": args.lr}
    if args.sync == "async":
        got.update(compressor=args.compressor, topk_ratio=args.topk_ratio,
                   ef=args.ef, tau_max=args.tau_max,
                   schedule=args.async_schedule, horizon=horizon)
    want = {k: step[k] for k in got}
    if got != want:
        raise ValueError(f"traffic argv gives {got}, its step says {want}")


def build(ctx) -> Trainer:
    ref = importlib.import_module(f"bench.reference.{ctx.conf['reference']}")
    cfg = program_config(ctx.conf)
    tr = Trainer(cfg, ctx.traffic["argv"], ctx.seed, ref.init, ctx.chips)
    check_args(tr.args, ctx.traffic["step"], tr.horizon)
    ctx.shapes = tr.shapes
    ctx.names = C.leaf_names(tr.shapes)
    return tr


def run(ctx) -> dict:
    import jax
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(ev)
        if ev in COMPILE_EVENTS else None)
    traffic, step = ctx.traffic, ctx.traffic["step"]
    tr = build(ctx)
    first_at = sgd.first_update_step(step, ctx.seed, ctx.chips)
    tr.compile()
    prog = tr.follow(traffic["check_steps"], first_at)
    tokens = dict(tr.tokens)
    t = traffic["check_steps"]
    for _ in range(traffic["warm_steps"]):
        tr.step(t)
        t += 1
    out = {"device": {}}
    n_compiles = len(compiles)
    if ctx.trace:
        directory = ctx.root / ".bench_trace" / ctx.cell["name"]
        losses = traced(tr, t, traffic["trace_steps"], directory)
        red = trace_reduce.reduce(trace_reduce.find_trace(directory))
        shutil.rmtree(directory, ignore_errors=True)
        out["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = trace_reduce.breakdown(red)
        tokens_per_step = step["batch"] * step["seq"]
        ctx.reading = dict(
            red=red, steps=red["steps"], chips=ctx.chips, peak=ctx.peak,
            flops_per_step=ctx.flops_per_token * tokens_per_step)
        out["metrics"] = ctx.read_per_layer(ctx.reading)
        print(f"trace ops (top 10): {out['breakdown']['device_ops']}",
              file=sys.stderr, flush=True)
    else:
        start, ends, losses, pauses = window(tr, t, ctx.seconds)
        if not ends:
            raise RuntimeError(f"no step finished within {ctx.seconds} s")
        steps_ms = np.diff([start] + ends) * 1e3
        span = ends[-1] - start
        out["metrics"] = {
            "setup_s": start - ctx.t_start,
            "tokens_per_s": len(ends) * step["batch"] * step["seq"] / span,
            "step_ms_p95": float(np.percentile(steps_ms, 95)),
        }
        slow = np.argsort(steps_ms)[::-1][:5]
        print(f"window: {len(ends)} steps in {span:.4f} s; step ms "
              f"median {np.median(steps_ms):.3f} p95 "
              f"{np.percentile(steps_ms, 95):.3f}; slowest (step: ms) "
              f"{[(int(i), round(float(steps_ms[i]), 3)) for i in slow]}; "
              f"gc pauses {len(pauses)}, longest (ms, generation) "
              f"{max(((round(p * 1e3, 3), g) for p, g in pauses), default=None)}",
              file=sys.stderr, flush=True)
    in_window = len(compiles) - n_compiles
    out["device"]["memory_peak_bytes"] = peak_bytes()
    print(f"memory: {jax.local_devices()[0].memory_stats()}",
          file=sys.stderr, flush=True)
    out["attempted"] = len(losses)
    out["failed"] = int(sum(not np.isfinite(x) for x in losses))
    tr.close()
    del tr

    ref_tokens = reference_batches(ctx, step)
    ref = reference_run(ctx, step, batches=ref_tokens)
    values = correct.numbers(prog, ref, ctx.names)
    values["tokens_differ"] = int(sum(
        int((tokens[t][0] != rt).sum() + (tokens[t][1] != rl).sum())
        for t, (rt, rl) in enumerate(ref_tokens)))
    values["compiles_in_window"] = in_window
    values["nonfinite_losses"] = out["failed"]
    print(f"losses program {prog['losses']} reference {ref['losses']}; "
          f"worst leaves: grad {values['_grad_leaf']}, distance "
          f"{values['_diff_leaf']}, change "
          f"{values['_change_leaf']}; left out of change "
          f"{values['_left_out']}", file=sys.stderr, flush=True)
    for k in ("loss_gap", "grad_gap", "change_gap", "grad_diff_gap"):
        if k not in ctx.limits:
            print(f"reading {k} {values[k]!r} (not compared in this cell)",
                  file=sys.stderr, flush=True)
    limits = dict(ctx.limits, tokens_differ=0, compiles_in_window=0,
                  nonfinite_losses=0)
    out["correct"], out["checks"] = correct.judge(values, limits)
    return out

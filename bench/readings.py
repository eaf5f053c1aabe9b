#!/usr/bin/env python3
"""Read the numbers that ``correct`` compares, to set a cell's limits:
for each seed, in one process, the program's first steps against the
float32 reference (the lower reading), the control (the reference one
step below each stated precision, ``common.control``, in the program's
place)
and the faults a training cell can have, planted in the reference in the
program's place: half of each batch left out with the mean over the rest,
and a step that leaves the state unchanged.

  python3 bench/readings.py --workload <cell> --seeds 11 12 13 ...
      [--no-program] [--no-control]

One JSON line per seed and kind on stdout. Runs on the chip (no window is
timed); see PERF.md for the readings each limit was set from.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def program_reading(ctx) -> dict:
    """The program's followed steps, as a run's set-up takes them."""
    from bench.drivers import train as D
    from bench.reference import sgd
    tr = D.build(ctx)
    tr.compile()
    first_at = sgd.first_update_step(ctx.traffic["step"], ctx.seed,
                                     ctx.chips)
    prog = tr.follow(ctx.traffic["check_steps"], first_at)
    tr.close()
    return prog


def readings(ctx, program=True, control=True) -> list:
    import numpy as np

    from bench import correct
    from bench.drivers import train as D
    from bench.reference import common as C
    from repro.models import transformer as TF
    from repro.models.params import abstract_params

    step = ctx.traffic["step"]
    ctx.shapes = abstract_params(TF.model_defs(D.program_config(ctx.conf)))
    ctx.names = C.leaf_names(ctx.shapes)
    out = []

    def emit(kind, prog, ref, t0):
        v = correct.numbers(prog, ref, ctx.names)
        # what the worst-leaf and largest-step numbers are made of, to
        # tell one noisy leaf or step from a gap across the model
        row = {"seed": ctx.seed, "kind": kind,
               "seconds": time.perf_counter() - t0,
               **{k: v[k] for k in ("loss_gap", "grad_gap", "change_gap",
                                    "grad_diff_gap")},
               "grad_leaf": v["_grad_leaf"], "change_leaf": v["_change_leaf"],
               "diff_leaf": v["_diff_leaf"],
               "left_out": v["_left_out"],
               "step_loss_gaps": [abs(a - b) for a, b in
                                  zip(prog["losses"], ref["losses"])],
               "grad_median_gap": float(np.median(correct.leaf_gaps(
                   prog["first"], ref["first"]))),
               "change_median_gap": float(np.median(correct.leaf_gaps(
                   prog["change"], ref["change"])))}
        print(json.dumps(row), flush=True)
        out.append(row)

    t0 = time.perf_counter()
    prog = program_reading(ctx) if program else None
    t1 = time.perf_counter()
    ref = D.reference_run(ctx, step)
    print(json.dumps({"seed": ctx.seed, "kind": "times",
                      "program_s": t1 - t0,
                      "reference_s": time.perf_counter() - t1}), flush=True)
    if prog is not None:
        emit("program", prog, ref, t0)
    if control:
        t0 = time.perf_counter()
        emit("control", D.reference_run(
            ctx, step, rnd=C.control()), ref, t0)
        if step["batch"] // ctx.chips >= 2:
            t0 = time.perf_counter()
            emit("fault_half_batch", D.reference_run(
                ctx, step, fault="half_batch"), ref, t0)
        # the state returned unchanged: parameters unmoved and momentum
        # still zero, so the first update as momentum holds it is zero
        same = dict(ref, change=[0.0] * len(ref["change"]),
                    first=[0.0] * len(ref["first"]),
                    first_host=[0.0 * a for a in ref["first_host"]])
        emit("fault_state_unchanged", same, ref, time.perf_counter())
    return out


def main() -> None:
    from bench import manifest as M
    from bench.run import context
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-program", action="store_true")
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    man = M.load()
    chips = M.cell(man, args.workload)["chips"]
    for seed in args.seeds:
        ctx = context(ROOT, man, args.workload, seed, 0.0, 0, chips, None)
        readings(ctx, program=not args.no_program,
                 control=not args.no_control)


if __name__ == "__main__":
    main()

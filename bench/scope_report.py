#!/usr/bin/env python3
"""Where a training cell's step spends its device time, by the program's
named scopes, on the chip.

  python3 bench/scope_report.py --workload <cell> --seed <n> [--out DIR]

Builds the cell as a benchmark run does (``bench/drivers/train.py``),
runs its followed and warm steps, traces ``trace_steps`` steps as a
``--trace 1`` run does, and reduces that trace twice: with
``trace_reduce`` (busy, top ops, idle gaps named by the host spans) and
with ``scopes`` and the compiled step's HLO text (device time per scope,
host time per span). The step is compiled afresh (the persistent cache
would hand back a program named by older code). Prints the scope table
on stderr and one JSON line on stdout, per step in ms; ``--out`` also
keeps the whole reduction there as ``<cell>.json``. No window is timed
and nothing is compared with the reference.
"""
import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def report(ctx, directory: Path) -> dict:
    from bench import scopes, trace_reduce
    from bench.drivers import train as D
    tr = D.build(ctx)
    tr.compile()
    t = 0
    for _ in range(ctx.traffic["check_steps"] + ctx.traffic["warm_steps"]):
        tr.step(t)
        t += 1
    D.traced(tr, t, ctx.traffic["trace_steps"], directory)
    trace = trace_reduce.find_trace(directory)
    red = trace_reduce.reduce(trace)
    sc = scopes.reduce(trace, tr.compiled.as_text())
    tr.close()
    shutil.rmtree(directory, ignore_errors=True)
    return {"red": red, "scopes": sc}


def summary(cell: str, seed: int, red: dict, sc: dict) -> dict:
    from bench import trace_reduce
    steps, busy = sc["steps"], sc["busy_s"]

    def ms(v):
        return 1e3 * v / steps
    return {
        "workload": cell, "seed": seed, "steps": steps,
        "step_ms": ms(sc["window_s"]), "busy_ms": ms(busy),
        "scopes_ms": {k: ms(v) for k, v in sc["scopes"].items()},
        "unscoped_pct": 100 * sc["scopes"]["(unscoped)"] / busy,
        "in_hlo_pct": 100 * (1 - sc["scopes"]["(not in HLO)"] / busy),
        "host_ms": {k: [ms(v[0]), v[1]] for k, v in
                    list(sc["host"].items())[:16]},
        "top_unscoped_ms": [[k, ms(v), o] for k, v, o in
                            sc["top_unscoped"]],
        "top_not_in_hlo_ms": [[k, ms(v)] for k, v, _ in
                              sc["top_not_in_hlo"]],
        "breakdown": trace_reduce.breakdown(red),
    }


def main() -> None:
    from bench import manifest as M
    from bench import scopes
    from bench.run import context
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU")
    from repro.launch.train import fresh_compiles
    fresh_compiles()
    man = M.load(ROOT)
    cell = M.cell(man, args.workload)
    ctx = context(ROOT, man, args.workload, args.seed, 0.0, 1,
                  cell["chips"], None)
    got = report(ctx, ROOT / ".bench_trace" / f"scopes_{args.workload}")
    print(scopes.table(got["scopes"]), file=sys.stderr, flush=True)
    line = summary(args.workload, args.seed, got["red"], got["scopes"])
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{args.workload}.json").write_text(
            json.dumps(dict(got, summary=line), indent=1))
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

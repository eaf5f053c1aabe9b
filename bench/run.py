#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print one JSON
line: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` with ``--trace 1``), then ``checks`` last.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled run. Everything a cell needs is found
by name from BENCHMARK.json (see bench/manifest.py). With no TPU, or
another number of chips than the cell asks for, it prints no result and
exits 1. The compile cache is the trainer's own (``.jax_cache`` in the
checkout unless ``JAX_COMPILATION_CACHE_DIR`` is set).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace as Ctx  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import manifest as M  # noqa: E402


def fail(msg: str) -> None:
    print(f"FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def per_layer_reader(root: Path, entries: list):
    def read(reading: dict) -> dict:
        out = {}
        for m in entries:
            v = M.load_module(M.metric_path(m["name"], root)).read(reading)
            if v is not None:
                out[m["name"]] = v
        return out
    return read


def context(root: Path, man: dict, name: str, seed: int, seconds: float,
            trace: int, chips: int, peak: dict | None) -> Ctx:
    """What a driver and the metric readers are given: everything about
    a cell that comes from files, by name, and the run's arguments."""
    from bench import flops
    cell = M.cell(man, name)
    conf = M.config(man, cell["config"], root)
    traffic = M.read_json(M.traffic_path(cell["traffic"], root))
    limits = M.read_json(M.cell_path(name, root))["limits"]
    return Ctx(
        root=root, man=man, cell=cell, conf=conf, traffic=traffic,
        limits=limits, seed=seed, seconds=seconds, trace=trace,
        chips=chips, peak=peak, t_start=T_START,
        flops_per_token=flops.train_flops_per_token(
            conf, traffic["step"]["seq"]),
        e2e=M.metrics_for(man, name, "end_to_end"),
        per_layer=M.metrics_for(man, name, "per_layer"),
        read_per_layer=per_layer_reader(
            root, M.metrics_for(man, name, "per_layer")))


def result_line(ctx, out: dict, device: dict) -> dict:
    section = ctx.per_layer if ctx.trace else ctx.e2e
    metrics = {}
    for m in section:
        if m["name"] in out["metrics"]:
            metrics[m["name"]] = {"value": out["metrics"][m["name"]],
                                  "unit": m["unit"]}
        elif not ctx.trace:
            raise RuntimeError(f"driver gave no {m['name']}")
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": dict(device, **out["device"])}
    if ctx.trace and "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    man = M.load(ROOT)
    cell = M.cell(man, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} devices; the benchmark "
             "measures the chip and never falls back")
    if jax.device_count() != cell["chips"]:
        fail(f"cell {cell['name']} needs {cell['chips']} chips, JAX found "
             f"{jax.device_count()}")
    kind = devs[0].device_kind
    peaks = M.read_json(ROOT / "bench" / "peaks.json")["kinds"]
    if kind not in peaks:
        fail(f"no peaks for device kind {kind!r} in bench/peaks.json")

    from repro.kernels import dispatch
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)

    ctx = context(ROOT, man, args.workload, args.seed, args.seconds,
                  args.trace, cell["chips"], peaks[kind])
    traffic = ctx.traffic
    driver = M.load_module(M.driver_path(traffic["kind"], ROOT))
    out = driver.run(ctx)
    print(f"dispatch: {dispatch.resolutions() or 'no Pallas call site'}",
          file=sys.stderr, flush=True)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": jax.device_count()}
    line = result_line(ctx, out, device)
    from bench import correct
    correct.report(out["checks"])
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

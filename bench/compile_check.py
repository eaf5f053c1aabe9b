#!/usr/bin/env python3
"""Compile each cell's step program at its real sizes, from shapes alone,
and print what the compiler says of it: bytes per chip (arguments,
outputs, aliased, temporaries) and the Pallas custom calls in it.

  python3 bench/compile_check.py [cell ...]              # the chips here
  python3 bench/compile_check.py --steps 2 [cell ...]    # and run 2 steps
  JAX_PLATFORMS=cpu python3 bench/compile_check.py --described [cell ...]

With ``--steps n`` (on the chips here) the cell's trainer is then built
with real weights and run ``n`` steps, and the allocator's own figures
(``memory_stats()``: ``bytes_in_use``, ``peak_bytes_in_use``,
``bytes_limit`` and the rest) are printed beside the compiler's, to show
which of the compiler's bytes the allocator counts.

With ``--described`` the program is compiled for a described v5e chip
that need not be attached (the TPU compiler is installed; nothing runs).
A compile is not a run: it gives memory and program text, never a time.
"""
import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def devices(described: bool, n: int):
    import jax
    if not described:
        return jax.devices()[:n]
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return list(topo.devices)[:n]


def check(cell_name: str, described: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import manifest as M
    from bench.drivers.train import build_program, program_config
    from repro.dist import sharding as SH
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import parse_args
    from repro.models import transformer as TF
    from repro.models.params import abstract_params

    man = M.load()
    cell = M.cell(man, cell_name)
    conf = M.config(man, cell["config"])
    traffic = M.read_json(M.traffic_path(cell["traffic"]))
    args = parse_args(traffic["argv"] + ["--seed", "0"])
    cfg = program_config(conf)
    mesh = make_host_mesh(model=args.model_shards,
                          devices=devices(described, cell["chips"]))
    jstep, pspecs, opt, sync_state_of, _ = build_program(args, cfg, mesh)

    def shaped(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, SH.named(mesh, specs))

    params = abstract_params(TF.model_defs(cfg))
    opt_state = jax.eval_shape(opt.init, params)
    sync_state = jax.eval_shape(lambda: sync_state_of(params))
    batch = {k: jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
             for k in ("tokens", "labels")}
    compiled = jstep.lower(
        shaped(params, pspecs),
        shaped(opt_state, SH.opt_state_specs(opt_state, pspecs)),
        shaped(sync_state, SH.sync_state_specs(sync_state, pspecs, mesh)),
        shaped(batch, SH.batch_specs(cfg, mesh, batch))).compile()
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return {"cell": cell_name, "described": described,
            "device_kind": mesh.devices.flat[0].device_kind,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "total_bytes": total,
            "tpu_custom_call": txt.count(
                'custom_call_target="tpu_custom_call"'),
            "sorts": txt.count(" sort("),
            "topk": txt.count("TopK")}


def run_steps(cell_name: str, steps: int) -> dict:
    """Build the cell's trainer as a run does, run ``steps`` steps, and
    read the allocator before and after."""
    import jax

    from bench import manifest as M
    from bench.drivers import train as D
    from bench.run import context

    def stats():
        return jax.local_devices()[0].memory_stats() or {}

    man = M.load()
    ctx = context(ROOT, man, cell_name, 1, 0.0, 0, M.cell(man, cell_name)
                  ["chips"], None)
    tr = D.build(ctx)
    built = stats()
    tr.compile()
    for t in range(steps):
        tr.step(t)
    ran = stats()
    tr.close()
    return {"cell": cell_name, "steps": steps, "after_build": built,
            "after_steps": ran}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--described", action="store_true")
    ap.add_argument("--steps", type=int, default=0)
    args = ap.parse_args()
    if args.described:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import manifest as M
    cells = args.cells or [c["name"] for c in M.load()["workloads"]]
    for name in cells:
        try:
            row = check(name, args.described)
        except Exception as e:  # noqa: BLE001 (report the compiler's refusal)
            lines = str(e).splitlines()
            row = {"cell": name, "error": lines[0], "hbm": [
                x.strip() for x in lines if "hbm" in x.lower()
                or "Total" in x][:12]}
        print(json.dumps(row), flush=True)
        if args.steps and not args.described and "error" not in row:
            print(json.dumps(run_steps(name, args.steps)), flush=True)


if __name__ == "__main__":
    main()

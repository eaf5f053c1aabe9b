#!/usr/bin/env python3
"""Readings of a MoE training cell, to set its limits: what
``bench/readings.py`` reads (the program, the control, the half-batch and
state-unchanged faults), plus two faults that leave out part of the MoE
layer's mathematics, planted in the reference in the program's place (the
routed scale at 1, and the shared experts dropped), and the number of
expert choices that flip between the program's routers and the
reference's on the first step's tokens.

  python3 bench/readings_moe.py --workload <cell> --seeds 11 12 ...
      [--control-seeds 11 12] [--no-program]

The control and the faults run on the seeds in ``--control-seeds``;
``--no-program`` leaves out the program's reading (the benchmark's own
runs give it). One JSON line per seed and kind on stdout. Runs on the
chip; see PERF.md for the readings each limit was set from.
"""
import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

KEYS = ("loss_gap", "grad_gap", "change_gap", "grad_diff_gap")
#: configuration changes that leave out part of the MoE mathematics
MOE_FAULTS = {"fault_routed_scale_1": {"routed_scaling_factor": 1.0},
              "fault_no_shared_experts": {"n_shared_experts": 0}}


def program_choices(cfg, params, tokens, flags) -> list:
    """The experts each MoE layer's router picks for ``tokens`` (B, S),
    (B*S, k) a layer: the program's layers (``transformer.attn_half``, the
    MLP or ``moe.moe_block``) one at a time, as its stacks scan them."""
    import jax
    import jax.numpy as jnp

    from repro.models import moe as MOE
    from repro.models import transformer as TF
    from repro.models.layers import mlp_block, rmsnorm
    x = TF.embed_input(cfg, params, {"tokens": tokens}, flags)
    positions = jnp.arange(x.shape[1])
    window = cfg.layer_window_sizes()[0]
    out = []
    for stack in ("dense_layers", "layers"):
        if stack not in params:
            continue
        for i in range(jax.tree.leaves(params[stack])[0].shape[0]):
            lp = jax.tree.map(lambda a: a[i], params[stack])
            x, _ = TF.attn_half(cfg, flags, lp, x, positions, window)
            y = rmsnorm(x, lp["ln_mlp"], cfg.norm_eps)
            if "moe" in lp:
                out.append(MOE.router(lp["moe"], cfg,
                                      y.reshape(-1, cfg.d_model))[1])
                h, _ = MOE.moe_block(lp["moe"], cfg, y)
            else:
                h = mlp_block(lp["mlp"], y)
            x = x + h
    return out


def flips(ctx) -> list:
    """Per MoE layer, the (token, choice) pairs whose expert the program's
    router and the reference's do not share, on step 0's tokens and the
    initial weights."""
    import jax
    import numpy as np

    from bench.drivers import train as D
    from bench.reference import common as C
    from bench.reference import markov
    from repro.models import transformer as TF
    ref = __import__(f"bench.reference.{ctx.conf['reference']}",
                     fromlist=["choices"])
    step = ctx.traffic["step"]
    tokens, _ = markov.batch(ctx.conf["vocab_size"], step["seq"],
                             step["batch"], ctx.seed, 0)
    params = jax.jit(lambda k: ref.init(ctx.shapes, k))(C.seed_key(ctx.seed))
    cfg = D.program_config(ctx.conf)
    prog = jax.jit(lambda p, t: program_choices(
        cfg, p, t, TF.RunFlags(remat=False)))(params, tokens)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: ref.choices(p, t, ctx.conf))(
            params, tokens)
    out = []
    for a, b in zip(prog, want):
        a = np.asarray(a).reshape(-1, a.shape[-1])
        b = np.asarray(b).reshape(-1, b.shape[-1])
        same = sum(len(set(x) & set(y)) for x, y in zip(a.tolist(),
                                                         b.tolist()))
        out.append(int(a.size - same))
    return out


def readings(ctx, control: bool, program: bool = True) -> list:
    """The rows for one seed's ``ctx``: the program (with ``program``),
    the flips, and with ``control`` the faults and the control, the MoE
    fault that sets the limits first."""
    from bench import correct
    from bench.drivers import train as D
    from bench.readings import program_reading
    from bench.reference import common as C
    from repro.models import transformer as TF
    from repro.models.params import abstract_params
    step = ctx.traffic["step"]
    ctx.shapes = abstract_params(TF.model_defs(D.program_config(ctx.conf)))
    ctx.names = C.leaf_names(ctx.shapes)
    rows = []

    def emit(row):
        print(json.dumps(row), flush=True)
        rows.append(row)

    def reading(kind, prog, ref, t0):
        v = correct.numbers(prog, ref, ctx.names)
        emit({"seed": ctx.seed, "kind": kind,
              "seconds": time.perf_counter() - t0,
              **{k: v[k] for k in KEYS},
              "grad_leaf": v["_grad_leaf"], "change_leaf": v["_change_leaf"],
              "diff_leaf": v["_diff_leaf"],
              "step_loss_gaps": [abs(a - b) for a, b in zip(
                  prog["losses"], ref["losses"])]})

    t0 = time.perf_counter()
    prog = program_reading(ctx) if program else None
    ref = D.reference_run(ctx, step)
    if prog is not None:
        reading("program", prog, ref, t0)
    emit({"seed": ctx.seed, "kind": "flips", "per_moe_layer": flips(ctx),
          "of": step["batch"] * step["seq"]
          * ctx.conf["num_experts_per_tok"]})
    if not control:
        return rows
    same = dict(ref, change=[0.0] * len(ref["change"]),
                first=[0.0] * len(ref["first"]),
                first_host=[0.0 * a for a in ref["first_host"]])
    reading("fault_state_unchanged", same, ref, time.perf_counter())
    for kind, change in MOE_FAULTS.items():
        t0 = time.perf_counter()
        faulty = SimpleNamespace(**dict(vars(ctx),
                                        conf=dict(ctx.conf, **change)))
        reading(kind, D.reference_run(faulty, step), ref, t0)
    t0 = time.perf_counter()
    reading("control", D.reference_run(ctx, step, rnd=C.control()), ref, t0)
    if step["batch"] // ctx.chips >= 2:
        t0 = time.perf_counter()
        reading("fault_half_batch", D.reference_run(
            ctx, step, fault="half_batch"), ref, t0)
    return rows


def main() -> None:
    from bench import manifest as M
    from bench.run import context
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--no-program", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    man = M.load()
    chips = M.cell(man, args.workload)["chips"]
    for seed in args.seeds:
        readings(context(ROOT, man, args.workload, seed, 0.0, 0, chips,
                         None), seed in args.control_seeds,
                 program=not args.no_program)


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination with ShapeDtypeStruct inputs (no allocation), prove the
sharding config is coherent, and extract the roofline raw terms.

XLA's cost_analysis counts a ``while`` (lax.scan) body ONCE regardless of
trip count, so per-(arch,shape) FLOPs/bytes/collective-bytes are measured by
lowering two reduced-layer-count variants (L1, L2 — chosen to preserve the
arch's structural pattern) and extrapolating linearly to the full depth:
    m(L) = m(L1) + (L - L1) * (m(L2) - m(L1)) / (L2 - L1).
The FULL config is still compiled (that is the fits-and-lowers proof and the
memory_analysis source); only the cost terms use the interpolation.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out experiments/dryrun
"""

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.configs.base import ArchConfig, InputShape
from repro.core.scheduler import SyncConfig
from repro.data.pipeline import make_batch_specs
from repro.dist import sharding as SH
from repro.dist.train import (make_decode_step, make_elastic_train_step,
                              make_prefill_step, make_train_step)
from repro.launch.mesh import make_production_mesh
from repro.models import actx
from repro.models import transformer as TF
from repro.models.params import abstract_params, param_specs
from repro.optim import momentum, sgd

# ---------------------------------------------------------------------------
# input_specs (deliverable: ShapeDtypeStruct stand-ins for every model input)
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: InputShape, flags: TF.RunFlags):
    """ShapeDtypeStructs for one workload: batch dict (+ cache for decode)."""
    batch = make_batch_specs(cfg, shape)
    if shape.kind != "decode":
        return {"batch": batch}
    cache = jax.eval_shape(
        lambda: TF.init_cache(cfg, shape.global_batch, shape.seq_len, flags))
    return {"batch": batch, "cache": cache}


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(segment: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the post-SPMD
    (compiled) HLO, weighted by ring traffic factor (all-reduce ~2x; others
    ~1x their payload). XLA groups several tensors into one tuple-shaped
    collective — all tuple element shapes are summed. ``-done`` ops and
    operand mentions (inside fusions / get-tuple-element) are skipped."""
    out: dict = {}
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        lhs, _, rhs = line.partition(" = ")
        for kind in _COLL_OPS:
            tok = rhs.find(kind)
            if tok < 0:
                continue
            after = rhs[tok + len(kind):]
            # accept "(", "-start(", ".12 = ..." forms; reject operand refs
            if not (after.startswith("(") or after.startswith("-start(")):
                continue
            b = _shape_bytes(rhs[:tok])
            factor = 2.0 if kind == "all-reduce" else 1.0
            out[kind] = out.get(kind, 0.0) + factor * b
            break
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


# ---------------------------------------------------------------------------
# lowering builders
# ---------------------------------------------------------------------------

def pick_optimizer(cfg: ArchConfig):
    """Paper-faithful default: SGD + momentum 0.9. grok-1's 314B of fp32
    master+momentum state does not fit 256 chips; it uses stateless SGD
    (documented in DESIGN.md / EXPERIMENTS.md)."""
    if cfg.name.startswith("grok"):
        return sgd(1e-2), "sgd"
    return momentum(1e-2, 0.9), "momentum"


def use_fsdp(cfg: ArchConfig) -> bool:
    """Shard params over the data axis too when replicated master+momentum
    would exceed ~6GB/device on the single-pod mesh."""
    per_dev = cfg.param_count() * 4 * 2 / 16  # fp32 x (param+momentum) /model
    return per_dev > 6e9


def build_flags(cfg: ArchConfig, shape: InputShape, mesh) -> TF.RunFlags:
    return TF.RunFlags(remat=True)


SEQUENCE_PARALLEL = False  # baseline OFF; flipped by --sp (a §Perf lever)


def act_rules_for(cfg: ArchConfig, mesh, shape: InputShape, *,
                  batch_axes: bool = True) -> dict:
    return SH.make_act_rules(
        cfg, mesh, batch_size=shape.global_batch,
        seq_len=shape.seq_len if shape.kind != "decode" else 1,
        sequence_parallel=SEQUENCE_PARALLEL and shape.kind != "decode",
        batch_axes=batch_axes)


GRAD_ACCUM = 1  # microbatching lever (--accum); baseline 1
WIRE_DTYPE = "f32"  # gradient-sync wire dtype (--wire-dtype); baseline f32


def lower_train(cfg: ArchConfig, mesh, shape: InputShape,
                sync: str = "exact", static_phase: int = 0):
    flags = build_flags(cfg, shape, mesh)
    sizes = SH.axis_sizes(mesh)
    fsdp = ("data",) if (use_fsdp(cfg) and sync == "exact") else ()
    defs = TF.model_defs(cfg)
    pspecs = param_specs(defs, sizes, fsdp_axes=fsdp)
    ab_params = abstract_params(defs)
    opt, _ = pick_optimizer(cfg)
    ab_opt = jax.eval_shape(opt.init, ab_params)
    ospecs = SH.opt_state_specs(ab_opt, pspecs)
    batch = make_batch_specs(cfg, shape)
    bspecs = SH.batch_specs(cfg, mesh, batch)

    if sync == "exact":
        step = make_train_step(cfg, opt, flags, grad_accum=GRAD_ACCUM)
        jitted = jax.jit(
            step,
            in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, ospecs),
                          SH.named(mesh, bspecs)),
            out_shardings=(SH.named(mesh, pspecs), SH.named(mesh, ospecs),
                           None),
            donate_argnums=(0, 1))
        with actx.rules(act_rules_for(cfg, mesh, shape)):
            return jitted.lower(ab_params, ab_opt, batch)

    scfg = SyncConfig(
        strategy=sync, axis_names=SH.data_axes(mesh),
        wire_dtype=WIRE_DTYPE,
        gate="static" if sync == "elastic" else "norm")
    from repro.dist.train import init_dist_sync_state
    ab_sync = jax.eval_shape(lambda: init_dist_sync_state(scfg, mesh,
                                                          ab_params))
    # per-worker entries (EF error / elastic residual) shard their leading
    # worker dim over the data axes and keep the params' model sharding
    sspecs = SH.sync_state_specs(ab_sync, pspecs, mesh)
    step = make_elastic_train_step(cfg, opt, mesh, scfg, pspecs, flags,
                                   static_phase=static_phase)
    jitted = jax.jit(
        step,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, ospecs),
                      SH.named(mesh, sspecs), SH.named(mesh, bspecs)),
        out_shardings=(SH.named(mesh, pspecs), SH.named(mesh, ospecs),
                       SH.named(mesh, sspecs), None),
        donate_argnums=(0, 1, 2))
    with actx.rules(act_rules_for(cfg, mesh, shape, batch_axes=False)):
        return jitted.lower(ab_params, ab_opt, ab_sync, batch)


def lower_prefill(cfg: ArchConfig, mesh, shape: InputShape):
    flags = build_flags(cfg, shape, mesh)
    sizes = SH.axis_sizes(mesh)
    defs = TF.model_defs(cfg)
    pspecs = param_specs(defs, sizes)
    ab_params = abstract_params(defs)
    batch = make_batch_specs(cfg, shape)
    bspecs = SH.batch_specs(cfg, mesh, batch)
    ab_cache = jax.eval_shape(
        lambda: TF.init_cache(cfg, shape.global_batch, shape.seq_len, flags))
    cspecs = SH.cache_specs(cfg, mesh, ab_cache)
    step = make_prefill_step(cfg, shape.seq_len, flags)
    jitted = jax.jit(
        step,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, bspecs)),
        out_shardings=(NamedSharding(mesh, SH.batch_spec(
            mesh, shape.global_batch)), SH.named(mesh, cspecs)))
    with actx.rules(act_rules_for(cfg, mesh, shape)):
        return jitted.lower(ab_params, batch)


def lower_decode(cfg: ArchConfig, mesh, shape: InputShape):
    flags = build_flags(cfg, shape, mesh)
    sizes = SH.axis_sizes(mesh)
    defs = TF.model_defs(cfg)
    pspecs = param_specs(defs, sizes)
    ab_params = abstract_params(defs)
    ab_cache = jax.eval_shape(
        lambda: TF.init_cache(cfg, shape.global_batch, shape.seq_len, flags))
    cspecs = SH.cache_specs(cfg, mesh, ab_cache)
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tspec = NamedSharding(
        mesh, P(*(tuple(SH.batch_spec(mesh, shape.global_batch)) + (None,))))
    step = make_decode_step(cfg, flags)
    jitted = jax.jit(
        step,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, cspecs), tspec),
        out_shardings=(NamedSharding(mesh, SH.batch_spec(
            mesh, shape.global_batch)), SH.named(mesh, cspecs)),
        donate_argnums=(1,))
    with actx.rules(act_rules_for(cfg, mesh, shape)):
        return jitted.lower(ab_params, ab_cache, tokens)


def lower_for(cfg: ArchConfig, mesh, shape: InputShape, sync="exact",
              static_phase: int = 0):
    if shape.kind == "train":
        return lower_train(cfg, mesh, shape, sync, static_phase)
    if shape.kind == "prefill":
        return lower_prefill(cfg, mesh, shape)
    return lower_decode(cfg, mesh, shape)


# ---------------------------------------------------------------------------
# layer-count interpolation for scan-aware costs
# ---------------------------------------------------------------------------

def reduced_depths(cfg: ArchConfig) -> tuple[int, int]:
    """(0, pattern_period): the zero-layer lowering isolates the base cost
    (embedding/logits/loss/optimizer) exactly, so the expensive unrolled
    point only needs ONE structural period of depth."""
    if cfg.shared_attn_every:
        return 0, cfg.shared_attn_every
    if cfg.global_every:
        return 0, cfg.global_every
    return 0, 1


def _cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` normalized across jax versions (older
    releases return a one-element list of dicts, newer a plain dict)."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


def _costs_of(lowered) -> dict:
    compiled = lowered.compile()
    ca = _cost_analysis(compiled)
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "collectives": coll,
    }


MAX_UNROLL_SEQ = 4096  # longest sequence we fully unroll for cost lowering


def _costs_at(cfg, mesh, shape, sync, static_phase, seq_len):
    s = shape
    if seq_len != shape.seq_len:
        s = dataclasses.replace(shape, seq_len=seq_len)
    return _costs_of(lower_for(cfg, mesh, s, sync, static_phase))


def _fit_seq(costs_by_seq, target):
    """Polynomial fit m(S) through the measured points (exact for our
    per-step cost structures: SSM/SWA terms linear in S, full-attention
    quadratic)."""
    xs = sorted(costs_by_seq)
    ys = [costs_by_seq[x] for x in xs]
    coef = np.polyfit(np.asarray(xs, np.float64),
                      np.asarray(ys, np.float64), deg=len(xs) - 1)
    return float(max(0.0, np.polyval(coef, target)))


def scan_aware_costs(cfg: ArchConfig, mesh, shape: InputShape,
                     sync="exact", static_phase: int = 0) -> dict:
    """FLOPs/bytes/collectives with every scan unrolled, extrapolated
    (a) linearly in layer count from two reduced depths and (b), when the
    sequence is too long to unroll (prefill_32k), quadratically in S from
    three reduced sequence lengths."""
    from repro.models.scan_utils import set_cost_unroll
    l1, l2 = reduced_depths(cfg)
    seqs = ([shape.seq_len] if shape.kind == "decode"
            or shape.seq_len <= MAX_UNROLL_SEQ
            else [1024, 2048, MAX_UNROLL_SEQ])

    set_cost_unroll(True)  # unroll every model scan so counts are exact
    try:
        grid = {}
        for li in (l1, l2):
            ci = dataclasses.replace(cfg, n_layers=li)
            for s in seqs:
                grid[(li, s)] = _costs_at(ci, mesh, shape, sync,
                                          static_phase, s)
    finally:
        set_cost_unroll(False)

    def metric(c, key, kind=None):
        return c["collectives"].get(kind, 0.0) if key == "coll" else c[key]

    def extrap(key, kind=None):
        # collectives (Megatron activation all-reduces) are LINEAR in S;
        # fitting them quadratically amplifies XLA partitioning-strategy
        # jumps between sizes. flops/bytes keep the quadratic model (full
        # attention really is O(S^2)).
        pts = seqs if key != "coll" else seqs[-2:]
        per_depth = {}
        for li in (l1, l2):
            per_depth[li] = _fit_seq(
                {s: metric(grid[(li, s)], key, kind) for s in pts},
                shape.seq_len)
        per = (per_depth[l2] - per_depth[l1]) / (l2 - l1)
        return max(0.0, per_depth[l1] + (cfg.n_layers - l1) * per)

    coll_kinds = set()
    for c in grid.values():
        coll_kinds |= set(c["collectives"])
    return {
        "flops": extrap("flops"),
        "bytes": extrap("bytes"),
        "collectives": {k: extrap("coll", k) for k in coll_kinds},
        "interpolation": {
            "l1": l1, "l2": l2, "seqs": seqs,
            "grid": {f"L{li}_S{s}": c for (li, s), c in grid.items()},
        },
    }


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def should_skip(cfg: ArchConfig, shape: InputShape):
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("long_500k requires sub-quadratic attention; "
                f"{cfg.name} is full-attention (see DESIGN.md)")
    if shape.kind != "train":
        return TF.serving_refusal(cfg)
    return None


def run_one(arch: str, shape_name: str, multi_pod: bool, sync: str = "exact",
            with_costs: bool = True, static_phase: int = 0) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "multi_pod_2x16x16" if multi_pod else "single_pod_16x16"
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "sync": sync,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "sequence_parallel": SEQUENCE_PARALLEL,
        "status": "ok",
    }
    skip = should_skip(cfg, shape)
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered = lower_for(cfg, mesh, shape, sync, static_phase)
    rec["lower_s"] = round(time.time() - t0, 1)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_per_device_gb": round(
            (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3),
    }
    print(f"  memory_analysis: {ma}")
    ca = _cost_analysis(compiled)
    rec["cost_analysis_raw"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }
    print(f"  cost_analysis(raw, scan-body-once): {rec['cost_analysis_raw']}")

    if with_costs:
        rec["costs"] = scan_aware_costs(cfg, mesh, shape, sync, static_phase)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--sync", default="exact")
    ap.add_argument("--static-phase", type=int, default=0)
    ap.add_argument("--no-costs", action="store_true")
    ap.add_argument("--sp", action="store_true",
                    help="enable sequence parallelism (a perf lever; "
                         "baseline keeps it off)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches (perf lever)")
    ap.add_argument("--wire-dtype", default="f32", choices=["f32", "bf16"],
                    help="gradient-sync wire dtype (perf lever)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    global SEQUENCE_PARALLEL, GRAD_ACCUM, WIRE_DTYPE
    if args.sp:
        SEQUENCE_PARALLEL = True
    GRAD_ACCUM = args.accum
    WIRE_DTYPE = args.wire_dtype

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = (list(INPUT_SHAPES) if args.shape == "all"
              else args.shape.split(","))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = (f"{arch}__{shape}__"
                       f"{'multi' if mp else 'single'}__{args.sync}"
                       + ("__sp" if args.sp else "")
                       + (f"__accum{args.accum}" if args.accum > 1 else "")
                       + ("__bf16wire" if args.wire_dtype == "bf16" else ""))
                out_path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(out_path):
                    rec = json.load(open(out_path))
                    if rec.get("status") in ("ok", "skipped"):
                        print(f"=== {tag} === (cached)", flush=True)
                        continue
                print(f"=== {tag} ===", flush=True)
                try:
                    rec = run_one(arch, shape, mp, args.sync,
                                  with_costs=not args.no_costs and not mp,
                                  static_phase=args.static_phase)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "sync": args.sync, "status": "failed",
                           "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=2)
                print(f"  -> {rec['status']}", flush=True)
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()

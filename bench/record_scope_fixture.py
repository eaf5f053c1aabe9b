#!/usr/bin/env python3
"""Record the small profiler trace the scope-reduction test reads.

  python3 bench/record_scope_fixture.py <out_dir>

Three annotated steps of a jitted function with two named scopes on the
chip: ``mix`` (a bf16 matmul) and ``loop`` (a ``lax.scan`` of matmuls:
a ``while`` op whose body's ops the trace shows inside it), then an
unscoped sum. Before each call the host spends about 20 ms inside a
``pipeline.batch`` span, 15 ms of it inside ``pipeline.markov``.

Writes ``scopes_3steps.xplane.pb``, the step's ``scopes_3steps.hlo.txt``
and ``scopes_3steps.json``: the expected numbers, counted here without
interval unions (``mix``: the sum of its ops, which run one after
another; ``loop``: the sum of its ``while`` ops, which hold the body's),
the sum over every ``loop`` op (what counting the body twice would
give), and the host spans' seconds and counts.
"""
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

NAME = "scopes_3steps"


def step(x, w):
    import jax
    import jax.numpy as jnp
    with jax.named_scope("mix"):
        y = jnp.tanh(x @ w)
    with jax.named_scope("loop"):
        y, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), y, None,
                            length=32)
    return y, jnp.sum(y.astype(jnp.float32))


def expected(trace: Path, hlo: str) -> dict:
    """The numbers the test compares, from plain sums over the events."""
    from jax.profiler import ProfileData

    from bench import scopes as S
    from bench import trace_reduce as T
    names = {k: v.split("/") for k, v in S.op_names(hlo).items()}
    pd = ProfileData.from_file(str(trace))
    host = [(n, s, d) for p in pd.planes if p.name == T.HOST_PLANE
            for _, n, s, d in T._events(p) if d > 0]
    marks = [(s, s + d) for n, s, d in host
             if n == "bench_step" or n.startswith("bench_step#")]
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    out = {"mix": 0.0, "loop": 0.0, "loop_all_ops": 0.0, "loop_whiles": 0}
    for plane in pd.planes:
        if not T.DEVICE_PLANE.match(plane.name):
            continue
        for _, ev, s, d in T._events(plane, T.OPS_LINE):
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            op = T.op_name(ev)
            parts = names.get(op, [])
            if "mix" in parts:
                out["mix"] += b - a
            if "loop" in parts:
                out["loop_all_ops"] += b - a
                if op.startswith("while"):
                    out["loop"] += b - a
                    out["loop_whiles"] += 1
    out = {k: (v * 1e-9 if isinstance(v, float) else v)
           for k, v in out.items()}
    for span in ("pipeline.batch", "pipeline.markov"):
        ev = [(max(s, lo), min(s + d, hi)) for n, s, d in host if n == span]
        ev = [(a, b) for a, b in ev if b > a]
        out[span] = [sum(b - a for a, b in ev) * 1e-9, len(ev)]
    return out


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from bench import trace_reduce as T
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU")
    out = Path(out)
    tmp = out / "trace"
    shutil.rmtree(tmp, ignore_errors=True)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    w = jnp.full((2048, 2048), 1e-3, jnp.bfloat16)
    f = jax.jit(step)
    compiled = f.lower(x, w).compile()
    hlo = compiled.as_text()
    float(compiled(x, w)[1])
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    float(compiled(x, w)[1])
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("bench_step", step_num=i):
            with jax.profiler.TraceAnnotation("pipeline.batch"):
                time.sleep(0.005)
                with jax.profiler.TraceAnnotation("pipeline.markov"):
                    time.sleep(0.015)
            float(compiled(x, w)[1])
    jax.profiler.stop_trace()
    trace = out / f"{NAME}.xplane.pb"
    shutil.copy(T.find_trace(tmp), trace)
    shutil.rmtree(tmp)
    (out / f"{NAME}.hlo.txt").write_text(hlo)
    want = expected(trace, hlo)
    red = T.reduce(trace)
    want.update(
        recorded="one v5e chip by bench/record_scope_fixture.py: three "
                 "steps of a bf16 2048x2048 matmul (scope mix), a 32-step "
                 "scan of the same (scope loop) and an unscoped sum, each "
                 "after 20 ms in host spans",
        window_s=red["window_s"], busy_s=red["busy_s"],
        steps=red["steps"])
    (out / f"{NAME}.json").write_text(json.dumps(want, indent=1) + "\n")
    print(json.dumps(want))
    if not want["loop_whiles"] or want["loop_all_ops"] <= want["loop"]:
        sys.exit("the trace shows no while op holding its body's ops")


if __name__ == "__main__":
    main(sys.argv[1])

#!/usr/bin/env python3
"""Record the small profiler trace the trace-reduction test reads.

  python3 bench/record_fixture.py <out_dir>

Three annotated steps of a jitted bf16 matmul and sort on the chip, each
preceded by a 20 ms host sleep inside a ``host.sleep`` annotation, so the
device's idle gaps are known: about 20 ms each, named ``host.sleep``.
"""
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU")
    f = jax.jit(lambda x: jnp.sort((x @ x).reshape(-1)).reshape(x.shape))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    float(f(x)[0, 0])
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("bench_step", step_num=i):
            with jax.profiler.TraceAnnotation("host.sleep"):
                time.sleep(0.02)
            float(f(x)[0, 0])
    jax.profiler.stop_trace()
    for p in Path(out).rglob("*.xplane.pb"):
        print(p, p.stat().st_size)


if __name__ == "__main__":
    main(sys.argv[1])

"""The control comes out not correct: the float32 reference, computed one
step below each precision the configuration states (every product's
operands and result in float8 e4m3, below the trainer's bfloat16; what it
keeps in float32, as rwkv6's recurrence, in bfloat16), put in the
program's place and judged by each cell's own limits, at a size the CPU
holds."""
import pytest

from bench import correct
from bench import manifest as M
from bench.reference import common as C
from bench.tests.tiny import tiny_ctx

CELLS = [w["name"] for w in M.load()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_correct(cell):
    from bench.drivers import train as D
    from repro.models import transformer as TF
    from repro.models.params import abstract_params
    ctx = tiny_ctx(cell)
    ctx.shapes = abstract_params(TF.model_defs(D.program_config(ctx.conf)))
    names = C.leaf_names(ctx.shapes)
    step = ctx.traffic["step"]
    ref = D.reference_run(ctx, step)
    control = D.reference_run(ctx, step, rnd=C.control())
    ok, checks = correct.judge(correct.numbers(control, ref, names),
                               ctx.limits)
    assert not ok, checks
    # the same reference in its own place is exact
    same, _ = correct.judge(correct.numbers(ref, ref, names), ctx.limits)
    assert same

"""The benchmark's step loop is the trainer's own.

``bench/drivers/train.py`` calls the builders ``repro.launch.train.train``
calls and repeats the body of its loop, because ``train()`` has no hook
between steps. This test keeps the two from drifting apart: from the
trainer's own initial weights, with each cell's argv and seed at a size
the CPU holds, ``Trainer.step`` gives the losses and the final state that
``train()`` gives, step for step.
"""
import numpy as np
import pytest

from bench import manifest as M
from bench.tests.tiny import tiny_ctx

CELLS = [w["name"] for w in M.load()["workloads"]]
STEPS = 4


@pytest.mark.parametrize("cell", CELLS)
def test_driver_loop_matches_train(cell, monkeypatch):
    import jax

    from bench.drivers import train as D
    from repro.launch import mesh as launch_mesh
    from repro.launch.train import parse_args, train
    from repro.models import transformer as TF
    from repro.models.params import init_params

    # train() meshes every device the process has; the cell has one chip
    # (a test process may hold many emulated CPU devices)
    make = launch_mesh.make_host_mesh
    monkeypatch.setattr(launch_mesh, "make_host_mesh",
                        lambda **kw: make(**{"devices": jax.devices()[:1],
                                             **kw}))
    ctx = tiny_ctx(cell)
    cfg = D.program_config(ctx.conf)
    argv = list(ctx.traffic["argv"]) + ["--steps", str(STEPS)]
    run = train(parse_args(argv + ["--seed", str(ctx.seed)]), cfg)

    # train()'s weights, drawn as train() draws them (outside jit)
    p0 = init_params(TF.model_defs(cfg), jax.random.PRNGKey(ctx.seed))

    def program_init(shapes, key):
        return p0

    tr = D.Trainer(cfg, argv, ctx.seed, program_init, chips=1)
    tr.compile()
    losses = [tr.step(t) for t in range(STEPS)]
    assert losses == run.losses
    got = jax.tree.leaves((tr.params, tr.opt_state, tr.sync_state))
    want = jax.tree.leaves(run.state)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

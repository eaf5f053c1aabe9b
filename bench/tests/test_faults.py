"""A run with the timed path broken underneath comes out not correct.

Each cell's run, at a size the CPU holds (bench/tests/tiny.py), is driven
past the chip check with the cell's own limits and the compiled step
replaced by one that leaves the state unchanged, or by one that computes
the mean over half the batch (the other half left out): it is not
correct. A batch of one row has no half to leave out. (A sound run at
this size is not held to the limits: they are set for the cell's size,
where rounding averages over far more terms.)
"""
import numpy as np
import pytest

from bench import manifest as M
from bench.tests.tiny import tiny_ctx

CELLS = [w["name"] for w in M.load()["workloads"]]


def _broken(real, fault):
    import jax
    import jax.numpy as jnp

    def unchanged(params, opt_state, sync_state, batch):
        copy = jax.tree.map(jnp.copy, (params, opt_state, sync_state))
        metrics = real(*copy, batch)[3]
        return params, opt_state, sync_state, metrics

    def half_batch(params, opt_state, sync_state, batch):
        h = jax.tree.leaves(batch)[0].shape[0] // 2
        doubled = jax.tree.map(
            lambda a: jnp.concatenate([a[:h], a[:h]]), batch)
        return real(params, opt_state, sync_state, doubled)

    return {"state_unchanged": unchanged, "half_batch": half_batch}[fault]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_fails_correct(cell, fault, monkeypatch):
    ctx = tiny_ctx(cell)
    if fault == "half_batch" and ctx.traffic["step"]["batch"] < 2:
        pytest.skip("one row: no half of the batch to leave out")
    driver = M.load_module(M.driver_path("train"))
    compile_ = driver.Trainer.compile

    def compile_broken(self):
        compile_(self)
        self.compiled = _broken(self.compiled, fault)

    monkeypatch.setattr(driver.Trainer, "compile", compile_broken)
    out = driver.run(ctx)
    values = {k: c["value"] for k, c in out["checks"].items()}
    assert np.isfinite(list(values.values())).all()
    assert values["tokens_differ"] == values["compiles_in_window"] == 0
    assert out["correct"] is False, out["checks"]

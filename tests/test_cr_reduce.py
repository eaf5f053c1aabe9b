"""Parity and property tests for the fused compress-then-reduce kernels.

`kernels/cr_reduce` is the consume side of the overlapped async engine:
it reduces a panel of S compact compressed messages (top-k vals/idx or
one-bit sign/mean) straight to the dense weighted sum, without ever
materializing the (S, M, R) dense panel.  Three things are pinned here:

  * the Pallas kernels (interpret mode off-TPU) match the jnp oracles
    bitwise-ish (f32 accumulate either way) across dtypes and shapes,
    including non-lane-aligned trailing dims;
  * fused compress-then-reduce of n workers' gradients equals the
    strawman compress -> densify -> dense mean, so swapping the engine's
    dense pmean for the fused path cannot change a trajectory;
  * `scheduler.ef_compress_leaf_compact`'s wire payload densifies to
    exactly `scheduler.ef_compress_leaf`'s payload (same residual too) —
    the compact wire form loses nothing relative to the legacy path.

Property tests need ``hypothesis`` (installed in CI; skipped elsewhere).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cr_reduce import ops as CR
from repro.kernels.cr_reduce.ref import (onebit_cr_deposit_ref,
                                         onebit_cr_reduce_ref,
                                         topk_cr_deposit_ref,
                                         topk_cr_reduce_ref)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # containers without hypothesis: CI still runs these
    HAVE_HYPOTHESIS = False


def _topk_panel(rng, s, m, r, k, dtype):
    vals = rng.standard_normal((s, m, k)).astype(dtype)
    idx = np.stack([
        np.stack([rng.choice(r, size=k, replace=False).astype(np.int32)
                  for _ in range(m)]) for _ in range(s)])
    w = rng.uniform(0.0, 1.5, size=(s,)).astype(np.float32)
    return jnp.asarray(vals), jnp.asarray(idx), jnp.asarray(w)


# interpret-mode kernel vs oracle; shapes cover lane-aligned and ragged R
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,m,r,k", [
    (1, 8, 128, 16),     # single message, aligned
    (3, 16, 100, 7),     # ragged R, k not a divisor
    (5, 8, 257, 1),      # prime R, k=1
    (2, 24, 64, 64),     # k == R (dense-as-sparse)
])
def test_topk_kernel_matches_ref(s, m, r, k, dtype):
    rng = np.random.default_rng(s * 1000 + r)
    vals, idx, w = _topk_panel(rng, s, m, r, k, dtype)
    got = CR.topk_reduce(vals, idx, w, r, impl="kernel")
    want = topk_cr_reduce_ref(vals, idx, w, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,m,r", [
    (1, 8, 128), (3, 16, 100), (4, 8, 257), (2, 32, 33),
])
def test_onebit_kernel_matches_ref(s, m, r, dtype):
    rng = np.random.default_rng(s * 7 + r)
    pos = jnp.asarray(rng.random((s, m, r)) > 0.5)
    means = jnp.asarray(rng.standard_normal((s, m, 2)).astype(dtype))
    w = jnp.asarray(rng.uniform(0.0, 1.5, size=(s,)).astype(np.float32))
    got = CR.onebit_reduce(pos, means, w, impl="kernel")
    want = onebit_cr_reduce_ref(pos, means, w, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_weights_mask_messages():
    """A zero weight is a dropped message; scaling a weight scales its
    contribution linearly — the delivery-mask contract the async engine
    leans on."""
    rng = np.random.default_rng(0)
    vals, idx, w = _topk_panel(rng, 4, 8, 64, 8, np.float32)
    base = np.asarray(topk_cr_reduce_ref(vals, idx, jnp.ones(4), 64))
    only2 = np.asarray(topk_cr_reduce_ref(
        vals, idx, jnp.asarray([0.0, 0.0, 1.0, 0.0]), 64))
    solo = np.asarray(topk_cr_reduce_ref(vals[2:3], idx[2:3],
                                         jnp.ones(1), 64))
    np.testing.assert_allclose(only2, solo, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(topk_cr_reduce_ref(vals, idx, 2.0 * jnp.ones(4), 64)),
        2.0 * base, atol=1e-5)


def test_zero_size_panels():
    assert CR.topk_reduce(jnp.zeros((0, 8, 4)), jnp.zeros((0, 8, 4),
                          jnp.int32), jnp.zeros((0,)), 32).shape == (8, 32)
    assert CR.topk_reduce(jnp.zeros((2, 8, 0)), jnp.zeros((2, 8, 0),
                          jnp.int32), jnp.ones((2,)), 0).shape == (8, 0)
    assert CR.onebit_reduce(jnp.zeros((0, 4, 16), bool),
                            jnp.zeros((0, 4, 2)),
                            jnp.zeros((0,))).shape == (4, 16)


# ---------------------------------------------------------------------------
# slot deposit: fused decompress into the delivery-indexed accumulator ring
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("cap,s,m,r,k", [
    (1, 2, 8, 128, 16),     # capacity-1 ring (tau_max = 0), aligned
    (4, 3, 16, 100, 7),     # ragged R
    (5, 5, 8, 257, 1),      # prime R, k=1, slot collisions likely
])
def test_topk_deposit_kernel_matches_ref(cap, s, m, r, k, dtype):
    rng = np.random.default_rng(cap * 100 + r)
    vals, idx, w = _topk_panel(rng, s, m, r, k, dtype)
    acc = jnp.asarray(rng.standard_normal((cap, m, r)).astype(np.float32))
    slots = jnp.asarray(rng.integers(0, cap, size=(s,)).astype(np.int32))
    got = CR.topk_deposit(acc, vals, idx, slots, w, impl="kernel")
    want = topk_cr_deposit_ref(acc, vals, idx, slots, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("cap,s,m,r", [
    (1, 2, 8, 128), (3, 4, 16, 100), (6, 3, 8, 33),
])
def test_onebit_deposit_kernel_matches_ref(cap, s, m, r):
    rng = np.random.default_rng(cap * 13 + r)
    pos = jnp.asarray(rng.random((s, m, r)) > 0.5)
    means = jnp.asarray(rng.standard_normal((s, m, 2)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.0, 1.5, size=(s,)).astype(np.float32))
    acc = jnp.asarray(rng.standard_normal((cap, m, r)).astype(np.float32))
    slots = jnp.asarray(rng.integers(0, cap, size=(s,)).astype(np.int32))
    got = CR.onebit_deposit(acc, pos, means, slots, w, impl="kernel")
    want = onebit_cr_deposit_ref(acc, pos, means, slots, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_deposit_accumulates_and_masks():
    """Two messages into the SAME slot accumulate on top of the slot's
    prior content; a zero weight is a no-op — the delivery semantics the
    engine's deposit-then-take protocol leans on."""
    rng = np.random.default_rng(7)
    vals, idx, _ = _topk_panel(rng, 2, 8, 64, 8, np.float32)
    acc = jnp.asarray(rng.standard_normal((3, 8, 64)).astype(np.float32))
    slots = jnp.asarray([1, 1], np.int32)
    out = topk_cr_deposit_ref(acc, vals, idx, slots, jnp.ones(2))
    want = np.asarray(acc).copy()
    want[1] += _densify_topk(vals[0], idx[0], 64)
    want[1] += _densify_topk(vals[1], idx[1], 64)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
    noop = topk_cr_deposit_ref(acc, vals, idx, slots, jnp.zeros(2))
    np.testing.assert_array_equal(np.asarray(noop), np.asarray(acc))
    noop1 = onebit_cr_deposit_ref(
        acc, jnp.asarray(rng.random((2, 8, 64)) > 0.5),
        jnp.asarray(rng.standard_normal((2, 8, 2)).astype(np.float32)),
        slots, jnp.zeros(2))
    np.testing.assert_array_equal(np.asarray(noop1), np.asarray(acc))


def test_deposit_then_take_equals_reduce():
    """Depositing a panel into a zeroed slot and taking that slot equals
    the panel's fused reduce with the same weights — the identity that
    makes the engine's single-deposit protocol equivalent to a per-step
    re-reduce."""
    rng = np.random.default_rng(11)
    vals, idx, w = _topk_panel(rng, 4, 8, 96, 12, np.float32)
    acc = jnp.zeros((5, 8, 96))
    slots = jnp.full((4,), 2, np.int32)
    out = topk_cr_deposit_ref(acc, vals, idx, slots, w)
    np.testing.assert_allclose(
        np.asarray(out[2]),
        np.asarray(topk_cr_reduce_ref(vals, idx, w, 96)), atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(out)[[0, 1, 3, 4]], np.zeros((4, 8, 96)))


# ---------------------------------------------------------------------------
# compact wire form vs the legacy densified compression
# ---------------------------------------------------------------------------

def _densify_topk(vals, idx, r):
    m, k = vals.shape
    out = np.zeros((m, r), np.float32)
    np.add.at(out, (np.arange(m)[:, None], np.asarray(idx)),
              np.asarray(vals, np.float32))
    return out


@pytest.mark.parametrize("method", ["topk", "onebit"])
def test_compact_densifies_to_legacy_payload(method):
    """ef_compress_leaf_compact's wire payload reconstructs bitwise to
    ef_compress_leaf's densified payload, and both leave the identical EF
    residual — the fused engine transmits exactly what the legacy engine
    would have."""
    from jax.sharding import PartitionSpec as P
    from repro.core.scheduler import ef_compress_leaf, ef_compress_leaf_compact
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.standard_normal((24, 40)).astype(np.float32))
    err = jnp.asarray(rng.standard_normal((24, 40)).astype(np.float32))
    spec = P("model", None)
    ratio = 1 / 8
    dense, err_d = ef_compress_leaf(g, err, spec, method, ratio)
    payload, err_c = ef_compress_leaf_compact(g, err, spec, method, ratio)
    np.testing.assert_array_equal(np.asarray(err_d), np.asarray(err_c))
    if method == "topk":
        recon = _densify_topk(payload["vals"], payload["idx"], 40)
    else:
        recon = np.where(np.asarray(payload["pos"]),
                         np.asarray(payload["means"])[:, 0:1],
                         np.asarray(payload["means"])[:, 1:2])
    np.testing.assert_array_equal(recon, np.asarray(dense))


# ---------------------------------------------------------------------------
# hypothesis: fused compress-then-reduce == compress -> densify -> mean
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(1, 5), m=st.integers(1, 6), r=st.integers(1, 40),
           seed=st.integers(0, 1000), method=st.sampled_from(
               ["topk", "onebit"]))
    def test_fused_equals_dense_mean_property(n, m, r, seed, method):
        """For any panel of n workers' gradients: compress each worker's
        rows to the compact wire form, fused-reduce with weights 1/n, and
        you get exactly the mean of the densified compressed payloads —
        the invariant that makes the overlapped engine's delivery a
        drop-in for densify + pmean."""
        from jax.sharding import PartitionSpec as P
        from repro.core.scheduler import ef_compress_leaf_compact
        rng = np.random.default_rng(seed)
        rows = rng.standard_normal((n, m, r)).astype(np.float32)
        spec = P("model", None)
        payloads = [ef_compress_leaf_compact(
            jnp.asarray(rows[i]), jnp.zeros((m, r)), spec, method, 1 / 4)[0]
            for i in range(n)]
        w = jnp.full((n,), 1.0 / n)
        if method == "topk":
            fused = CR.topk_reduce(
                jnp.stack([p_["vals"] for p_ in payloads]),
                jnp.stack([p_["idx"] for p_ in payloads]), w, r)
            dense = np.mean([_densify_topk(p_["vals"], p_["idx"], r)
                             for p_ in payloads], axis=0)
        else:
            fused = CR.onebit_reduce(
                jnp.stack([p_["pos"] for p_ in payloads]),
                jnp.stack([p_["means"] for p_ in payloads]), w)
            dense = np.mean([np.where(np.asarray(p_["pos"]),
                                      np.asarray(p_["means"])[:, 0:1],
                                      np.asarray(p_["means"])[:, 1:2])
                             for p_ in payloads], axis=0)
        np.testing.assert_allclose(np.asarray(fused), dense,
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# selection by row length: threshold search for long rows, sort below
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compact_impl", ["auto", "kernel"])
def test_compress_leaf_selects_by_row_length(compact_impl, monkeypatch):
    """compress_leaf counts its pick at ``topk_ef.select`` by the row
    length alone — the threshold search from THRESHOLD_MIN_R on, the sort
    of lax.top_k below — and the async engine's compact path, whose long
    leaves take the threshold search, walks the trajectory of the
    densified path, which keeps lax.top_k.  The search's compaction
    resolves at ``topk_ef.compact``: the jnp reference under ``auto`` on
    the CPU, the streaming kernel (interpreted) when asked.  It is asked for
    at that site alone: the engine's M = 1 deposits and short rows refuse
    ``kernel_impl="kernel"``."""
    import dataclasses

    from repro.configs import get_config
    from repro.data.pipeline import SyntheticLMDataset
    from repro.dist import sharding as SH
    from repro.dist.async_engine import (AsyncConfig, init_async_state,
                                         make_async_train_step)
    from repro.jax_compat import make_mesh
    from repro.kernels import dispatch
    from repro.kernels.topk_ef import ops as TK
    from repro.models import transformer as TF
    from repro.models.params import init_params, param_specs
    from repro.optim import momentum

    for r, how in [(TK.THRESHOLD_MIN_R, "threshold"),
                   (TK.THRESHOLD_MIN_R - 1, "sort")]:
        dispatch.reset_resolutions()
        row = jax.ShapeDtypeStruct((1, r), jnp.float32)
        jax.eval_shape(lambda a: TK.compress_leaf(a, a, 1 / 16), row)
        assert dispatch.resolutions()[TK.SELECT_SITE] == {how: 1}
    if compact_impl == "kernel":    # ask for the kernel at that site alone
        choose = TK.choose
        monkeypatch.setattr(TK, "choose", lambda site, impl, why=None: choose(
            site, "kernel" if site == TK.COMPACT_SITE else impl, why))
    dispatch.reset_resolutions()
    row = jax.ShapeDtypeStruct((1, TK.THRESHOLD_MIN_R), jnp.float32)
    jax.eval_shape(lambda a: TK.compress_leaf(a, a, 1 / 16), row)
    compacted = ("kernel (interpret)" if compact_impl == "kernel"
                 else "ref (backend cpu)")
    assert dispatch.resolutions()[TK.COMPACT_SITE] == {compacted: 1}

    base = get_config("qwen3-1.7b").reduced()
    cfg = dataclasses.replace(
        base, d_ff=max(base.d_ff, TK.THRESHOLD_MIN_R // base.d_model))
    mesh = make_mesh((1, 1), ("data", "model"))
    flags = TF.RunFlags(remat=False)
    defs = TF.model_defs(cfg)
    pspecs = param_specs(defs, SH.axis_sizes(mesh))
    params = init_params(defs, jax.random.PRNGKey(0))
    opt = momentum(1e-2, 0.9)
    data = SyntheticLMDataset(cfg.vocab_size, 32, 4, seed=0)
    batches = [data.batch(t) for t in range(4)]

    def run(overlap):
        acfg = AsyncConfig(tau_max=1, schedule="uniform", seed=1,
                           compressor="topk", topk_ratio=1 / 16,
                           overlap=overlap)
        state = init_async_state(acfg, mesh, params,
                                 pspecs if acfg.fused else None)
        dispatch.reset_resolutions()
        step = jax.jit(make_async_train_step(cfg, opt, mesh, acfg, pspecs,
                                             flags))
        p, opt_state, traj = params, opt.init(params), []
        for b in batches:
            p, opt_state, state, m = step(p, opt_state, state, b)
            traj.append((float(m["loss"]),
                         [np.asarray(x) for x in jax.tree.leaves(p)]))
        return traj, dispatch.resolutions()

    fused, picks = run(True)
    select = picks.get(TK.SELECT_SITE, {})
    assert select.get("threshold", 0) > 0 and select.get("sort", 0) > 0
    assert picks[TK.COMPACT_SITE] == {compacted: select["threshold"]}
    legacy, _ = run(False)
    for t, ((lf, pf), (ll, pl)) in enumerate(zip(fused, legacy)):
        assert lf == ll, f"loss diverged at step {t}"
        for a, b in zip(pf, pl):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=0,
                                       err_msg=f"step {t}")

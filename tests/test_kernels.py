"""Pallas kernels vs pure-jnp oracles (interpret mode), swept over shapes
and dtypes per the brief."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels.onebit_ef import onebit_ef, onebit_ef_ref, unpack
from repro.kernels.swa_attention import swa_decode_attention, swa_decode_ref
from repro.kernels.topk_ef import topk_ef, topk_ef_ref
from repro.kernels.topk_ef.ops import (COMPACT_SITE, THRESHOLD_MIN_R,
                                      decompress_sum)
from repro.kernels.topk_ef import compact, select


@pytest.mark.parametrize("m,r,k", [(8, 64, 4), (16, 256, 8), (32, 128, 1),
                                   (8, 1024, 32), (64, 96, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_ef_matches_ref(m, r, k, dtype):
    key = jax.random.PRNGKey(m * r + k)
    g = jax.random.normal(key, (m, r), dtype)
    e = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (m, r),
                                jnp.float32)
    v1, i1, e1 = topk_ef(g, e, k=k, interpret=True)
    v2, i2, e2 = topk_ef_ref(g, e, k=k)
    # selection sets must match (order can differ on ties)
    np.testing.assert_allclose(np.sort(np.abs(v1), 1), np.sort(np.abs(v2), 1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(e1, e2, rtol=1e-5, atol=1e-5)


def _select_rows(kind, m, r, seed):
    rng = np.random.default_rng(seed)
    if kind == "gauss":
        return rng.standard_normal((m, r)).astype(np.float32)
    if kind == "int_ties":      # ~1/7 of the row per value: ties cross k
        return rng.integers(-3, 4, (m, r)).astype(np.float32)
    if kind == "zeros":         # every magnitude ties, one of them -0.0
        w = np.zeros((m, r), np.float32)
        w[:, r // 3] = -0.0
        return w
    if kind == "clump":         # the largest r/16 in one run mid-row
        w = (rng.standard_normal((m, r)) * 1e-3).astype(np.float32)
        lo = r // 2 - r // 32
        w[:, lo:lo + r // 16] += np.float32(10.0)
        return w
    # denormals: magnitudes below the smallest normal float32
    return (rng.standard_normal((m, r)) * 1e-39).astype(np.float32)


#: Lines of the compaction kernel's blocks, in entries.
_BLOCK = compact.BLOCK_LINES * compact.LANES


@pytest.mark.parametrize("m,r,k", [
    (1, THRESHOLD_MIN_R - 1, (THRESHOLD_MIN_R - 1) // 16),  # below
    (8, THRESHOLD_MIN_R + 5, (THRESHOLD_MIN_R + 5) // 16),  # above
    (8, 96, 1),
    (1, 200, 200),
    # "clump" keeps block 1 alone, "zeros" block 0 alone
    (1, 3 * _BLOCK + 1000, 4000),
    # "int_ties": ties at the threshold taken past the first block; the
    # clump's run straddles the first block boundary
    (1, 3 * _BLOCK + 1000, 25000),
    # every entry kept, over more than one half of the output ring
    (1, 2 * _BLOCK + 300, 2 * _BLOCK + 300),
])
@pytest.mark.parametrize("kind", ["gauss", "int_ties", "zeros", "denormal",
                                  "clump"])
@pytest.mark.parametrize("residual", [False, True])
@pytest.mark.parametrize("impl", ["ref", "kernel"])
def test_threshold_select_matches_ref(kind, m, r, k, residual, impl):
    """The threshold search picks lax.top_k's set, ties to the lowest
    index, and leaves a bit-identical residual; idx come ascending.  The
    row is split between ``g`` and an EF residual ``err`` or all in
    ``g``.  The compaction is the jnp one (``ref``) or the streaming
    kernel, interpreted (``kernel``), which must also match the jnp one
    bit for bit."""
    w = _select_rows(kind, m, r, m * r + k)
    half = np.float32(0.5) * w if residual else np.zeros_like(w)
    g, e = jnp.asarray(w - half), jnp.asarray(half)
    kernel, interpret = dispatch.choose(COMPACT_SITE, impl)
    got = jax.jit(select.topk_threshold_ef,
                  static_argnames=("k", "kernel", "interpret"))(
                      g, e, k=k, kernel=kernel, interpret=interpret)
    v2, i2, e2 = jax.jit(topk_ef_ref, static_argnames="k")(g, e, k=k)
    order = np.argsort(np.asarray(i2), axis=1)
    want = (np.take_along_axis(np.asarray(v2), order, 1),
            np.take_along_axis(np.asarray(i2), order, 1), np.asarray(e2))
    if kernel:
        jnp_path = jax.jit(select.topk_threshold_ef,
                           static_argnames="k")(g, e, k=k)
        for a, b in zip(got, jnp_path):
            np.testing.assert_array_equal(np.asarray(a).view(np.int32),
                                          np.asarray(b).view(np.int32))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a).view(np.int32),
                                      np.asarray(b).view(np.int32))


@pytest.mark.parametrize("m,r", [(8, 64), (16, 256), (8, 1024), (24, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_onebit_ef_matches_ref(m, r, dtype):
    key = jax.random.PRNGKey(m + r)
    g = jax.random.normal(key, (m, r), dtype)
    e = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (m, r),
                                jnp.float32)
    p1, m1, e1 = onebit_ef(g, e, interpret=True)
    p2, m2, e2 = onebit_ef_ref(g, e)
    assert bool(jnp.all(p1 == p2))
    np.testing.assert_allclose(m1, m2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(e1, e2, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,t,kv,g,d", [
    (1, 256, 1, 4, 64), (2, 1024, 2, 2, 128), (1, 512, 4, 1, 64),
])
@pytest.mark.parametrize("window", [0, 100])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_decode_matches_ref(b, t, kv, g, d, window, dtype):
    key = jax.random.PRNGKey(b * t + d + window)
    q = jax.random.normal(key, (b, kv, g, d), dtype)
    kc = jax.random.normal(jax.random.fold_in(key, 1), (b, t, kv, d), dtype)
    vc = jax.random.normal(jax.random.fold_in(key, 2), (b, t, kv, d), dtype)
    for pos in (3, t // 2, t - 1):
        out = swa_decode_attention(q, kc, vc, jnp.int32(pos), window=window,
                                   block_t=128, interpret=True)
        ref = swa_decode_ref(q, kc, vc, pos, window=window)
        atol = 3e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=atol)


def test_topk_wire_roundtrip_sums():
    """decompress_sum over P workers' payloads equals sum of dense Q(w)."""
    key = jax.random.PRNGKey(0)
    p, m, r, k = 4, 8, 128, 8
    dense_sum = jnp.zeros((m, r))
    vals, idxs = [], []
    for i in range(p):
        g = jax.random.normal(jax.random.fold_in(key, i), (m, r))
        e = jnp.zeros((m, r))
        v, ix, e2 = topk_ef(g, e, k=k, interpret=True)
        vals.append(v)
        idxs.append(ix)
        dense_sum = dense_sum + (g - e2)  # Q(w) = w - err
    got = decompress_sum(jnp.stack(vals), jnp.stack(idxs), r)
    np.testing.assert_allclose(got, dense_sum, rtol=1e-5, atol=1e-5)


def test_topk_blocklocal_contraction():
    """Kernel's row-local selection still satisfies Eq. 25 with the row
    ratio's gamma (the property Lemma 18 needs)."""
    key = jax.random.PRNGKey(3)
    m, r, k = 16, 256, 16
    w = jax.random.normal(key, (m, r))
    v, ix, err = topk_ef(w, jnp.zeros((m, r)), k=k, interpret=True)
    q = w - err
    gamma = (r - k) / r
    assert float(jnp.sum((q - w) ** 2)) <= gamma * float(jnp.sum(w ** 2))


@pytest.mark.parametrize("b,t,h,hd,n,chunk", [
    (1, 256, 2, 64, 32, 128), (2, 128, 4, 32, 64, 64), (1, 512, 1, 128, 64, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_matches_ref(b, t, h, hd, n, chunk, dtype):
    from repro.kernels.ssd import ssd_chunked_kernel, ssd_ref
    key = jax.random.PRNGKey(b * t + h)
    xh = jax.random.normal(key, (b, t, h, hd), dtype)
    a = -0.1 * jax.random.uniform(jax.random.fold_in(key, 1), (b, t, h))
    bm = jax.random.normal(jax.random.fold_in(key, 2), (b, t, n), dtype)
    cm = jax.random.normal(jax.random.fold_in(key, 3), (b, t, n), dtype)
    y1, s1 = ssd_chunked_kernel(xh, a, bm, cm, chunk=chunk, interpret=True)
    y2, s2 = ssd_ref(xh, a, bm, cm)
    atol = 2e-4 if dtype == jnp.float32 else 0.5
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=atol,
                               rtol=1e-2)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=atol,
                               rtol=1e-2)

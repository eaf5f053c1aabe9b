"""Streaming compaction of a threshold top-k selection: one Pallas pass.

Given the threshold bits ``t`` and ``last_tie`` of
:mod:`repro.kernels.topk_ef.select`, the kept entries of a row are those
with ``bits(|w|) > t``, or ``== t`` at a position up to ``last_tie``.
This kernel turns that set into ``(vals, idx)`` in ascending index order
and writes the residual ``where(kept, 0, w)``, reading the row once and
touching HBM at no single entry.

The row is seen as (L, 128) lines and streamed in blocks of lines over a
sequential grid.  Per block, on whole vector registers: the keep mask;
each line's inclusive count of kept lanes (one 0/1 matmul against a
triangle of ones, exact in float32); and the left-pack of each line, by a
7-step binary search over those counts with lane gathers, so lane ``q``
of a packed line holds the line's ``q``-th kept entry.  Then, line by
line, the scalar unit carries the running output position ``p`` (from
per-line counts the caller computed) and the packed line is rotated by
``p mod 128`` and stored into a VMEM ring of output lines: masked from
lane ``p mod 128`` on into line ``p // 128``, whole into the next line.
Lanes a line stores past its own entries hold stale data until the
following lines overwrite them, since each line writes only at or past its
own start.  The ring has two halves of :data:`HALF_LINES` lines; when the
position leaves a half, that half goes to the ``vals`` and ``idx`` outputs
in HBM by one DMA each, and the last step sends the half it ends in.  The
outputs are padded to whole halves; the caller trims them to ``k``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pltpu_compat import compiler_params

LANES = 128
#: Lines of the row in one grid step.
BLOCK_LINES = 512
#: Lines of one half of the output ring.  Larger than a block, so a block
#: (at most one output line per input line, plus the line after) fills at
#: most one half and never wraps onto the half it started in.
HALF_LINES = 1024
assert HALF_LINES > BLOCK_LINES


def vmem_bytes() -> int:
    """Double-buffered (B, 128) blocks of the row and the residual, the
    two packed (B, 128) blocks, the (2 H, 128) value and index rings (H =
    :data:`HALF_LINES`), and about six (B, 128) temporaries of the
    block's packing."""
    line = LANES * 4
    return (4 * BLOCK_LINES + 2 * BLOCK_LINES + 4 * HALF_LINES
            + 6 * BLOCK_LINES) * line


def out_lines(k: int) -> int:
    """Lines of the padded ``vals`` / ``idx`` outputs: whole ring halves up
    to and including the one that holds position ``k``."""
    return (k // (LANES * HALF_LINES) + 1) * HALF_LINES


def _compact_kernel(params_ref, counts_ref, lines_ref, err_ref, vals_hbm,
                    idx_hbm, ring_v, ring_i, packed_v, packed_i, state, sem,
                    *, n_lines: int):
    i = pl.program_id(0)
    b = BLOCK_LINES
    t, last_tie = params_ref[0], params_ref[1]

    @pl.when(i == 0)
    def _():
        state[0] = 0        # output position of the next kept entry
        state[1] = 0        # the ring half being filled, counted from 0

    w = lines_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (b, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, LANES), 1)
    first = (i * b + row) * LANES           # position of each line's lane 0
    pos = first + lane
    bits = jax.lax.bitcast_convert_type(jnp.abs(w), jnp.int32)
    keep = (((bits > t) | ((bits == t) & (pos <= last_tie)))
            & (pos < n_lines * LANES))      # a partial last block's tail
    err_ref[...] = jnp.where(keep, 0.0, w)

    # kept lanes up to and including each lane: 0/1 products, sums <= 128
    tri = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))
    incl = jnp.dot(keep.astype(jnp.bfloat16), tri.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    # src[q] = lanes whose count is <= q: the lane of the (q+1)-th kept
    # entry, for q below the line's count (past it, stale lanes)
    src = jnp.zeros((b, LANES), jnp.int32)
    for step in (64, 32, 16, 8, 4, 2, 1):
        probe = jnp.take_along_axis(incl, src + (step - 1), axis=1)
        src = jnp.where(probe <= lane, src + step, src)
    packed_v[...] = jnp.take_along_axis(w, src, axis=1)
    packed_i[...] = first + src

    ring = 2 * HALF_LINES
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def line(v, x, p):
        s = p & (LANES - 1)
        at = p // LANES
        lo = at & (ring - 1)
        hi = (at + 1) & (ring - 1)
        from_s = lane1 >= s
        v = pltpu.roll(v, s, 1)
        pltpu.store(ring_v.at[pl.ds(lo, 1), :], v, mask=from_s)
        ring_v[pl.ds(hi, 1), :] = v
        x = pltpu.roll(x, s, 1)
        pltpu.store(ring_i.at[pl.ds(lo, 1), :], x, mask=from_s)
        ring_i[pl.ds(hi, 1), :] = x

    def eight(j, p):
        at = pl.multiple_of(8 * j, 8)
        v8, x8 = packed_v[pl.ds(at, 8), :], packed_i[pl.ds(at, 8), :]
        for r in range(8):
            line(v8[r:r + 1], x8[r:r + 1], p)
            p = p + counts_ref[0, at + r]
        return p

    p = jax.lax.fori_loop(0, b // 8, eight, state[0])
    state[0] = p

    def flush(half):
        at = pl.multiple_of((half & 1) * HALF_LINES, HALF_LINES)
        to = pl.multiple_of(half * HALF_LINES, HALF_LINES)
        copies = [pltpu.make_async_copy(src_ring.at[pl.ds(at, HALF_LINES)],
                                        dst.at[pl.ds(to, HALF_LINES)],
                                        sem.at[n])
                  for n, (src_ring, dst) in enumerate(
                      ((ring_v, vals_hbm), (ring_i, idx_hbm)))]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    half = state[1]
    filled = p // LANES >= (half + 1) * HALF_LINES

    @pl.when(filled)
    def _():
        flush(half)
        state[1] = half + 1

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        flush(state[1])


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ef_compact(lines: jax.Array, counts: jax.Array, t: jax.Array,
               last_tie: jax.Array, *, k: int, interpret: bool = False):
    """lines (L, 128) f32, counts (L,) i32 kept entries a line, t and
    last_tie int32 scalars -> (vals (k,) f32, idx (k,) i32 ascending,
    new_err (L, 128) f32, written over ``lines``)."""
    n_lines = lines.shape[0]
    nb = pl.cdiv(n_lines, BLOCK_LINES)
    counts = jnp.pad(counts, (0, nb * BLOCK_LINES - n_lines)).reshape(
        nb, 1, BLOCK_LINES)
    params = jnp.stack([t, last_tie]).astype(jnp.int32)
    n_out = out_lines(k)
    block = pl.BlockSpec((BLOCK_LINES, LANES), lambda i, prm: (i, 0))
    err, vals, idx = pl.pallas_call(
        functools.partial(_compact_kernel, n_lines=n_lines),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((None, 1, BLOCK_LINES), lambda i, prm: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                block,
            ],
            out_specs=[
                block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((2 * HALF_LINES, LANES), jnp.float32),
                pltpu.VMEM((2 * HALF_LINES, LANES), jnp.int32),
                pltpu.VMEM((BLOCK_LINES, LANES), jnp.float32),
                pltpu.VMEM((BLOCK_LINES, LANES), jnp.int32),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((n_lines, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_out, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_out, LANES), jnp.int32),
        ],
        input_output_aliases={2: 0},
        compiler_params=compiler_params(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ef_compact",
    )(params, counts, lines)
    return vals.reshape(-1)[:k], idx.reshape(-1)[:k], err

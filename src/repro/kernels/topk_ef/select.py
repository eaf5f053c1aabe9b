"""Exact whole-row top-k by a search on the magnitudes' bit patterns.

The jnp twin of :func:`repro.kernels.topk_ef.ref.topk_ef_ref` for long
rows: the same selected set, ties included, and a bit-identical residual,
without sorting the row.  Only the set of the k largest magnitudes is
needed, not their order.  For ``|w| >= 0`` the float32 bit pattern read
as int32 is monotone in the value, so the k-th largest magnitude ``t`` is
found exactly by bisection on that integer: 31 passes, each one compare-
and-count over the row.  Every entry above ``t`` is taken, then the first
``k - count(above)`` entries equal to it (``lax.top_k``'s lowest-index-
wins tie rule).

The selection is compacted in index order with no scatter or gather per
entry of the row (the tied embedding is a 311M-entry row).  The row is
seen as 32-entry words of mask bits, packed by one matmul of the 0/1
mask (exact: distinct powers of two below 2**16 in float32).  Each
nonempty word marks, by one scatter, the payload slot where its entries
start; a running max over the slots then names each slot's word and
start, and the slot's column is found among the word's bits by a 5-step
rank search.  So the random accesses are per word and per payload entry,
about a fifth of the row.  Rows are worked one at a time as flat arrays:
a (1, N) array would fill one sublane of eight in the TPU's vector
registers.  The payload comes out in ascending index order, not magnitude
order; its consumers scatter distinct indices, so their dense result is
the same.  Where the caller asks for it, the compaction is instead
:func:`repro.kernels.topk_ef.compact.ef_compact`, one streaming Pallas
pass with no random access; the jnp one is its reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.topk_ef.compact import ef_compact

#: One above the bit pattern of +inf: every finite or infinite magnitude
#: lies below it, so ``count(bits >= _HI) < k`` holds from the start.
_HI = 0x7F800001
#: Halvings of ``[0, _HI)`` down to one candidate (``_HI < 2**31``).
_PASSES = 31
#: Lanes of a line: the TPU's vector width.
LANES = 128
#: Mask bits per packed word.
WORD = 32


def _bits(w: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(jnp.abs(w), jnp.int32)


def kth_largest_bits(w: jax.Array, k: int) -> jax.Array:
    """f32 array -> int32 scalar: the largest ``t`` with
    ``count(bits(|w|) >= t) >= k``, the k-th largest magnitude's bits."""

    def halve(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum(_bits(w) >= mid, dtype=jnp.int32) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid)

    lo, _ = jax.lax.fori_loop(0, _PASSES, halve,
                              (jnp.int32(0), jnp.int32(_HI)))
    return lo


def pack_words(mask: jax.Array) -> jax.Array:
    """(L, 128) bool -> (4 L,) uint32: word ``4 l + i`` holds lanes
    ``32 i .. 32 i + 31`` of line ``l``, lane ``32 i + b`` as bit ``b``."""
    lane = np.arange(LANES)
    # lane c adds 2**(c % 16) to half (c // 16) % 2 of word c // 32
    weights = np.zeros((LANES, 2, LANES // WORD), np.float32)
    weights[lane, lane // 16 % 2, lane // WORD] = 2.0 ** (lane % 16)
    h = jnp.einsum("lc,chi->hil", mask.astype(jnp.bfloat16),
                   jnp.asarray(weights, jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.uint32)
    return (h[0] | (h[1] << 16)).T.reshape(-1)


def select_bit(word: jax.Array, q: jax.Array) -> jax.Array:
    """Position of the set bit of rank ``q`` (from 0, lowest first) in
    each uint32 ``word``; ``q`` below the word's count."""
    pos = jnp.zeros(word.shape, jnp.uint32)
    q = q.astype(jnp.uint32)
    for s in (16, 8, 4, 2, 1):
        low = jax.lax.population_count((word >> pos) & ((1 << s) - 1))
        skip = q >= low
        q = jnp.where(skip, q - low, q)
        pos = jnp.where(skip, pos + s, pos)
    return pos.astype(jnp.int32)


def _upto(x: jax.Array) -> jax.Array:
    """uint32 with bits ``0 .. x`` set (none for x < 0, all for x > 30)."""
    n = jnp.clip(x + 1, 0, WORD).astype(jnp.uint32)
    return jnp.where(n >= WORD, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << n) - 1)


def _exclusive_cumsum(x: jax.Array) -> jax.Array:
    return jnp.cumsum(x, dtype=jnp.int32) - x


def _select_row(w: jax.Array, k: int, kernel: bool, interpret: bool):
    """(R,) f32 -> (vals (k,), idx (k,) ascending, new_err (R,))."""
    r = w.shape[0]
    n_lines = -(-r // LANES)
    # zero padding ranks after every real entry, so it is never taken
    lines = jnp.pad(w, (0, n_lines * LANES - r)).reshape(n_lines, LANES)
    with jax.named_scope("topk_threshold"):
        t = kth_largest_bits(lines, k)
    with jax.named_scope("topk_compact"):
        bits = _bits(lines)
        above, ties = pack_words(bits > t), pack_words(bits == t)
        pop = jax.lax.population_count
        need = k - jnp.sum(pop(above), dtype=jnp.int32)
        # the taken ties are the row's first ``need``: up to ``last_tie``
        n_ties = pop(ties).astype(jnp.int32)
        before = _exclusive_cumsum(n_ties)
        word = jnp.arange(ties.shape[0], dtype=jnp.int32)
        cut = (before < need) & (need <= before + n_ties)
        at_cut = WORD * word + select_bit(ties, need - before - 1)
        last_tie = jnp.max(jnp.where(cut, at_cut, -1))
        keep = above | (ties & _upto(last_tie - WORD * word))
        n_keep = pop(keep).astype(jnp.int32)
        if kernel:
            counts = n_keep.reshape(n_lines, LANES // WORD).sum(1)
            vals, idx, new_err = ef_compact(lines, counts, t, last_tie, k=k,
                                            interpret=interpret)
            return vals, idx, new_err.reshape(-1)[:r]
        # each nonempty word marks the slot where its entries start
        marks = jnp.full((k,), -1, jnp.int32).at[
            _exclusive_cumsum(n_keep)].max(
                jnp.where(n_keep > 0, word, -1), mode="drop",
                indices_are_sorted=True)
        slot = jnp.arange(k, dtype=jnp.int32)
        of_slot = jax.lax.cummax(marks)
        first = jax.lax.cummax(jnp.where(marks >= 0, slot, -1))
        kw = keep.at[of_slot].get(mode="promise_in_bounds")
        idx = WORD * of_slot + select_bit(kw, slot - first)
        vals = w.at[idx].get(mode="promise_in_bounds")
        bits = _bits(w)
        mask = (bits > t) | ((bits == t)
                             & (jnp.arange(r, dtype=jnp.int32) <= last_tie))
        new_err = jnp.where(mask, 0.0, w)
    return vals, idx, new_err


def topk_threshold_ef(g: jax.Array, err: jax.Array, *, k: int,
                      kernel: bool = False, interpret: bool = False):
    """g, err (M, R) -> (vals (M, k) f32, idx (M, k) i32, new_err (M, R)
    f32): :func:`topk_ef_ref`'s contract, with ``idx`` ascending.  The
    compaction is :func:`ef_compact`'s kernel where ``kernel`` is set
    (``interpret`` runs it in the interpreter), else the jnp one."""
    w = err + g.astype(jnp.float32)
    r = w.shape[1]
    if -(-r // LANES) * LANES >= 2 ** 31:
        raise ValueError(f"rows of {r} entries: positions overflow int32")
    if w.shape[0] == 1:     # the loop below would keep a (1, R) layout
        return tuple(x[None] for x in _select_row(w.reshape(r), k,
                                                  kernel, interpret))
    return jax.lax.map(
        lambda row: _select_row(row, k, kernel, interpret), w)

"""Dispatch for topk_ef: leaf-level compress/decompress used by the
production sync strategy (rows = model-sharded dims, cols compressed).

The kernel takes (8, R) row blocks whole in VMEM, so it needs M a multiple
of 8 and an R whose block fits :data:`repro.kernels.dispatch.VMEM_BUDGET`;
any other call resolves by the rule in `repro.kernels.dispatch`.  Where
the kernel does not take the call, the selection is exact either way and
picked by the row length alone: ``lax.top_k`` (a sort) for short rows,
the threshold search of `select.py` from :data:`THRESHOLD_MIN_R` on.  The
pick is counted at the ``topk_ef.select`` site as ``sort`` or
``threshold``.  The threshold search's compaction resolves at the
``topk_ef.compact`` site: the streaming kernel of `compact.py`, or the jnp
compaction as its reference."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import choose, record, vmem_why_not
from repro.kernels.topk_ef import compact
from repro.kernels.topk_ef.kernel import topk_ef, vmem_bytes
from repro.kernels.topk_ef.ref import topk_ef_ref
from repro.kernels.topk_ef.select import topk_threshold_ef

SITE = "topk_ef"
SELECT_SITE = "topk_ef.select"
COMPACT_SITE = "topk_ef.compact"
BLOCK_ROWS = 8
#: Row length from which the threshold search is faster than the sort of
#: ``lax.top_k`` on a v5e chip (k = R / 16; PERF.md section 6).
THRESHOLD_MIN_R = 1 << 15


def why_not(m: int, r: int, k: int) -> str | None:
    """None when the kernel takes an (M, R) leaf with k picks per row."""
    if m % BLOCK_ROWS:
        return f"M={m} rows is not a multiple of the {BLOCK_ROWS}-row block"
    return vmem_why_not(vmem_bytes(BLOCK_ROWS, r, k),
                        f"({BLOCK_ROWS}, {r}) row block")


def compact_why_not(r: int) -> str | None:
    """None when the compaction kernel takes a row of R entries."""
    block = compact.BLOCK_LINES * compact.LANES
    if -(-r // block) * block >= 2 ** 31:
        return f"R={r}: positions of the last block overflow int32"
    return vmem_why_not(compact.vmem_bytes(), "compaction block and ring")


def compress_leaf(g2d: jax.Array, err2d: jax.Array, ratio: float, *,
                  impl: str = "auto", payload: bool = True):
    """(M, R) leaf -> (vals, idx, new_err), k = max(1, round(R * ratio)).
    ``payload=False``: the caller keeps only ``new_err``, so a long row
    keeps the jnp compaction, whose gathers XLA drops as dead code."""
    m, r = g2d.shape
    k = max(1, int(round(r * ratio)))
    use_kernel, interpret = choose(SITE, impl, why_not(m, r, k))
    if use_kernel:
        return topk_ef(g2d, err2d, k=k, block_rows=BLOCK_ROWS,
                       interpret=interpret)
    if r < THRESHOLD_MIN_R:
        record(SELECT_SITE, "sort")
        return topk_ef_ref(g2d, err2d, k=k)
    record(SELECT_SITE, "threshold")
    use_kernel = interpret = False
    if payload:
        use_kernel, interpret = choose(COMPACT_SITE, impl,
                                       compact_why_not(r))
    return topk_threshold_ef(g2d, err2d, k=k, kernel=use_kernel,
                             interpret=interpret)


def decompress_sum(vals: jax.Array, idx: jax.Array, r: int) -> jax.Array:
    """Sum per-worker sparse payloads: vals/idx (P, M, k) -> dense (M, R)."""
    p, m, k = vals.shape
    dense = jnp.zeros((m, r), jnp.float32)

    def add_one(dense, pv):
        v, i = pv
        return dense.at[jnp.arange(m)[:, None], i].add(v), None

    dense, _ = jax.lax.scan(add_one, dense, (vals, idx))
    return dense

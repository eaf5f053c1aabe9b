"""The comparison that decides ``correct`` for a training cell.

The program's first steps and the float32 reference's are reduced to
four numbers; those the cell's file gives a limit are held to it, the
others are printed:

  loss_gap    the largest |loss - reference loss| over the followed steps;
  grad_gap    the first update as the optimizer holds it (its momentum
              after the first step that delivers one), by the worst leaf:
              | |g| - |g_ref| | / max(|g_ref|, median leaf |g_ref|);
  change_gap  the parameters' change over the followed steps, by the
              worst leaf, measured alike; leaves whose first reference
              gradient is under a thousandth of the median leaf's move by
              rounding alone and are left out;
  grad_diff_gap  the first update's distance from the reference's, by
              the worst leaf: |g - g_ref| / max(|g_ref|, median leaf
              |g_ref|). It grows with any error, where the gaps of norms
              above take the error's part along the update alone, so it
              parts a lower precision from the program where they do not
              (rwkv6, PERF.md).

Exact checks (limit 0) ride along: tokens that differ from the reference
stream, compilations inside the window, non-finite losses.
"""
from __future__ import annotations

import sys

import numpy as np

TINY_GRAD = 1e-3


def leaf_gaps(prog, ref, keep=None, scale=None) -> np.ndarray:
    """Per leaf: |prog - ref| / max(scale, median leaf scale), the scale
    being the reference's own norms unless given."""
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    scale = ref if scale is None else np.asarray(scale, float)
    if keep is not None:
        prog, ref, scale = prog[keep], ref[keep], scale[keep]
    return np.abs(prog - ref) / np.maximum(scale, float(np.median(scale)))


def _worst_leaf(prog, ref, names, keep=None, scale=None):
    gaps = leaf_gaps(prog, ref, keep, scale)
    if keep is not None:
        names = [n for n, k in zip(names, keep) if k]
    i = int(np.argmax(gaps))
    return float(gaps[i]), names[i]


def numbers(prog: dict, ref: dict, names: list) -> dict:
    """``prog`` / ``ref``: ``losses`` per step, per-leaf norms ``first``
    and ``change``, the first update's leaves ``first_host``; ``ref`` also
    ``grad0``."""
    grad0 = np.asarray(ref["grad0"], float)
    keep = grad0 >= TINY_GRAD * np.median(grad0)
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"],
                                              ref["losses"]))
    grad_gap, grad_leaf = _worst_leaf(prog["first"], ref["first"], names)
    change_gap, change_leaf = _worst_leaf(prog["change"], ref["change"],
                                          names, keep)
    dist = [float(np.linalg.norm((a - b).ravel()))
            for a, b in zip(prog["first_host"], ref["first_host"])]
    grad_diff_gap, diff_leaf = _worst_leaf(dist, np.zeros(len(dist)), names,
                                           scale=ref["first"])
    return {"loss_gap": float(loss_gap), "grad_gap": grad_gap,
            "change_gap": change_gap, "grad_diff_gap": grad_diff_gap,
            "_diff_leaf": diff_leaf, "_grad_leaf": grad_leaf,
            "_change_leaf": change_leaf,
            "_left_out": [n for n, k in zip(names, keep) if not k]}


def judge(values: dict, limits: dict):
    """-> (correct, checks) with checks ``{name: {value, limit}}``."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def report(checks: dict) -> None:
    for k, c in checks.items():
        mark = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {k} {c['value']!r} limit {c['limit']!r} {mark}",
              file=sys.stderr, flush=True)

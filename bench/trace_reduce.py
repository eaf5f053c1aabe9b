"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
device time and the device's idle gaps, each named by what the host was
doing in it.

The window is the span of the host events named ``window`` (the
benchmark's step annotations): from the first one's start to the last
one's end. Within it, per device plane (``/device:TPU:<n>``):

  busy    the union of the intervals of the ops on its ``XLA Ops`` line;
  idle    the window less that union, as gaps;
  ops     each op's summed duration, clipped to the window, by its HLO
          instruction name (the event text before `` = ``, without ``%``).

A gap is named by the host event that covers at least half of it and is
the shortest such (the innermost); failing that, by the host event that
overlaps it most; failing that, ``"none"``. The window annotations
themselves never name a gap.

    python bench/trace_reduce.py <trace.xplane.pb> [window]
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_trace(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for e in line.events:
                yield line.name, e.name, float(e.start_ns), \
                    float(e.duration_ns)


def op_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _gaps(union, lo, hi):
    gaps, cur = [], lo
    for s, e in union:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def _name_gap(lo, hi, host):
    length = hi - lo
    inner, widest = None, None
    for name, s, d in host:
        ov = min(hi, s + d) - max(lo, s)
        if ov <= 0:
            continue
        if ov >= 0.5 * length and (inner is None or d < inner[1]):
            inner = (name, d)
        if widest is None or ov > widest[1]:
            widest = (name, ov)
    if inner:
        return inner[0]
    return widest[0] if widest else "none"


def reduce(path, window: str = "bench_step", top: int = 10) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    host_plane = [p for p in pd.planes if p.name == HOST_PLANE]
    host = [(n, s, d) for p in host_plane for _, n, s, d in _events(p)
            if d > 0]
    mark = re.compile(rf"^{re.escape(window)}(#|$)")
    marks = [(s, s + d) for n, s, d in host if mark.match(n)]
    if not marks:
        raise ValueError(f"no host event named {window!r} in {path}")
    if not devices:
        raise ValueError(f"no device plane in {path}")
    lo = min(s for s, _ in marks)
    hi = max(e for _, e in marks)
    others = [(n, s, d) for n, s, d in host if not mark.match(n)]
    busy, ops, gaps = [], {}, []
    for i, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        iv = []
        for _, name, s, d in _events(plane, OPS_LINE):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                iv.append((a, b))
                name = op_name(name)
                ops[name] = ops.get(name, 0.0) + (b - a)
        union = _union(iv)
        busy.append(sum(e - s for s, e in union))
        if i == 0:
            gaps = _gaps(union, lo, hi)
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "devices": n,
        "steps": len(marks),
        "ops": {k: v / n * 1e-9 for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[_name_gap(a, b, others), (b - a) * 1e-9]
                      for a, b in gaps[:top]],
    }


def breakdown(red: dict, top: int = 10) -> dict:
    return {"device_ops": [[k, v] for k, v in
                           list(red["ops"].items())[:top]],
            "idle_gaps": red["idle_gaps"][:top]}


if __name__ == "__main__":
    red = reduce(sys.argv[1], *sys.argv[2:3])
    red["ops"] = dict(list(red["ops"].items())[:20])
    print(json.dumps(red, indent=1))

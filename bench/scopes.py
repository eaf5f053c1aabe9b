"""Device time by the program's named scopes, and host time by span, from
a profiler trace (``.xplane.pb``) and the compiled step's HLO text.

The program marks its layers with ``jax.named_scope`` (``src/repro``):

  model_fwd_bwd  dist/train.mean_grads: the loss and its gradient
  lm_head        the final norm, the head product, log-softmax and NLL
  wkv            models/rwkv6: the chunked WKV6 recurrence
  optimizer      dist/train.guarded_update: the optimizer's update
  compress       dist/async_engine: the top-k / one-bit selection and the
                 error-feedback residual
  deliver        dist/async_engine: the payload gather, the deposit into
                 the delay rings and the takes from them

A scope ends up as a component of an instruction's ``op_name`` metadata,
anywhere in the path and possibly wrapped by a transform
(``jit(step)/model_fwd_bwd/transpose(jvp(lm_head))/...``). The trace
names each device op by its instruction only, so the HLO text maps it to
its scopes. Within the window (as in ``trace_reduce``: the host events
named ``window``) and per device plane:

  scopes[s]        the union of the intervals of the ops that carry s (a
                   ``while`` and its body's ops are counted once); nested
                   scopes overlap, so the values do not add up to busy
  (unscoped)       busy time under ops found in the HLO text that carry
                   none of the scopes
  (not in HLO)     busy time under ops the HLO text does not hold (other
                   programs: transfers, initialisation)
  host[name]       [seconds, count] of the host events of that name,
                   clipped to the window

Seconds are averaged over the device planes, like ``busy_s``.

    python3 -m bench.scopes <profile dir> [window]

run from the root of the checkout, reads the newest ``.xplane.pb`` under
the directory and ``step.hlo.txt`` in it (what ``python -m
repro.launch.train --profile-dir`` writes).
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

from bench import trace_reduce as T

SCOPES = ("model_fwd_bwd", "lm_head", "wkv", "optimizer", "compress",
          "deliver")
UNSCOPED = "(unscoped)"
NOT_IN_HLO = "(not in HLO)"

INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# a path component, with any transform wrappers: "transpose(jvp(wkv))"
COMPONENT = re.compile(r"^(?:[\w\-]+\()*([\w.\-]*)\)*$")


def op_names(hlo_text: str) -> dict:
    """Instruction name -> its ``op_name`` metadata ("" where it has
    none), over every computation of the module."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            meta = OP_NAME.search(line)
            out[m.group(1)] = meta.group(1) if meta else ""
    return out


def scopes_of(op_name: str, scopes=SCOPES) -> frozenset:
    """The scopes among ``scopes`` that are components of ``op_name``."""
    found = set()
    for part in op_name.split("/"):
        m = COMPONENT.match(part)
        if m and m.group(1) in scopes:
            found.add(m.group(1))
    return frozenset(found)


def _length(intervals) -> float:
    return sum(e - s for s, e in T._union(intervals))


def reduce(path, hlo_text: str, window: str = "bench_step",
           scopes=SCOPES, top: int = 10) -> dict:
    """Per-scope device seconds and per-span host seconds in the window
    (see the module docstring), with ``window_s``, ``busy_s`` and
    ``steps`` as ``trace_reduce.reduce`` gives them, and the ``top``
    longest ops that carry no scope (with their ``op_name``) or are not
    in the HLO text."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices = sorted((p for p in pd.planes if T.DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)
    host = [(n, s, d) for p in pd.planes if p.name == T.HOST_PLANE
            for _, n, s, d in T._events(p) if d > 0]
    mark = re.compile(rf"^{re.escape(window)}(#|$)")
    marks = [(s, s + d) for n, s, d in host if mark.match(n)]
    if not marks:
        raise ValueError(f"no host event named {window!r} in {path}")
    if not devices:
        raise ValueError(f"no device plane in {path}")
    lo = min(s for s, _ in marks)
    hi = max(e for _, e in marks)

    names = op_names(hlo_text)
    carried = {}                 # instruction -> its scopes, memoised
    totals = dict.fromkeys((*scopes, UNSCOPED, NOT_IN_HLO), 0.0)
    busy = 0.0
    loose = {UNSCOPED: {}, NOT_IN_HLO: {}}
    for plane in devices:
        every, found, scoped = [], [], []
        by_scope = {s: [] for s in scopes}
        for _, event, s, d in T._events(plane, T.OPS_LINE):
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            op = T.op_name(event)
            every.append((a, b))
            if op not in names:
                bucket = loose[NOT_IN_HLO]
                bucket[op] = bucket.get(op, 0.0) + (b - a)
                continue
            found.append((a, b))
            if op not in carried:
                carried[op] = scopes_of(names[op], scopes)
            if not carried[op]:
                bucket = loose[UNSCOPED]
                bucket[op] = bucket.get(op, 0.0) + (b - a)
                continue
            scoped.append((a, b))
            for sc in carried[op]:
                by_scope[sc].append((a, b))
        for sc, iv in by_scope.items():
            totals[sc] += _length(iv)
        n_every, n_found = _length(every), _length(found)
        totals[UNSCOPED] += n_found - _length(scoped)
        totals[NOT_IN_HLO] += n_every - n_found
        busy += n_every
    n = len(devices)

    spans = {}
    for name, s, d in host:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            secs, count = spans.get(name, (0.0, 0))
            spans[name] = (secs + (b - a), count + 1)

    def ranked(ops):
        return [[k, v / n * 1e-9, names.get(k, "")] for k, v in
                sorted(ops.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / n * 1e-9,
        "steps": len(marks),
        "scopes": {k: v / n * 1e-9 for k, v in totals.items()},
        "host": {k: [v[0] * 1e-9, v[1]] for k, v in sorted(
            spans.items(), key=lambda kv: -kv[1][0])},
        "top_unscoped": ranked(loose[UNSCOPED]),
        "top_not_in_hlo": ranked(loose[NOT_IN_HLO]),
    }


def table(red: dict) -> str:
    """Per-step milliseconds of each scope and its share of busy time."""
    steps, busy = max(red["steps"], 1), red["busy_s"] or 1.0
    rows = [f"{'scope':<16}{'ms/step':>12}{'% busy':>9}"]
    for k, v in red["scopes"].items():
        rows.append(f"{k:<16}{1e3 * v / steps:>12.3f}"
                    f"{100 * v / busy:>9.2f}")
    rows.append(f"{'busy':<16}{1e3 * red['busy_s'] / steps:>12.3f}")
    rows.append(f"{'window':<16}{1e3 * red['window_s'] / steps:>12.3f}")
    return "\n".join(rows)


def main(directory: str, window: str = "train_step") -> None:
    trace = T.find_trace(directory)
    hlo = (Path(directory) / "step.hlo.txt").read_text()
    red = reduce(trace, hlo, window)
    print(table(red), file=sys.stderr)
    print(json.dumps(red, indent=1))


if __name__ == "__main__":
    main(*sys.argv[1:3])

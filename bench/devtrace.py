"""Reduce a profiler trace to device metrics.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it. A device is a plane named
``/device:TPU:<i>``; its line ``XLA Ops`` holds one event per operation
run, with start and duration in nanoseconds on the same clock as the
host's planes. The traced window is the host annotation ``WINDOW``.

  busy      union of the operation intervals inside the window
  idle      1 - busy / window
  kernel    summed duration of the operations whose name holds a pattern
            (of leaf operations: a ``while`` holds its body's operations)
  gaps      the longest idle stretches, each named by the innermost host
            annotation (a program span) open at its middle
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.traced_bracket"
OP_LINE = "XLA Ops"
TOP = 10


def record(fn, log_dir: str):
    """Run ``fn()`` under the profiler inside the ``WINDOW`` annotation.
    Returns (``fn``'s result, path of the written ``.xplane.pb``)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no trace under {log_dir}")
    return out, paths[-1]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


_OP = re.compile(r"^%?([\w.-]+) = (\(?[a-z0-9]+\[[0-9,]*\])")


def op_label(hlo: str) -> str:
    """A device operation's short name: ``%fusion.21 = s32[4960,4960]{..}
    fusion(...)`` becomes ``fusion.21 s32[4960,4960]``."""
    m = _OP.match(hlo)
    return f"{m.group(1)} {m.group(2).lstrip('(')}" if m else hlo[:80]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def leaves(ops: List[Tuple[str, float, float]]) -> List[Tuple[str, float, float]]:
    """The operations that hold no other: a ``while`` or ``conditional`` is
    on the line together with the operations of its body."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= o[2]]


def device_ops(pd) -> Dict[str, List[Tuple[str, float, float]]]:
    """Device plane name -> its operations (name, start_ns, end_ns)."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == OP_LINE:
                out[plane.name] = _events(line)
    return out


def host_annotations(pd) -> List[Tuple[str, float, float]]:
    """Every event on the host's planes (annotations among them)."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(_events(line))
    return out


def window_of(host: Sequence[Tuple[str, float, float]]) -> Tuple[float, float]:
    spans = [(s, e) for n, s, e in host if n == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def merge(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _name_gap(host, names: Sequence[str], mid: float) -> str:
    best, depth = "host", None
    for n, s, e in host:
        if n in names and s <= mid <= e and (depth is None or s >= depth):
            best, depth = n, s
    return best


def reduce(pd, kernels: Dict[str, str], span_names: Sequence[str] = ()):
    """Device metrics of one trace. ``kernels`` maps a kernel's name to a
    substring of its operations' names. Times in seconds."""
    host = host_annotations(pd)
    lo, hi = window_of(host)
    window_s = (hi - lo) / 1e9
    devs = device_ops(pd)
    if not devs:
        raise ValueError("no device operations in the trace")
    busy_each, op_time, kernel_s, kernel_calls = [], defaultdict(float), \
        defaultdict(float), defaultdict(int)
    gaps: List[Tuple[float, float]] = []
    for i, (plane, ops) in enumerate(sorted(devs.items())):
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        busy = merge([(s, e) for _, s, e in inside], lo, hi)
        busy_each.append(sum(e - s for s, e in busy) / 1e9)
        for n, s, e in leaves(inside):
            op_time[op_label(n)] += (e - s) / 1e9
            for k, pat in kernels.items():
                if pat in n:
                    kernel_s[k] += (e - s) / 1e9
                    kernel_calls[k] += 1
        if i == 0:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                    if b > a]
    busy_s = sum(busy_each) / len(busy_each)
    n_dev = len(busy_each)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "kernel_s": {k: v / n_dev for k, v in kernel_s.items()},
        "kernel_calls": {k: v // n_dev for k, v in kernel_calls.items()},
        "device_ops": sorted(([n, t / n_dev] for n, t in op_time.items()),
                             key=lambda x: -x[1])[:TOP],
        "idle_gaps": [[_name_gap(host, span_names, (a + b) / 2), (b - a) / 1e9]
                      for a, b in sorted(gaps, key=lambda g: g[0] - g[1])
                      [:TOP]],
    }


def reduce_file(path: str, kernels: Dict[str, str],
                span_names: Sequence[str] = ()) -> Optional[dict]:
    return reduce(load(path), kernels, span_names)

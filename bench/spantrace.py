"""The program's spans read for their layers' time: device seconds from a
profiler trace, and host seconds from a tracer's records.

The program opens a ``jax.profiler.TraceAnnotation`` for every span it
records (``repro.runtime.telemetry``), under the span's own name, so the
host planes of a trace hold the spans beside the device's operations.

  span_device_s   per span name, the device-busy seconds (the union of the
                  first device's leaf operations) inside the union of that
                  name's annotations in the traced window (``devtrace.WINDOW``)
  unspanned_s     per ``session.estimate`` span, its duration less the union
                  of the layer spans it holds: estimator glue and host
                  reductions that no layer span covers

``run.py`` does not read these yet (PERF.md, Open questions).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from bench import devtrace, run

# Program spans that time one layer each, and the query's root span that
# holds them.
LAYER_SPANS = run.SPAN_NAMES
ROOT_SPAN = "session.estimate"
SPAN_NAMES = LAYER_SPANS + (ROOT_SPAN,)
# Layers by the spans that time them.
LAYERS = {"sssp": ("sssp.solve",),
          "grow": ("engine.stage", "engine.oneshot", "engine.finalize"),
          "quotient": ("quotient.build", "quotient.solve")}


def overlap(a: Sequence[Tuple[float, float]],
            b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_device_s(pd, names: Sequence[str] = SPAN_NAMES) -> Dict[str, float]:
    """Device-busy seconds inside each name's annotations; a name with no
    annotation in the window is left out."""
    host = devtrace.host_annotations(pd)
    lo, hi = devtrace.window_of(host)
    devs = devtrace.device_ops(pd)
    if not devs:
        raise ValueError("no device operations in the trace")
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in devs[min(devs)]
              if e > lo and s < hi]
    busy = devtrace.merge([(s, e) for _, s, e in devtrace.leaves(inside)],
                          lo, hi)
    out = {}
    for name in names:
        spans = devtrace.merge([(s, e) for n, s, e in host if n == name],
                               lo, hi)
        if spans:
            out[name] = overlap(busy, spans) / 1e9
    return out


def layer_device_s(per_span: Dict[str, float]) -> Dict[str, float]:
    """``span_device_s`` summed per layer (``LAYERS``); a layer none of
    whose spans was found is left out."""
    return {layer: sum(per_span[n] for n in names if n in per_span)
            for layer, names in LAYERS.items()
            if any(n in per_span for n in names)}


def unspanned_s(spans, layers: Sequence[str] = LAYER_SPANS) -> List[float]:
    """Per ``ROOT_SPAN`` record, in start order: its duration less the union
    of the layer spans under it (``telemetry.SpanRecord``\\ s of one
    tracer)."""
    by_index = {s.index: s for s in spans}

    def root_of(s):
        while s.parent is not None:
            s = by_index.get(s.parent)
            if s is None:
                return None
            if s.name == ROOT_SPAN:
                return s.index
        return None

    held = defaultdict(list)
    for s in spans:
        if s.name in layers:
            r = root_of(s)
            if r is not None:
                held[r].append((s.start, s.start + s.duration))
    out = []
    for s in sorted(spans, key=lambda s: s.index):
        if s.name == ROOT_SPAN:
            covered = devtrace.merge(held[s.index], s.start,
                                     s.start + s.duration)
            out.append(s.duration - sum(e - b for b, e in covered))
    return out

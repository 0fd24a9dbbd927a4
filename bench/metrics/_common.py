"""Helpers the metric readers share (not a metric: no name of
``BENCHMARK.json`` starts with ``_``)."""
from __future__ import annotations

QUOTIENT_METHODS = ("cluster-quotient", "cascade")


def done(run):
    return [q for q in run.queries if q.error is None]


def parts(result):
    """The single estimates a query's answer is made of."""
    return list(getattr(result, "estimates", {}).values()) or [result]


def span_seconds_per_query(run, names):
    """Seconds the window's spans of these names took, per answered query
    (``None`` where the window recorded no such span)."""
    q = done(run)
    spans = [s for s in run.spans if s.name in names]
    if not q or not spans:
        return None
    return sum(s.duration for s in spans) / len(q)


def quotient_field_per_query(run, get):
    """Mean over answered queries of ``get(estimate)`` of their
    cluster-quotient estimates (``None`` where no query has one)."""
    vals = [get(e) for q in done(run) for e in parts(q.result)
            if getattr(e, "method", None) in QUOTIENT_METHODS]
    return sum(vals) / len(done(run)) if vals else None

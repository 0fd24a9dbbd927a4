"""Decomposition engine: seconds per bracket in the ``engine.stage``,
``engine.oneshot`` and ``engine.finalize`` spans (host clock; each closes
after a host fetch)."""
from bench.metrics._common import span_seconds_per_query


def read(run):
    return span_seconds_per_query(
        run, ("engine.stage", "engine.oneshot", "engine.finalize"))

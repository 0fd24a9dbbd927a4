"""SSSP bounds: seconds per bracket in the ``sssp.solve`` spans (host
clock; each closes after a host fetch)."""
from bench.metrics._common import span_seconds_per_query


def read(run):
    return span_seconds_per_query(run, ("sssp.solve",))

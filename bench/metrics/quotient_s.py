"""Quotient and solve: seconds per bracket in the ``quotient.build`` and
``quotient.solve`` spans (host clock; each closes after a host fetch)."""
from bench.metrics._common import span_seconds_per_query


def read(run):
    return span_seconds_per_query(run, ("quotient.build", "quotient.solve"))

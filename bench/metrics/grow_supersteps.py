"""Decomposition engine: growing supersteps per bracket
(``DiameterEstimate.growing_steps`` of the cluster-quotient estimate)."""
from bench.metrics._common import quotient_field_per_query


def read(run):
    return quotient_field_per_query(run, lambda e: e.growing_steps)

"""Quotient and solve: Bellman-Ford supersteps of the quotient solve per
bracket (``PipelineMetrics.solve_supersteps``)."""
from bench.metrics._common import quotient_field_per_query


def read(run):
    return quotient_field_per_query(run, lambda e: e.pipeline.solve_supersteps)

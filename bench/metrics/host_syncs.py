"""Session and estimators: host syncs per bracket as the program counts
them (``PipelineMetrics.total_host_syncs``); the check holds them equal to
the transfers ``guard.measured_transfers`` saw."""
from bench.metrics._common import done


def read(run):
    q = done(run)
    if not q:
        return None
    return sum(r.result.pipeline.total_host_syncs for r in q) / len(q)

"""SSSP bounds: milliseconds per Bellman-Ford superstep, the ``sssp.solve``
spans' seconds over the supersteps they report. Steadier than ``sssp_s``:
the number of supersteps follows the query's source, their cost does not."""


def read(run):
    spans = [s for s in run.spans if s.name == "sssp.solve"]
    steps = sum(s.attrs.get("supersteps", 0) for s in spans)
    if not steps:
        return None
    return 1000.0 * sum(s.duration for s in spans) / steps

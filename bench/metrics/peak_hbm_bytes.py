"""``memory_stats()["peak_bytes_in_use"]`` of the first chip after the
window: the graph, the planes and the largest quotient solve."""


def read(run):
    return run.peak_hbm_bytes

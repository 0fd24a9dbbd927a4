"""Edge-relax kernel: share of its roofline in %. The least time is the
bytes every traced call must stream (``bench.costs.edge_relax_bytes`` of the
session's kernel shapes) over the chip's published HBM bandwidth
(``bench.peaks``); the time is the summed device time of the kernel's
operations in the traced bracket."""
from bench.costs import edge_relax_bytes
from bench.peaks import peak


def read(run):
    t = run.trace
    shapes = run.kernel_shapes.get("edge_relax")
    if not t or not shapes:
        return None
    calls, seconds = t["kernel_calls"].get("edge_relax", 0), \
        t["kernel_s"].get("edge_relax", 0.0)
    if not calls or seconds <= 0:
        return None
    least = calls * edge_relax_bytes(**shapes) / peak(run.device_kind,
                                                      "hbm_bytes_per_s")
    return 100.0 * least / seconds

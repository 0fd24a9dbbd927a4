"""The trace reduction on a small trace recorded on one TPU v5e (one
cluster-quotient query on a 600-node lattice graph, ``data/``)."""
import gzip
import os

import pytest

from bench import devtrace, run

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "small.xplane.pb.gz")


def load_recorded():
    import jax

    with gzip.open(TRACE, "rb") as f:
        return jax.profiler.ProfileData.from_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def reduced():
    return devtrace.reduce(load_recorded(), run.KERNELS, run.SPAN_NAMES)


def test_busy_within_window(reduced):
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert 0 <= reduced["idle_share"] < 1
    assert reduced["idle_share"] == pytest.approx(
        1 - reduced["busy_s"] / reduced["window_s"])


def test_edge_relax_kernel_is_found(reduced):
    assert reduced["kernel_calls"]["edge_relax"] > 0
    assert 0 < reduced["kernel_s"]["edge_relax"] <= reduced["busy_s"]


def test_breakdown_lists(reduced):
    ops, gaps = reduced["device_ops"], reduced["idle_gaps"]
    assert 0 < len(ops) <= devtrace.TOP and 0 < len(gaps) <= devtrace.TOP
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert sum(t for _, t in gaps) <= reduced["window_s"]


def test_window_annotation_present():
    lo, hi = devtrace.window_of(devtrace.host_annotations(load_recorded()))
    assert hi > lo


@pytest.mark.parametrize("intervals,want", [
    ([(0, 2), (1, 3), (5, 6)], [(0, 3), (5, 6)]),
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)]),
    ([(-5, 1), (9, 20)], [(0, 1), (9, 10)]),
    ([(2, 2)], []),
])
def test_merge(intervals, want):
    assert devtrace.merge(intervals, 0, 10) == want


def test_leaves_drop_the_operations_that_hold_others():
    ops = [("while", 0, 10), ("body.a", 1, 4), ("body.b", 5, 9),
           ("after", 10, 12)]
    assert [n for n, _, _ in devtrace.leaves(ops)] == ["body.a", "body.b",
                                                       "after"]


def test_op_label():
    assert devtrace.op_label(
        "%fusion.21 = s32[4960,4960]{1,0:T(8,128)} fusion(s32[18432]") == \
        "fusion.21 s32[4960,4960]"
    assert devtrace.op_label(
        "%_edge_relax_pallas_jit.4 = (s32[3,1,256]{2,1,0}, s32[3,1,256]) "
        "custom-call(s32[5]").startswith("_edge_relax_pallas_jit.4")

"""The program's spans read for their layers' time (``spantrace``), on a
small trace recorded on one TPU v5e under the program's own annotations
(one bracket query, walk and cluster quotient, on the Kronecker graph at
scale 9, ``data/bracket_small.xplane.pb.gz``); the set-up builds a tracer
attributes against the harness's count; and ``trace_probe.py`` end to end
on the CPU at a tiny size."""
import gzip
import json
import os

import pytest

from bench import devtrace, run, spantrace, spec, trace_probe
from bench.tests import tiny
from bench.tests.test_devtrace import load_recorded as load_old

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "bracket_small.xplane.pb.gz")


def load_bracket():
    import jax

    with gzip.open(TRACE, "rb") as f:
        return jax.profiler.ProfileData.from_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def bracket():
    pd = load_bracket()
    return (devtrace.reduce(pd, run.KERNELS, spantrace.SPAN_NAMES),
            spantrace.span_device_s(pd))


def test_every_span_name_of_a_bracket_is_found(bracket):
    _, per_span = bracket
    assert {"sssp.solve", "engine.stage", "engine.finalize", "quotient.build",
            "quotient.solve", spantrace.ROOT_SPAN} <= set(per_span)


def test_span_device_seconds_within_busy(bracket):
    red, per_span = bracket
    assert all(0 < v <= red["busy_s"] for v in per_span.values())


def test_disjoint_layer_spans_sum_within_busy(bracket):
    red, per_span = bracket
    layers = spantrace.layer_device_s(per_span)
    assert set(layers) == {"sssp", "grow", "quotient"}
    assert 0 < sum(layers.values()) <= red["busy_s"]


def test_the_query_span_holds_nearly_all_device_time(bracket):
    red, per_span = bracket
    assert per_span[spantrace.ROOT_SPAN] >= 0.95 * red["busy_s"]


def test_query_span_names_the_gaps_outside_layer_spans(bracket):
    """The longest gap lies in the query but in no layer span: named by the
    query's span, where the layer spans alone leave it to the host."""
    red, _ = bracket
    assert red["idle_gaps"][0][0] == spantrace.ROOT_SPAN
    assert all(n in spantrace.SPAN_NAMES for n, _ in red["idle_gaps"])
    layers_only = devtrace.reduce(load_bracket(), run.KERNELS, run.SPAN_NAMES)
    assert layers_only["idle_gaps"][0][0] == "host"


def test_names_without_annotations_are_left_out():
    """The older trace, recorded before the query had a root span."""
    per_span = spantrace.span_device_s(load_old())
    assert spantrace.ROOT_SPAN not in per_span
    assert "quotient.solve" in per_span


@pytest.mark.parametrize("a,b,want", [
    ([(0, 2), (5, 9)], [(1, 6)], 2),
    ([(0, 10)], [(2, 3), (4, 6)], 3),
    ([(0, 1)], [(1, 2)], 0),
    ([], [(0, 5)], 0),
])
def test_overlap(a, b, want):
    assert spantrace.overlap(a, b) == want
    assert spantrace.overlap(b, a) == want


def _rec(name, index, parent, start, duration):
    from repro.runtime.telemetry import SpanRecord

    return SpanRecord(name=name, start=start, duration=duration, depth=0,
                      index=index, parent=parent)


def test_unspanned_seconds_per_query():
    spans = [
        _rec("session.estimate", 0, None, 0.0, 10.0),
        _rec("sssp.solve", 1, 0, 1.0, 3.0),
        _rec("other", 2, 0, 4.0, 2.0),          # not a layer span
        _rec("engine.stage", 3, 2, 4.5, 1.0),   # a layer under it
        _rec("cascade.level", 4, 0, 7.0, 2.0),
        _rec("engine.stage", 5, 4, 7.5, 1.0),   # held twice: counted once
        _rec("session.estimate", 6, None, 20.0, 1.0),
        _rec("sssp.solve", 7, None, 30.0, 1.0),  # under no query
    ]
    assert spantrace.unspanned_s(spans) == pytest.approx([4.0, 1.0])


@pytest.fixture
def root(tmp_path, monkeypatch):
    r = tiny.make_root(tmp_path / "checkout")
    tiny.use_root(monkeypatch, r, tmp_path)
    return r


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_setup_builds_equal_the_programs_counted(root, cell):
    """The exact check: what a tracer installed over set-up attributes (its
    spans' builds plus those outside every span) is what the harness's
    counter counts over the same interval."""
    import jax

    jax.clear_caches()    # so that this set-up builds its programs again
    c = spec.load_cell(cell)
    cr, out = trace_probe.setup(c, 2**31 + 404, 0.5)
    cr.close()
    assert out["setup_builds"] == out["setup_programs_counted"] > 0
    assert out["setup_build_s"] >= out["setup_compile_s_counted"] > 0
    assert sum(b for b, _ in out["builds_by_span"].values()) + \
        out["builds_outside"] == out["setup_builds"]
    assert out["open_s"] > 0


def test_probe_runs_on_the_cpu(root, capsys, monkeypatch):
    """The CPU's trace has no TPU plane, so the reduction reads the recorded
    bracket in its place."""
    monkeypatch.setattr(devtrace, "load", lambda path: load_bracket())
    rc = trace_probe.main(["--workload", "kron.bracket", "--seed",
                           str(2**31 + 99), "--seconds", "0.5",
                           "--pairs", "1"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["setup_builds"] == out["setup_programs_counted"]
    assert len(out["pairs"]["untraced_s"]) == len(out["pairs"]["traced_s"]) \
        == 1
    prof = out["profile"]
    assert prof["error"] is None and len(prof["unspanned_s"]) == 1
    assert 0 < prof["layers_over_busy"] <= 1
    assert out["device"]["platform"] == "cpu"

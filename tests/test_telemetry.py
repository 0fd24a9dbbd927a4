"""Telemetry subsystem tests: span tracer semantics (nesting, exclusive
transfer attribution), streaming-histogram quantiles against numpy on
adversarial distributions, exporter round-trips, the TransferMeter
bounded-memory regression, and the zero-extra-sync contract — the PR 8
transfer-equality assertions must hold bit-identically with tracing on.
"""
import json

import numpy as np
import pytest

from repro.analysis import guard
from repro.runtime import telemetry
from repro.runtime.telemetry import (
    MetricsRegistry,
    StreamingHistogram,
    Tracer,
    export_chrome_trace,
    export_jsonl,
    export_prometheus,
    write_telemetry,
)


# ---------------------------------------------------------------------------
# span tracer: nesting, null path, exclusive attribution
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_without_tracer_is_null_singleton(self):
        s1 = telemetry.span("a", x=1)
        s2 = telemetry.span("b")
        assert s1 is s2 is telemetry.NULL_SPAN
        with s1 as sp:           # usable, inert
            sp.set(anything=2)

    def test_nesting_parent_child_indices(self):
        t = Tracer()
        with telemetry.tracing(t):
            with telemetry.span("outer"):
                with telemetry.span("inner.a"):
                    pass
                with telemetry.span("inner.b"):
                    pass
        by_name = {s.name: s for s in t.spans}
        outer, a, b = by_name["outer"], by_name["inner.a"], by_name["inner.b"]
        assert outer.parent is None and outer.depth == 0
        assert a.parent == outer.index and a.depth == 1
        assert b.parent == outer.index and b.depth == 1
        assert a.index < b.index               # start order
        assert t.spans[-1].name == "outer"     # close order
        assert outer.duration >= a.duration + b.duration - 1e-9

    def test_non_lifo_close_raises(self):
        t = Tracer()
        with telemetry.tracing(t):
            s1 = telemetry.span("a")
            s2 = telemetry.span("b")
            s1.__enter__()
            s2.__enter__()
            with pytest.raises(RuntimeError):
                s1.__exit__(None, None, None)
            s2.__exit__(None, None, None)
            s1.__exit__(None, None, None)

    def test_exclusive_attribution_partitions_measured(self):
        """The headline invariant: under a root span, the sum of per-span
        EXCLUSIVE transfer counts equals the measured total — every fetch
        is attributed to exactly one (the innermost live) span."""
        t = Tracer()
        with telemetry.tracing(t), guard.metered() as meter:
            with telemetry.span("root"):
                guard.fetch(np.arange(4), reason="root-level fetch")
                with telemetry.span("child"):
                    guard.fetch(np.arange(8), reason="child fetch")
                    guard.fetch(np.arange(2), reason="child fetch")
                with telemetry.span("empty-child"):
                    pass
        assert meter.transfers == 3
        assert t.total_transfers() == meter.transfers
        by_name = {s.name: s for s in t.spans}
        assert by_name["child"].transfers == 2
        assert by_name["child"].elements == 10
        assert by_name["root"].transfers == 1          # exclusive
        assert by_name["root"].transfers_incl == 3     # inclusive
        assert by_name["empty-child"].transfers == 0
        assert by_name["child"].by_reason == {"child fetch": 2}
        attr = t.attribution()
        assert attr["root"] == {"root-level fetch": 1}
        assert "empty-child" not in attr

    def test_tracing_adds_no_transfers(self):
        """Zero-extra-sync contract at the meter level: a traced region
        and an untraced region running the same fetches measure the same
        count (spans are pure host bookkeeping)."""
        def work():
            with telemetry.span("w"):
                guard.fetch(np.arange(3), reason="work")

        with guard.metered() as m_off:
            work()                      # no tracer installed -> NULL_SPAN
        t = Tracer()
        with telemetry.tracing(t), guard.metered() as m_on:
            work()
        assert m_on.transfers == m_off.transfers == 1
        assert m_on.elements == m_off.elements


# ---------------------------------------------------------------------------
# TransferMeter: bounded per-reason aggregation (regression for the
# unbounded .events list)
# ---------------------------------------------------------------------------


class TestTransferMeterAggregation:
    def test_ten_thousand_fetches_aggregate_not_accumulate(self):
        """10k fetches over 3 distinct reasons must aggregate into 3
        Counter entries — the meter's footprint is O(distinct reasons),
        not O(fetches). (The old ``events`` list grew one tuple per
        fetch; a long-lived serve loop leaked without bound.)"""
        x = np.arange(5)
        with guard.metered() as m:
            for i in range(10_000):
                guard.fetch(x, reason=f"reason-{i % 3}")
        assert m.transfers == 10_000
        assert m.elements == 50_000
        assert not hasattr(m, "events")
        assert len(m.reason_counts) == 3
        assert m.reasons() == ["reason-0", "reason-1", "reason-2"]
        assert m.by_reason()["reason-1"] == (3333, 16665)
        counts = m.by_reason()
        assert sum(c for c, _ in counts.values()) == 10_000
        assert sum(e for _, e in counts.values()) == 50_000

    def test_reasons_first_seen_order_distinct(self):
        with guard.metered() as m:
            guard.fetch(np.arange(1), reason="b")
            guard.fetch(np.arange(1), reason="a")
            guard.fetch(np.arange(1), reason="b")
        assert m.reasons() == ["b", "a"]

    def test_pop_meter_non_lifo_raises(self):
        m1 = guard.push_meter()
        m2 = guard.push_meter()
        with pytest.raises(RuntimeError):
            guard.pop_meter(m1)
        guard.pop_meter(m2)
        guard.pop_meter(m1)


# ---------------------------------------------------------------------------
# streaming histogram: quantiles vs numpy on adversarial distributions
# ---------------------------------------------------------------------------


def _fill(values):
    h = StreamingHistogram()
    for v in values:
        h.record(float(v))
    return h


class TestStreamingHistogram:
    def test_empty_histogram_is_all_zero(self):
        h = StreamingHistogram()
        assert h.quantile(0.5) == 0.0
        s = h.summary()
        assert s == {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                     "mean": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}

    def test_constant_distribution_is_exact(self):
        h = _fill([3.25] * 1000)
        for q in (0.01, 0.5, 0.95, 0.99, 1.0):
            assert h.quantile(q) == pytest.approx(3.25, rel=1e-12)
        assert h.summary()["mean"] == pytest.approx(3.25)

    def test_bimodal_distribution(self):
        """Two far-apart spikes: every quantile must snap to one of the
        modes (the clamp to observed [min, max] plus log-bucketing keeps
        each mode in its own bucket)."""
        vals = [0.001] * 500 + [1000.0] * 500
        h = _fill(vals)
        assert h.quantile(0.25) == pytest.approx(0.001, rel=0.05)
        assert h.quantile(0.75) == pytest.approx(1000.0, rel=0.05)
        assert h.quantile(0.0) == pytest.approx(0.001, rel=0.05)
        assert h.quantile(1.0) == pytest.approx(1000.0, rel=1e-12)

    def test_heavy_tail_vs_numpy(self):
        rng = np.random.default_rng(7)
        vals = rng.lognormal(mean=2.0, sigma=1.5, size=20_000)
        h = _fill(vals)
        for q in (0.5, 0.95, 0.99):
            ref = float(np.percentile(vals, q * 100))
            assert h.quantile(q) == pytest.approx(ref, rel=0.08), q

    def test_uniform_vs_numpy(self):
        rng = np.random.default_rng(3)
        vals = rng.uniform(0.5, 100.0, size=10_000)
        h = _fill(vals)
        for q in (0.5, 0.95, 0.99):
            ref = float(np.percentile(vals, q * 100))
            assert h.quantile(q) == pytest.approx(ref, rel=0.08), q

    def test_merge_is_associative_and_matches_single_pass(self):
        rng = np.random.default_rng(11)
        a, b, c = (rng.exponential(5.0, size=3000) for _ in range(3))
        hab_c = _fill(a); hab_c.merge(_fill(b))
        habc1 = StreamingHistogram(); habc1.merge(hab_c); habc1.merge(_fill(c))
        hbc = _fill(b); hbc.merge(_fill(c))
        habc2 = _fill(a); habc2.merge(hbc)
        one = _fill(np.concatenate([a, b, c]))
        for q in (0.5, 0.95, 0.99):
            assert habc1.quantile(q) == pytest.approx(habc2.quantile(q),
                                                      rel=1e-12)
            assert habc1.quantile(q) == pytest.approx(one.quantile(q),
                                                      rel=1e-12)
        assert habc1.summary()["count"] == 9000

    def test_negative_and_nan_rejected(self):
        h = StreamingHistogram()
        with pytest.raises(ValueError):
            h.record(-1.0)
        with pytest.raises(ValueError):
            h.record(float("nan"))

    def test_tiny_values_hit_underflow_bucket(self):
        h = _fill([0.0, 1e-15, 1e-13])
        assert h.summary()["count"] == 3
        assert h.quantile(0.5) == pytest.approx(0.0, abs=1e-12)


# ---------------------------------------------------------------------------
# exporters: Perfetto round-trip, JSONL, Prometheus
# ---------------------------------------------------------------------------


def _traced_tracer():
    t = Tracer()
    with telemetry.tracing(t):
        with telemetry.span("outer", stage=1):
            guard.fetch(np.arange(6), reason="outer fetch")
            with telemetry.span("inner", level=2) as sp:
                guard.fetch(np.arange(4), reason="inner fetch")
                sp.set(supersteps=7)
    return t


class TestExporters:
    def test_chrome_trace_round_trip(self, tmp_path):
        """Re-parse the exported trace: span nesting must be recoverable
        from the timestamps (child interval inside parent interval) and
        the attached counters must survive in ``args``."""
        t = _traced_tracer()
        path = tmp_path / "trace.json"
        export_chrome_trace(t, str(path))
        payload = json.loads(path.read_text())
        events = payload["traceEvents"]
        assert [e["name"] for e in events] == ["outer", "inner"]
        outer, inner = events
        assert all(e["ph"] == "X" for e in events)
        # nesting: inner's [ts, ts+dur] within outer's
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
        # attached counters + attribution ride in args
        assert outer["args"]["stage"] == 1
        assert outer["args"]["transfers"] == 1          # exclusive
        assert inner["args"]["supersteps"] == 7
        assert inner["args"]["transfers"] == 1
        assert inner["args"]["elements"] == 4
        assert inner["args"]["transfer_reasons"] == {"inner fetch": 1}

    def test_jsonl_spans_and_snapshot(self, tmp_path):
        t = _traced_tracer()
        reg = MetricsRegistry()
        reg.counter("c", 3)
        reg.observe("lat", 0.5)
        path = tmp_path / "spans.jsonl"
        export_jsonl(t, reg.snapshot(), str(path))
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        spans = [l for l in lines if l["type"] == "span"]
        snap = [l for l in lines if l["type"] == "snapshot"]
        assert len(spans) == 2 and len(snap) == 1
        inner = next(s for s in spans if s["name"] == "inner")
        outer = next(s for s in spans if s["name"] == "outer")
        assert inner["parent"] == outer["index"]
        assert inner["by_reason"] == {"inner fetch": 1}
        assert snap[0]["counters"]["c"] == 3
        assert snap[0]["histograms"]["lat"]["count"] == 1

    def test_prometheus_text(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("engine.host_syncs", 4)
        reg.gauge("pool.sessions", 2)
        for v in (0.1, 0.2, 0.3):
            reg.observe("serve.latency.cascade", v)
        path = tmp_path / "metrics.prom"
        export_prometheus(reg.snapshot(), str(path))
        text = path.read_text()
        assert "engine_host_syncs_total 4" in text
        assert "pool_sessions 2" in text
        assert 'serve_latency_cascade{quantile="0.5"}' in text
        assert "serve_latency_cascade_count 3" in text

    def test_write_telemetry_bundle(self, tmp_path):
        t = _traced_tracer()
        reg = MetricsRegistry()
        reg.counter("x", 1)
        paths = write_telemetry(str(tmp_path), tracer=t, registry=reg)
        assert set(paths) == {"trace", "jsonl", "prom"}
        for p in paths.values():
            assert (tmp_path / p).exists() or __import__("os").path.exists(p)
        json.loads(open(paths["trace"]).read())   # parses

    def test_numpy_scalar_attrs_serialize(self, tmp_path):
        t = Tracer()
        with telemetry.tracing(t):
            with telemetry.span("s") as sp:
                sp.set(k=np.int32(5), v=np.float64(1.5))
        export_chrome_trace(t, str(tmp_path / "t.json"))
        args = json.loads((tmp_path / "t.json").read_text())[
            "traceEvents"][0]["args"]
        assert args["k"] == 5 and args["v"] == 1.5


# ---------------------------------------------------------------------------
# registry ingestion of the existing metrics dataclasses
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_ingest_dataclass_and_meter(self):
        from repro.core.session import SessionMetrics

        sm = SessionMetrics()
        sm.sessions_opened = 2
        sm.queries = 5
        with guard.metered() as m:
            guard.fetch(np.arange(3), reason="r1")
            guard.fetch(np.arange(3), reason="r1")
        reg = MetricsRegistry()
        reg.ingest(sm, "session")
        reg.ingest(m, "serve.transfers")
        snap = reg.snapshot()
        assert snap.counters["session.sessions_opened"] == 2
        assert snap.counters["session.queries"] == 5
        assert snap.counters["serve.transfers.transfers"] == 2
        assert snap.counters["serve.transfers.elements"] == 6
        assert snap.counters["serve.transfers.reason.r1"] == 2

    def test_histogram_summary_in_snapshot(self):
        reg = MetricsRegistry()
        for v in range(1, 101):
            reg.observe("lat", float(v))
        s = reg.snapshot().histograms["lat"]
        assert s["count"] == 100
        assert s["p50"] == pytest.approx(50.0, rel=0.1)
        assert s["p99"] == pytest.approx(99.0, rel=0.1)


# ---------------------------------------------------------------------------
# zero-extra-sync contract: PR 8 transfer equalities under tracing
# ---------------------------------------------------------------------------


def _graph():
    from repro.graph import random_geometric

    return random_geometric(512, avg_degree=6.0, seed=1)


class TestEqualityContractsUnderTracing:
    def test_stages_equality_holds_traced(self):
        from repro.core import cluster

        t = Tracer()
        with telemetry.tracing(t), guard.measured_transfers() as meter:
            dec = cluster(_graph(), 12, seed=0)
        m = dec.metrics
        assert meter.transfers == m.host_syncs + m.finalize_syncs
        # and every one of them is attributed to a named span
        assert t.total_transfers() == meter.transfers
        # (tau=12 at n=512 keeps the stage threshold above n, so the
        # stage loop may not run — finalize always does)
        assert "engine.finalize" in {s.name for s in t.spans}

    def test_pipeline_equality_holds_traced(self):
        from repro.core import ClusterQuotientEstimator, open_session

        t = Tracer()
        with telemetry.tracing(t):
            with open_session(_graph(), tau=12) as sess:
                with guard.measured_transfers() as meter:
                    res = sess.estimate(ClusterQuotientEstimator())
        assert meter.transfers == res.pipeline.total_host_syncs

    def test_traced_equals_untraced_decomposition(self):
        """Determinism: tracing must not change the computation — same
        decomposition, same sync count, traced or not."""
        from repro.core import cluster

        with guard.measured_transfers() as m_off:
            dec_off = cluster(_graph(), 12, seed=0)
        t = Tracer()
        with telemetry.tracing(t), guard.measured_transfers() as m_on:
            dec_on = cluster(_graph(), 12, seed=0)
        assert m_on.transfers == m_off.transfers
        np.testing.assert_array_equal(dec_on.final_c, dec_off.final_c)
        np.testing.assert_array_equal(dec_on.final_pathw, dec_off.final_pathw)


# ---------------------------------------------------------------------------
# spans on the profiler's clock, program builds per span, Span.wait
# ---------------------------------------------------------------------------


class _Notes:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs opens and
    closes."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **kw):
        log = self.log

        class Note:
            def __enter__(self):
                log.append(("open", name))
                return self

            def __exit__(self, *exc):
                log.append(("close", name))

        return Note()


class TestProfilerAnnotations:
    def test_one_annotation_per_span_under_a_tracer(self, monkeypatch):
        import jax.profiler

        notes = _Notes()
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", notes)
        with telemetry.tracing(Tracer()):
            with telemetry.span("outer", k=1):
                with telemetry.span("inner"):
                    pass
        assert notes.log == [("open", "outer"), ("open", "inner"),
                             ("close", "inner"), ("close", "outer")]

    def test_no_annotation_without_a_tracer(self, monkeypatch):
        import jax.profiler

        notes = _Notes()
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", notes)
        with telemetry.span("a"):
            with telemetry.span("b"):
                pass
        assert notes.log == []

    def test_non_lifo_close_keeps_the_annotation_open(self, monkeypatch):
        import jax.profiler

        notes = _Notes()
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", notes)
        with telemetry.tracing(Tracer()):
            s1, s2 = telemetry.span("a"), telemetry.span("b")
            s1.__enter__()
            s2.__enter__()
            with pytest.raises(RuntimeError):
                s1.__exit__(None, None, None)
            assert ("close", "a") not in notes.log
            s2.__exit__(None, None, None)
            s1.__exit__(None, None, None)
        assert notes.log[-2:] == [("close", "b"), ("close", "a")]


class TestBuildAttribution:
    def test_fresh_jit_builds_once_in_the_innermost_span(self):
        import jax
        import jax.monitoring

        seen = []

        def count(event, seconds, **kw):
            if event == telemetry.COMPILE_EVENT:
                seen.append(seconds)

        x = np.arange(7, dtype=np.int32)
        t = Tracer()
        jax.monitoring.register_event_duration_secs_listener(count)
        try:
            with telemetry.tracing(t):
                jax.jit(lambda v: v * 2 + 1)(x).block_until_ready()
                with telemetry.span("outer"):
                    with telemetry.span("inner"):
                        jax.jit(lambda v: v * 5 - 3)(x).block_until_ready()
        finally:
            jax.monitoring.unregister_event_duration_listener(count)
        by_name = {s.name: s for s in t.spans}
        assert by_name["inner"].builds == 1
        assert by_name["inner"].build_s > 0
        assert by_name["outer"].builds == 0
        assert by_name["outer"].build_s == 0.0
        assert t.builds_outside >= 1
        assert t.total_builds() == len(seen)
        assert t.total_build_s() >= sum(seen)

    def test_no_tracer_attributes_nothing(self):
        import jax

        t = Tracer()
        with telemetry.tracing(t):
            pass
        # the listener stays registered once installed; with no tracer
        # installed it returns at once
        jax.jit(lambda v: v + 11)(np.arange(3)).block_until_ready()
        assert t.total_builds() == 0 and t.builds_outside == 0

    def test_events_of_other_kinds_are_ignored(self):
        t = Tracer()
        with telemetry.tracing(t):
            with telemetry.span("s"):
                telemetry._on_build_event("/jax/some/other_duration", 3.0)
        assert t.spans[0].builds == 0 and t.spans[0].build_s == 0.0


class TestSpanWait:
    def test_wait_blocks_on_the_arrays(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        waited = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: waited.append(x) or real(x))
        a, b = jnp.arange(4), jnp.ones(3)
        t = Tracer()
        with telemetry.tracing(t), guard.metered() as m:
            with telemetry.span("s") as sp:
                assert sp.wait(a, b) is sp
        assert len(waited) == 1
        assert waited[0][0] is a and waited[0][1] is b
        assert m.transfers == 0 and t.spans[0].transfers == 0

    def test_null_span_wait_is_a_no_op(self, monkeypatch):
        import jax

        def refuse(x):
            raise AssertionError("NULL_SPAN.wait blocked")

        monkeypatch.setattr(jax, "block_until_ready", refuse)
        with telemetry.span("s") as sp:
            assert sp is telemetry.NULL_SPAN
            assert sp.wait(np.arange(2)) is sp


class TestBuildFieldsExported:
    def _tracer(self):
        t = Tracer()
        with telemetry.tracing(t):
            with telemetry.span("outer"):
                with telemetry.span("inner"):
                    telemetry._on_build_event(telemetry.COMPILE_EVENT, 0.25)
                    telemetry._on_build_event(
                        "/jax/core/compile/jaxpr_trace_duration", 0.5)
        return t

    def test_chrome_trace_carries_builds(self, tmp_path):
        path = tmp_path / "trace.json"
        export_chrome_trace(self._tracer(), str(path))
        events = {e["name"]: e for e in
                  json.loads(path.read_text())["traceEvents"]}
        assert events["inner"]["args"]["builds"] == 1
        assert events["inner"]["args"]["build_s"] == pytest.approx(0.75)
        assert events["outer"]["args"]["builds"] == 0
        assert events["outer"]["args"]["build_s"] == 0.0

    def test_jsonl_carries_builds(self, tmp_path):
        path = tmp_path / "spans.jsonl"
        export_jsonl(self._tracer(), None, str(path))
        rows = {r["name"]: r for r in
                map(json.loads, path.read_text().splitlines())}
        assert rows["inner"]["builds"] == 1
        assert rows["inner"]["build_s"] == pytest.approx(0.75)
        assert rows["outer"]["builds"] == 0


class TestSessionSpans:
    def test_estimate_span_wraps_the_query(self):
        from repro.core import IntervalEstimator, open_session

        t = Tracer()
        with open_session(_graph(), tau=12) as sess:
            with telemetry.tracing(t), guard.measured_transfers() as meter:
                res = sess.estimate(IntervalEstimator())
        roots = [s for s in t.spans if s.parent is None]
        assert [s.name for s in roots] == ["session.estimate"]
        root = roots[0]
        assert root.attrs["estimator"] == "IntervalEstimator"
        assert root.transfers_incl == meter.transfers
        assert t.total_transfers() == meter.transfers
        assert meter.transfers == res.pipeline.total_host_syncs
        assert all(s.start >= root.start and
                   s.start + s.duration <= root.start + root.duration + 1e-9
                   for s in t.spans)

    def test_default_estimator_is_named(self):
        from repro.core import open_session

        t = Tracer()
        with open_session(_graph(), tau=12) as sess:
            with telemetry.tracing(t):
                sess.estimate()
        root = [s for s in t.spans if s.name == "session.estimate"]
        assert [s.attrs["estimator"] for s in root] == [
            "ClusterQuotientEstimator"]

    def test_traced_open_waits_for_the_resident_arrays(self, monkeypatch):
        from repro.core import open_session

        waited = []
        real = telemetry.Span.wait

        def wait(self, *arrays):
            waited.append((self.name, arrays))
            return real(self, *arrays)

        monkeypatch.setattr(telemetry.Span, "wait", wait)
        t = Tracer()
        with telemetry.tracing(t), guard.metered() as m:
            sess = open_session(_graph(), tau=12)
        assert [n for n, _ in waited] == ["session.open"]
        assert len(waited[0][1]) == len(sess.backend.graph_args())
        assert m.transfers == 0
        sess.close()

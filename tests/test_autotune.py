"""Graph-statistics autotuner: stats correctness, knob derivation,
validation, cache round-trip, and the session pin/override contract."""
import dataclasses

import numpy as np
import pytest

from repro.core import autotune
from repro.core.autotune import (
    AutotuneError,
    TuningRecord,
    clear_cache,
    compute_graph_stats,
    derive_tuning,
    get_tuning,
    graph_signature,
    load_cache,
    save_cache,
    validate_tuning,
)
from repro.core.session import open_session
from repro.config.base import GraphEngineConfig
from repro.graph.structures import EdgeList
from repro.runtime.roofline import V5E


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_cache()
    yield
    clear_cache()


def _edges(n=500, e=2000, wmax=100, seed=0):
    r = np.random.default_rng(seed)
    return EdgeList(n, r.integers(0, n, e).astype(np.int32),
                    r.integers(0, n, e).astype(np.int32),
                    r.integers(1, wmax + 1, e).astype(np.int32))


# ---------------------------------------------------------------------------
# stats pass
# ---------------------------------------------------------------------------

def test_graph_stats_match_numpy():
    edges = _edges(seed=3)
    s = compute_graph_stats(edges)
    deg = np.bincount(edges.dst, minlength=edges.n_nodes)
    assert s.n_nodes == edges.n_nodes and s.n_edges == edges.n_edges
    assert s.max_degree == int(deg.max())
    assert s.min_weight == int(edges.weight.min())
    assert s.max_weight == int(edges.weight.max())
    assert s.weight_sum == int(edges.weight.astype(np.int64).sum())
    assert s.avg_weight == s.weight_sum // edges.n_edges
    # histograms: log2 buckets cover every edge / node exactly once
    assert sum(s.weight_hist) == edges.n_edges
    assert sum(s.degree_hist) == edges.n_nodes
    w_buckets = np.clip(np.floor(np.log2(np.maximum(
        edges.weight, 1))).astype(int), 0, autotune.N_BUCKETS - 1)
    expect = np.bincount(w_buckets, minlength=autotune.N_BUCKETS)
    assert tuple(int(x) for x in expect) == s.weight_hist


def test_graph_stats_empty_and_heavy_weights():
    empty = compute_graph_stats(EdgeList(
        0, np.zeros(0, np.int32), np.zeros(0, np.int32), np.zeros(0, np.int32)))
    assert empty.n_edges == 0 and empty.weight_sum == 0
    # weight_sum overflows int32 — must be exact via the host int64 path
    big = EdgeList(4, np.zeros(8, np.int32), np.ones(8, np.int32),
                   np.full(8, 2**30 - 1, np.int32))
    s = compute_graph_stats(big)
    assert s.weight_sum == 8 * (2**30 - 1)
    assert s.weight_hist[29] == 8


def test_signature_is_stable_and_shape_sensitive():
    a = graph_signature(compute_graph_stats(_edges(seed=1)))
    b = graph_signature(compute_graph_stats(_edges(seed=1)))
    c = graph_signature(compute_graph_stats(_edges(seed=2)))
    assert a == b
    assert a != c


# ---------------------------------------------------------------------------
# derivation + validation
# ---------------------------------------------------------------------------

def test_derive_tuning_is_valid_across_shapes():
    for n, e, wmax in [(50, 100, 3), (2000, 8000, 100), (500, 4000, 2**28)]:
        stats = compute_graph_stats(_edges(n, e, wmax, seed=n))
        rec = derive_tuning(stats, peaks=V5E)
        validate_tuning(rec, stats, V5E)  # must not raise
        assert 4 <= rec.tau <= n
        assert rec.tau_solve >= 64 and rec.levels in (0, 1, 2)
        assert 1 <= rec.delta_init < 2**30


def test_derive_tuning_hub_skew_doubles_tau():
    n, e = 4000, 16000
    r = np.random.default_rng(0)
    flat = EdgeList(n, r.integers(0, n, e).astype(np.int32),
                    r.integers(0, n, e).astype(np.int32),
                    r.integers(1, 100, e).astype(np.int32))
    hub_dst = r.integers(0, n, e).astype(np.int32)
    hub_dst[: e // 2] = 0  # one node takes half the edges
    hub = EdgeList(n, flat.src, hub_dst, flat.weight)
    t_flat = derive_tuning(compute_graph_stats(flat), peaks=V5E)
    t_hub = derive_tuning(compute_graph_stats(hub), peaks=V5E)
    assert t_hub.tau == 2 * t_flat.tau


def test_derive_tuning_delta_tracks_median_weight():
    light = derive_tuning(compute_graph_stats(_edges(wmax=3, seed=1)),
                          peaks=V5E)
    heavy = derive_tuning(compute_graph_stats(_edges(wmax=2**20, seed=1)),
                          peaks=V5E)
    assert light.delta_init < heavy.delta_init
    # heavy-tailed: median-based delta sits far below the mean-based "avg"
    skewed = _edges(seed=4)
    w = np.asarray(skewed.weight).copy()
    w[:20] = 2**29  # 1% giants drag the mean up ~4 orders of magnitude
    stats = compute_graph_stats(EdgeList(skewed.n_nodes, skewed.src,
                                         skewed.dst, w))
    rec = derive_tuning(stats, peaks=V5E)
    assert rec.delta_init < stats.avg_weight


def test_validate_tuning_rejects_stale_records():
    stats = compute_graph_stats(_edges())
    rec = derive_tuning(stats, peaks=V5E)
    for bad in (
        dataclasses.replace(rec, edge_block=100),       # kernel precondition
        dataclasses.replace(rec, tau=0),
        dataclasses.replace(rec, tau_solve=1),
        dataclasses.replace(rec, levels=9),
        dataclasses.replace(rec, delta_init=2**30),
        dataclasses.replace(rec, fuse=-1),
    ):
        with pytest.raises((AutotuneError, ValueError)):
            validate_tuning(bad, stats, V5E)


def test_validate_tuning_rejects_roofline_regression():
    # a graph large enough that the tiling choice matters: a wildly padded
    # alternative must fail the 1.05x roofline check
    stats = compute_graph_stats(_edges(n=20000, e=60000, seed=9))
    rec = derive_tuning(stats, peaks=V5E)
    worst = None
    for nt in autotune.NODE_TILE_CANDIDATES:
        for eb in autotune.EDGE_BLOCK_CANDIDATES:
            t, _ = autotune._tiling_time(stats.n_nodes, stats.n_edges, nt, eb,
                                          V5E)
            if worst is None or t > worst[2]:
                worst = (nt, eb, t)
    assert worst[2] > rec.predicted_superstep_s * 1.05
    stale = dataclasses.replace(rec, node_tile=worst[0], edge_block=worst[1])
    with pytest.raises(AutotuneError, match="stale"):
        validate_tuning(stale, stats, V5E)


def test_peaks_table_is_keyed_by_device_kind():
    from repro.runtime.roofline import PEAKS, peaks_for

    assert peaks_for("TPU v5 lite") is V5E is PEAKS["TPU v5 lite"]
    assert (V5E.flops, V5E.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        peaks_for("TPU v9 imaginary")


def test_derive_tuning_never_fuses():
    for n, e in [(500, 2000), (20000, 60000)]:
        stats = compute_graph_stats(_edges(n=n, e=e, seed=n))
        assert derive_tuning(stats, peaks=V5E).fuse == 0


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def test_get_tuning_caches_by_signature():
    edges = _edges(seed=5)
    r1 = get_tuning(edges)
    r2 = get_tuning(edges)
    assert r1 is r2
    assert autotune.TUNE_EVENTS == {"hits": 1, "misses": 1}
    get_tuning(_edges(seed=6))
    assert autotune.TUNE_EVENTS["misses"] == 2
    # backend is part of the key
    get_tuning(edges, backend="pallas")
    assert autotune.TUNE_EVENTS["misses"] == 3


def test_cache_round_trip(tmp_path):
    path = str(tmp_path / "tune.json")
    edges = _edges(seed=7)
    rec = get_tuning(edges, record=True, cache_path=path)
    clear_cache()
    assert load_cache(path) == 1
    hit = get_tuning(edges)
    assert hit == rec
    assert autotune.TUNE_EVENTS == {"hits": 1, "misses": 0}
    # explicit save path and missing-file load
    assert save_cache(str(tmp_path / "again.json")).endswith("again.json")
    assert load_cache(str(tmp_path / "absent.json")) == 0


def test_loaded_record_survives_dataclass_round_trip(tmp_path):
    path = str(tmp_path / "tune.json")
    get_tuning(_edges(seed=8), record=True, cache_path=path)
    clear_cache()
    load_cache(path)
    (rec,) = autotune._CACHE.values()
    assert isinstance(rec, TuningRecord)
    validate_tuning(rec, compute_graph_stats(_edges(seed=8)), V5E)


# ---------------------------------------------------------------------------
# session wiring: pins beat the tuner; defaults follow it
# ---------------------------------------------------------------------------

def test_session_autotune_defaults_and_pins():
    edges = _edges(n=2000, e=6000, seed=11)
    cfg = GraphEngineConfig(autotune="auto")
    tuned = open_session(edges, cfg)
    assert tuned.tuning is not None
    assert tuned.tau == tuned.tuning.tau
    assert tuned.tau_solve == tuned.tuning.tau_solve
    assert tuned.cfg.delta_init == str(tuned.tuning.delta_init)

    pinned = open_session(edges, GraphEngineConfig(
        autotune="auto", delta_init="123"), tau=17, tau_solve=99)
    assert pinned.tau == 17 and pinned.tau_solve == 99
    assert pinned.cfg.delta_init == "123"  # numeric config stays pinned

    off = open_session(edges, GraphEngineConfig())
    assert off.tuning is None

    with pytest.raises(ValueError, match="autotune"):
        open_session(edges, GraphEngineConfig(), autotune="bogus")


def test_session_autotune_estimates():
    edges = _edges(n=1500, e=5000, seed=13)
    sess = open_session(edges, GraphEngineConfig(autotune="auto"))
    est = sess.estimate()
    assert est.phi_approx >= est.radius >= 0
    baseline = open_session(edges, GraphEngineConfig()).estimate()
    assert est.connected == baseline.connected

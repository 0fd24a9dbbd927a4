"""One-shot exponential-shift decomposition mode (core/engine.run_oneshot).

Contracts under test:
  * the weighted-radius certificate: for every node, the scipy-exact
    distance from its assigned center is <= final_pathw (the same bound the
    staged engine certifies — oneshot folds shifts into d, never pathw);
  * IntervalEstimator keeps `lower <= scipy exact <= upper` under BOTH
    modes on single/pallas (in-process) and sharded (subprocess) backends;
  * deterministic=True makes the output a seed-independent function of the
    graph, byte-identical across two processes with DIFFERENT seeds;
  * mode="stages" is byte-identical to the pre-mode default path;
  * unknown mode names raise ValueError listing the valid names everywhere
    a mode enters (library, session, estimator, both launcher CLIs);
  * the one-shot sync contract: exactly ONE host sync per decomposition.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

from _hypothesis_compat import given, settings, st

from repro.core import (
    CascadeEstimator,
    ClusterQuotientEstimator,
    ENGINE_MODES,
    IntervalEstimator,
    LowerBoundEstimator,
    check_engine_mode,
    cluster,
    open_session,
    resolve_engine_mode,
)
from repro.graph import grid_mesh, random_geometric, social_like

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _adj(g):
    return sp.coo_matrix((g.weight, (g.src, g.dst)),
                         shape=(g.n_nodes, g.n_nodes)).tocsr()


def _exact_diameter(g) -> int:
    D = dijkstra(_adj(g))
    finite = D[np.isfinite(D)]
    return int(finite.max()) if finite.size else 0


def _assert_radius_certificate(g, dec):
    """dist(center(u), u) <= final_pathw[u] for every node, scipy-exact."""
    centers = np.unique(dec.final_c)
    D = dijkstra(_adj(g), indices=centers)
    row = {c: i for i, c in enumerate(centers)}
    for u in range(g.n_nodes):
        d = D[row[dec.final_c[u]], u]
        assert d <= dec.final_pathw[u] + 1e-9, (
            f"node {u}: exact {d} > certified {dec.final_pathw[u]}")


# ---------------------------------------------------------------------------
# mode validation / registry
# ---------------------------------------------------------------------------


def test_unknown_mode_raises_listing_names():
    with pytest.raises(ValueError, match="stages"):
        check_engine_mode("bogus")
    with pytest.raises(ValueError, match="oneshot"):
        resolve_engine_mode("bogus")
    g = grid_mesh(6, "unit")
    with pytest.raises(ValueError, match="unknown engine mode"):
        cluster(g, 4, mode="bogus")


def test_mode_errors_before_device_work_in_session_and_estimators():
    from repro.config.base import GraphEngineConfig

    g = grid_mesh(6, "unit")
    with pytest.raises(ValueError, match="unknown engine mode"):
        open_session(g, GraphEngineConfig(mode="bogus"))
    sess = open_session(g)
    with pytest.raises(ValueError, match="unknown engine mode"):
        sess.estimate(ClusterQuotientEstimator(mode="bogus"))
    with pytest.raises(ValueError, match="unknown engine mode"):
        sess.estimate(CascadeEstimator(level_mode="bogus"))


def test_auto_resolves_to_stages_without_tuning():
    assert resolve_engine_mode("auto") == "stages"
    for m in ENGINE_MODES:
        check_engine_mode(m)  # every advertised name is accepted


def test_launchers_reject_unknown_engine_mode():
    """--engine-mode bogus must ValueError (not argparse-exit) BEFORE any
    graph is built, on both CLIs — the PR 5 estimator-name contract."""
    from repro.launch import diameter as dia_mod
    from repro.launch import serve as serve_mod

    argv = sys.argv
    try:
        sys.argv = ["diameter.py", "--n", "50", "--engine-mode", "bogus"]
        with pytest.raises(ValueError, match="unknown engine mode"):
            dia_mod.main()
        sys.argv = ["serve.py", "--mode", "graph-diameter", "--graph-n",
                    "50", "--engine-mode", "bogus"]
        with pytest.raises(ValueError, match="unknown engine mode"):
            serve_mod.main()
    finally:
        sys.argv = argv


def test_decomposition_mode_registry():
    from repro.core import DECOMPOSITION_MODES
    from repro.core.engine import run_cluster, run_oneshot

    assert DECOMPOSITION_MODES["stages"].runner is run_cluster
    assert DECOMPOSITION_MODES["oneshot"].runner is run_oneshot


# ---------------------------------------------------------------------------
# stages mode: identity pin
# ---------------------------------------------------------------------------


def test_stages_mode_is_the_default_byte_identical():
    g = random_geometric(1200, avg_degree=3.0, seed=2)
    a = cluster(g, 12, seed=5)
    b = cluster(g, 12, seed=5, mode="stages")
    np.testing.assert_array_equal(a.final_c, b.final_c)
    np.testing.assert_array_equal(a.final_pathw, b.final_pathw)
    assert a.growing_steps == b.growing_steps


# ---------------------------------------------------------------------------
# oneshot: certificate + sync contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["single", "pallas"])
def test_oneshot_radius_certificate_and_single_sync(backend):
    g = random_geometric(1000, avg_degree=3.0, seed=3)
    dec = cluster(g, 12, seed=7, mode="oneshot", backend=backend)
    assert dec.metrics.host_syncs == 1, dec.metrics
    assert dec.metrics.stages == 1
    assert dec.metrics.state_transfers <= 1
    _assert_radius_certificate(g, dec)


def test_oneshot_backend_parity():
    g = grid_mesh(20, "bimodal", heavy_w=500, heavy_p=0.15, seed=3)
    a = cluster(g, 8, seed=5, mode="oneshot")
    b = cluster(g, 8, seed=5, mode="oneshot", backend="pallas")
    np.testing.assert_array_equal(a.final_c, b.final_c)
    np.testing.assert_array_equal(a.final_pathw, b.final_pathw)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(min_value=20, max_value=300),
       seed=st.integers(min_value=0, max_value=2**31 - 1),
       deterministic=st.booleans())
def test_oneshot_radius_bound_property(n, seed, deterministic):
    g = random_geometric(n, avg_degree=3.0, seed=seed % 1000)
    dec = cluster(g, max(n // 50, 2), seed=seed, mode="oneshot",
                  deterministic=deterministic)
    assert dec.metrics.host_syncs == 1
    # every node is assigned and certified
    assert (dec.final_pathw >= 0).all()
    _assert_radius_certificate(g, dec)


# ---------------------------------------------------------------------------
# interval bracket under both modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["single", "pallas"])
@pytest.mark.parametrize("mode", ["stages", "oneshot"])
def test_interval_bracket_both_modes(backend, mode):
    from repro.config.base import GraphEngineConfig

    g = random_geometric(700, avg_degree=3.0, seed=4)
    exact = _exact_diameter(g)
    sess = open_session(g, GraphEngineConfig(backend=backend, mode=mode))
    iv = sess.estimate(IntervalEstimator(estimators=(
        LowerBoundEstimator(), ClusterQuotientEstimator())))
    assert iv.lower <= exact <= iv.upper, (iv.lower, exact, iv.upper)


def test_interval_bracket_oneshot_sharded_subprocess():
    code = textwrap.dedent("""
    import jax, numpy as np
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra
    from repro.graph import grid_mesh
    from repro.core import (ClusterQuotientEstimator, IntervalEstimator,
                            LowerBoundEstimator, cluster, open_session)
    from repro.core.distributed import DistributedEngine
    g = grid_mesh(18, "bimodal", heavy_w=500, heavy_p=0.15, seed=3)
    eng = DistributedEngine(g, mesh)
    be = eng.make_relax_fn()
    # sharded backend parity with single-device oneshot, byte for byte
    ref = cluster(g, 8, seed=5, mode="oneshot")
    out = cluster(g, 8, seed=5, mode="oneshot", relax_fn=be)
    assert np.array_equal(ref.final_c, out.final_c)
    assert np.array_equal(ref.final_pathw, out.final_pathw)
    assert out.metrics.host_syncs == 1, out.metrics
    # certified bracket through the session layer on the sharded backend
    from repro.config.base import GraphEngineConfig
    sess = open_session(g, GraphEngineConfig(mode="oneshot"), backend=be)
    iv = sess.estimate(IntervalEstimator(estimators=(
        LowerBoundEstimator(), ClusterQuotientEstimator())))
    A = sp.coo_matrix((g.weight, (g.src, g.dst)),
                      shape=(g.n_nodes, g.n_nodes)).tocsr()
    D = dijkstra(A)
    exact = int(D[np.isfinite(D)].max())
    assert iv.lower <= exact <= iv.upper, (iv.lower, exact, iv.upper)
    print("ONESHOT-SHARDED-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ONESHOT-SHARDED-OK" in out.stdout


def test_cascade_level_mode_oneshot_keeps_bracket():
    g = social_like(9, 6, seed=2, weight_dist="uniform", high=2**20)
    exact = _exact_diameter(g)
    sess = open_session(g, tau_solve=8)
    iv = sess.estimate(IntervalEstimator(estimators=(
        LowerBoundEstimator(),
        CascadeEstimator(levels=2, level_mode="oneshot"))))
    assert iv.lower <= exact <= iv.upper, (iv.lower, exact, iv.upper)


# ---------------------------------------------------------------------------
# deterministic variant: seed independence across processes
# ---------------------------------------------------------------------------


def test_deterministic_seed_independent_in_process():
    g = random_geometric(900, avg_degree=3.0, seed=6)
    a = cluster(g, 10, seed=1, mode="oneshot", deterministic=True)
    b = cluster(g, 10, seed=2**30 + 17, mode="oneshot", deterministic=True)
    np.testing.assert_array_equal(a.final_c, b.final_c)
    np.testing.assert_array_equal(a.final_pathw, b.final_pathw)
    # the random variant genuinely depends on the seed (sanity check that
    # the deterministic path isn't trivially constant)
    c = cluster(g, 10, seed=1, mode="oneshot")
    d = cluster(g, 10, seed=2, mode="oneshot")
    assert not np.array_equal(c.final_c, d.final_c)


def test_deterministic_byte_identical_across_processes():
    """Two processes, DIFFERENT seeds: deterministic output must hash the
    same (the sharded/dynamic reproducibility story)."""
    code = textwrap.dedent("""
    import sys, hashlib, numpy as np
    from repro.graph import random_geometric
    from repro.core import cluster
    g = random_geometric(600, avg_degree=3.0, seed=11)
    dec = cluster(g, 8, seed=int(sys.argv[1]), mode="oneshot",
                  deterministic=True)
    h = hashlib.md5(dec.final_c.tobytes() + dec.final_pathw.tobytes())
    print("HASH", h.hexdigest())
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    hashes = []
    for seed in ("3", "424242"):
        out = subprocess.run([sys.executable, "-c", code, seed],
                             capture_output=True, text=True, env=env,
                             timeout=900)
        assert out.returncode == 0, out.stderr[-3000:]
        line = [l for l in out.stdout.splitlines() if l.startswith("HASH")]
        assert line, out.stdout
        hashes.append(line[0])
    assert hashes[0] == hashes[1], hashes


# ---------------------------------------------------------------------------
# autotune integration
# ---------------------------------------------------------------------------


def test_tuning_record_mode_derivation_and_validation():
    import dataclasses

    from repro.core.autotune import (AutotuneError, compute_graph_stats,
                                     derive_tuning, validate_tuning)
    from repro.runtime.roofline import V5E

    g = random_geometric(2000, avg_degree=3.0, seed=1)
    stats = compute_graph_stats(g)
    rec = derive_tuning(stats, peaks=V5E)
    assert rec.mode in ("stages", "oneshot")  # never "auto": records store
    validate_tuning(rec, stats, V5E)          # the RESOLVED mode
    for bad in ("auto", "bogus"):
        with pytest.raises(AutotuneError, match="mode"):
            validate_tuning(dataclasses.replace(rec, mode=bad), stats, V5E)
    # cfg.mode="auto" on a tuned session resolves to the record's choice;
    # the default "stages" stays pinned even under autotune
    from repro.config.base import GraphEngineConfig

    sess = open_session(g, GraphEngineConfig(mode="auto", autotune="auto"))
    assert sess.cfg.mode == sess.tuning.mode
    sess2 = open_session(g, GraphEngineConfig(autotune="auto"))
    assert sess2.cfg.mode == "stages"


def test_tuning_cache_backcompat_without_mode_field():
    """JSON cache entries recorded before TuningRecord grew ``mode`` must
    load with the 'stages' default."""
    import dataclasses

    from repro.core.autotune import TuningRecord

    fields = {f.name for f in dataclasses.fields(TuningRecord)}
    d = {"signature": "x", "tau": 8, "tau_solve": 64, "levels": 0,
         "delta_init": 4, "node_tile": 128, "edge_block": 128, "fuse": 0,
         "predicted_superstep_s": 1e-6, "padded_edges": 128}
    assert fields - set(d) == {"mode"}
    assert TuningRecord(**d).mode == "stages"


# ---------------------------------------------------------------------------
# degenerate inputs
# ---------------------------------------------------------------------------


def test_oneshot_degenerates():
    from repro.graph.structures import EdgeList

    empty = EdgeList(n_nodes=0, src=np.zeros(0, np.int32),
                     dst=np.zeros(0, np.int32), weight=np.zeros(0, np.int32))
    dec = cluster(empty, 1, mode="oneshot")
    assert dec.n_nodes == 0 and dec.n_clusters == 0
    single = EdgeList(n_nodes=1, src=np.zeros(0, np.int32),
                      dst=np.zeros(0, np.int32), weight=np.zeros(0, np.int32))
    dec = cluster(single, 1, mode="oneshot")
    assert dec.n_clusters == 1 and dec.radius == 0

"""Multi-level quotient cascade (``CascadeEstimator``): level-0 field
identity with the flat pipeline, the bound contract
``lower <= scipy exact <= upper`` at every level count across backends,
conservativeness of the int64->int32 weight rescale, degenerate inputs,
and the per-level ``PipelineMetrics`` accounting."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import (
    CascadeEstimator,
    ClusterQuotientEstimator,
    DiameterEstimator,
    IntervalEstimator,
    LowerBoundEstimator,
    SessionPool,
    open_session,
    quotient_as_edgelist,
)
from repro.core.quotient import INF64, DeviceQuotient
from repro.graph import grid_mesh, random_connected, random_geometric
from repro.graph.structures import MAX_WEIGHT, EdgeList, to_scipy_csr

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _true_diameter(edges):
    from scipy.sparse.csgraph import shortest_path
    d = shortest_path(to_scipy_csr(edges), method="D", directed=False)
    fin = d[np.isfinite(d)]
    return int(fin.max()) if len(fin) else 0


def _edgeless(n):
    z = np.array([], dtype=np.int32)
    return EdgeList(n, z, z, z)


def _assert_estimates_identical(a, b, ignore=("seconds", "method")):
    for f in dataclasses.fields(a):
        if f.name in ignore:
            continue
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            np.testing.assert_array_equal(x, y, err_msg=f.name)
        else:
            assert x == y, (f.name, x, y)


# ---------------------------------------------------------------------------
# level 0 == the flat pipeline, field for field
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["single", "pallas"])
def test_level0_cascade_field_identical_to_flat(backend):
    from repro.config.base import GraphEngineConfig

    g = random_geometric(900, avg_degree=3.0, seed=5)
    sess = open_session(g, GraphEngineConfig(backend=backend), tau=8)
    flat = sess.estimate(ClusterQuotientEstimator())
    casc = sess.estimate(CascadeEstimator(levels=0))
    _assert_estimates_identical(flat, casc)
    assert casc.method == "cascade"
    assert casc.pipeline.cascade_levels == 0
    assert casc.pipeline.level_clusters == []


def test_levels0_identical_even_when_quotient_is_large():
    """levels=0 must never cascade, no matter how small tau_solve is."""
    g = random_geometric(700, avg_degree=3.0, seed=2)
    sess = open_session(g, tau=8)
    flat = sess.estimate(ClusterQuotientEstimator())
    casc = sess.estimate(CascadeEstimator(levels=0, tau_solve=2))
    _assert_estimates_identical(flat, casc)


# ---------------------------------------------------------------------------
# bound contract across level counts and backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["single", "pallas"])
@pytest.mark.parametrize("levels", [0, 1, 2])
def test_cascade_bound_contract(backend, levels):
    from repro.config.base import GraphEngineConfig

    g = random_connected(300, 900, seed=9, weight_dist="uniform", high=1000)
    exact = _true_diameter(g)
    sess = open_session(g, GraphEngineConfig(backend=backend), tau=4,
                        tau_solve=4)
    lo = sess.estimate(LowerBoundEstimator(rounds=3, seed=0))
    up = sess.estimate(CascadeEstimator(levels=levels))
    assert lo.lower <= exact <= up.upper
    assert up.connected and lo.connected
    assert up.phi_approx == up.phi_quotient + 2 * up.radius
    if levels:
        assert up.pipeline.cascade_levels >= 1  # tau_solve=4 forces it


def test_cascade_monotone_in_levels():
    """Each extra level only coarsens the bound:
    diam(Q_l) <= 2 R_{l+1} + diam(Q_{l+1})."""
    g = random_geometric(1200, avg_degree=3.0, seed=3)
    sess = open_session(g, tau=8, tau_solve=8)
    uppers = [sess.estimate(CascadeEstimator(levels=lv)).upper
              for lv in (0, 1, 2, 3)]
    assert uppers == sorted(uppers)
    assert _true_diameter(g) <= uppers[0]


def test_cascade_sharded_backend_subprocess():
    """Level 0 on the sharded backend (forced 4-device host mesh), deeper
    levels on the device-resident single backend — the bound contract must
    hold end to end."""
    code = textwrap.dedent("""
    import jax, numpy as np
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    from repro.core import CascadeEstimator, open_session
    from repro.core.distributed import DistributedEngine
    from repro.graph import grid_mesh
    from repro.graph.structures import to_scipy_csr
    from scipy.sparse.csgraph import shortest_path
    g = grid_mesh(20, "uniform", high=100, seed=3)
    be = DistributedEngine(g, mesh, comm="halo").make_relax_fn()
    sess = open_session(g, tau=6, tau_solve=8, backend=be)
    est = sess.estimate(CascadeEstimator(levels=2))
    d = shortest_path(to_scipy_csr(g), method="D", directed=False)
    exact = int(d[np.isfinite(d)].max())
    assert est.connected
    assert est.upper >= exact, (est.upper, exact)
    assert est.pipeline.cascade_levels >= 1
    print("CASCADE-SHARDED-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "CASCADE-SHARDED-OK" in out.stdout


@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(30, 120),
    ef=st.integers(2, 5),
    seed=st.integers(0, 10_000),
    levels=st.integers(0, 2),
    wmax=st.sampled_from([1, 10, 1000, 2**20]),
)
def test_property_cascade_bracket(n, ef, seed, levels, wmax):
    """lower <= scipy exact <= cascade upper on random connected graphs at
    every level count; the interval bracket stays certified."""
    g = random_connected(n, n * ef, seed=seed, weight_dist="uniform",
                         high=wmax)
    exact = _true_diameter(g)
    sess = open_session(g, tau=4, tau_solve=4)
    lo = sess.estimate(LowerBoundEstimator(rounds=3, seed=0))
    up = sess.estimate(CascadeEstimator(levels=levels))
    assert lo.lower <= exact <= up.upper
    assert lo.connected == up.connected
    iv = sess.estimate(IntervalEstimator(estimators=(
        LowerBoundEstimator(rounds=3, seed=0),
        CascadeEstimator(levels=levels))))
    assert iv.lower <= exact <= iv.upper


# ---------------------------------------------------------------------------
# the int64 -> int32 weight rescale
# ---------------------------------------------------------------------------

def test_quotient_as_edgelist_rescales_and_inerts_padding():
    import jax
    import jax.numpy as jnp

    heavy = 3 * int(MAX_WEIGHT)  # int64-only quotient weight
    with jax.enable_x64(True):
        dq = DeviceQuotient(
            centers=jnp.arange(3, dtype=jnp.int32),
            src=jnp.asarray([0, 1, 2, 7], jnp.int32),
            dst=jnp.asarray([1, 2, 0, 7], jnp.int32),
            weight=jnp.asarray([heavy, 5, 1, int(INF64)], jnp.int64),
            n_clusters=jnp.int32(3), n_edges=jnp.int32(3),
            max_weight=jnp.int64(heavy),
            weight_sum=jnp.int64(heavy + 6),
        )
    lv = quotient_as_edgelist(dq, 3, 3, heavy, heavy + 6, edge_bucket=4)
    assert lv.scale == 3
    w = np.asarray(lv.weight)
    # ceil(heavy / 3) == MAX_WEIGHT; small weights ceil-divide; minimum 1
    assert w[0] == int(MAX_WEIGHT) and w[1] == 2 and w[2] == 1
    # the host mirror (graph/structures.rescale_weights) must agree with
    # the device kernel edge for edge
    from repro.graph import rescale_weights
    w_host, scale_host = rescale_weights(np.array([heavy, 5, 1], np.int64))
    assert scale_host == lv.scale
    np.testing.assert_array_equal(w[:3].astype(np.int64), w_host)
    # padding slot became an inert self-loop
    assert (int(lv.src[3]), int(lv.dst[3]), int(w[3])) == (0, 0, 1)
    el = lv.to_edgelist()  # host materialization passes EdgeList validation
    assert el.n_nodes == 3 and el.n_edges == 3
    assert lv.weight_sum >= int(w[:3].sum())


def test_cascade_conservative_under_rescale():
    """Weights near 2^30 push quotient sums past int32 — the cascade must
    rescale (scale > 1 somewhere) and STILL upper-bound the exact
    diameter."""
    g = random_connected(120, 360, seed=4, weight_dist="uniform",
                         high=2**30 - 1)
    exact = _true_diameter(g)
    sess = open_session(g, tau=4, tau_solve=4)
    est = sess.estimate(CascadeEstimator(levels=2))
    assert est.pipeline.cascade_levels >= 1
    assert est.upper >= exact
    assert est.connected


# ---------------------------------------------------------------------------
# degenerate inputs + accounting
# ---------------------------------------------------------------------------

def test_cascade_degenerate_graphs():
    for n in (0, 1):
        est = open_session(_edgeless(n), tau=2).estimate(
            CascadeEstimator(levels=2, tau_solve=2))
        assert est.phi_approx == 0 and est.connected
    # edgeless nodes: disconnected, diameter bound 0 over finite pairs
    est = open_session(_edgeless(5), tau=2).estimate(
        CascadeEstimator(levels=2, tau_solve=2))
    assert not est.connected
    # two triangles: every level preserves the component structure
    u = np.array([0, 1, 2, 3, 4, 5], np.int32)
    v = np.array([1, 2, 0, 4, 5, 3], np.int32)
    g = EdgeList.from_undirected(6, u, v, np.ones(6, np.int32))
    est = open_session(g, tau=2).estimate(
        CascadeEstimator(levels=2, tau_solve=2))
    assert not est.connected
    assert est.phi_approx >= 1


def test_cascade_metrics_accounting():
    g = random_geometric(1000, avg_degree=3.0, seed=7)
    sess = open_session(g, tau=8)
    est = sess.estimate(CascadeEstimator(levels=2, tau_solve=8))
    pm = est.pipeline
    assert pm.cascade_levels == len(pm.level_clusters) \
        == len(pm.level_supersteps) == len(pm.level_syncs) >= 1
    assert pm.total_host_syncs == (pm.decompose_syncs + pm.finalize_syncs
                                   + pm.quotient_syncs + pm.solve_syncs)
    # per-level syncs are part of (not in addition to) the scalar counters
    assert sum(pm.level_syncs) < pm.total_host_syncs
    # growing_steps aggregates every level's decomposition supersteps
    flat = sess.estimate(ClusterQuotientEstimator())
    assert est.growing_steps == flat.growing_steps + sum(pm.level_supersteps)


def test_cascade_validation_and_protocol():
    g = grid_mesh(4, "unit")
    sess = open_session(g)
    with pytest.raises(ValueError, match="levels"):
        sess.estimate(CascadeEstimator(levels=-1))
    with pytest.raises(ValueError, match="tau_solve"):
        sess.estimate(CascadeEstimator(tau_solve=1))
    with pytest.raises(ValueError, match="tau_solve"):
        open_session(g, tau_solve=0)
    with pytest.raises(ValueError, match="tau_solve"):
        SessionPool(tau_solve=1)
    assert isinstance(CascadeEstimator(), DiameterEstimator)


def test_cascade_in_pool_matches_unpooled():
    g = random_geometric(500, avg_degree=3.0, seed=6)
    pooled = SessionPool(tau_solve=8).open(g, tau=6)
    solo = open_session(g, tau=6, tau_solve=8)
    a = pooled.estimate(CascadeEstimator(levels=2))
    b = solo.estimate(CascadeEstimator(levels=2))
    _assert_estimates_identical(a, b, ignore=("seconds",))

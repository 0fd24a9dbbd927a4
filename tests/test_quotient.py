"""Device-resident quotient pipeline: jitted build_quotient parity with the
numpy oracle across backends (random + degenerate graphs), int64 exactness
of the batched multi-source solve against the scipy oracle, the unified
(diameter, connected) contract, the end-to-end host-sync budget, and the
batched multi-graph entry point."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (
    approximate_diameter,
    approximate_diameter_batch,
    build_quotient,
    build_quotient_numpy,
    cluster,
    make_backend,
    quotient_diameter,
    quotient_diameter_device,
    quotient_diameter_minplus,
    QuotientGraph,
)
from repro.core.engine import Decomposition
from repro.graph import grid_mesh, random_connected, random_geometric, social_like
from repro.graph.structures import EdgeList

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _assert_quotient_equal(a: QuotientGraph, b: QuotientGraph):
    assert a.n_clusters == b.n_clusters
    np.testing.assert_array_equal(a.center_ids, b.center_ids)
    np.testing.assert_array_equal(a.src, b.src)
    np.testing.assert_array_equal(a.dst, b.dst)
    np.testing.assert_array_equal(a.weight, b.weight)


def _manual_dec(final_c: np.ndarray, final_pathw: np.ndarray) -> Decomposition:
    n = len(final_c)
    return Decomposition(
        n_nodes=n, final_c=final_c.astype(np.int32),
        final_pathw=final_pathw.astype(np.int32),
        radius=int(final_pathw.max()) if n else 0, delta_end=1,
        n_clusters=len(np.unique(final_c)) if n else 0,
        n_stages=1, growing_steps=0,
    )


# ---------------------------------------------------------------------------
# jitted build_quotient == numpy oracle, edge for edge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gen,kw,tau", [
    (random_geometric, dict(n=1200, avg_degree=3.0), 10),
    (social_like, dict(n_log2=8, edge_factor=6, weight_dist="uniform",
                       high=2**20), 6),
    (grid_mesh, dict(side=20, weight_dist="bimodal", heavy_w=500,
                     heavy_p=0.15), 8),
])
@pytest.mark.parametrize("backend", ["single", "pallas"])
def test_build_quotient_parity_random(gen, kw, tau, backend):
    g = gen(**kw, seed=7)
    be = make_backend(g, backend)
    dec = cluster(g, tau, seed=2, backend=be)
    _assert_quotient_equal(build_quotient_numpy(g, dec),
                           build_quotient(g, dec, backend=be))


def test_build_quotient_parity_sharded():
    """Sharded backend (forced 4-device host mesh, subprocess so the XLA
    device count doesn't leak) — the quotient reads the engine's per-device
    edge shards with no host round-trip and must match numpy exactly."""
    code = textwrap.dedent("""
    import jax, numpy as np
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    from repro.graph import grid_mesh
    from repro.core import build_quotient, build_quotient_numpy, cluster
    from repro.core.distributed import DistributedEngine
    g = grid_mesh(24, "bimodal", heavy_w=500, heavy_p=0.15, seed=3)
    be = DistributedEngine(g, mesh, comm="halo").make_relax_fn()
    dec = cluster(g, 12, seed=5, relax_fn=be)
    a = build_quotient_numpy(g, dec)
    b = build_quotient(g, dec, backend=be)
    assert a.n_clusters == b.n_clusters
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    assert np.array_equal(a.weight, b.weight)
    assert np.array_equal(a.center_ids, b.center_ids)
    print("QUOTIENT-SHARDED-PARITY-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "QUOTIENT-SHARDED-PARITY-OK" in out.stdout


def test_build_quotient_parity_degenerate():
    z = np.array([], np.int32)
    # empty graph
    _assert_quotient_equal(
        build_quotient_numpy(EdgeList(0, z, z, z), _manual_dec(z, z)),
        build_quotient(EdgeList(0, z, z, z), _manual_dec(z, z)))
    # edgeless nodes (every node a singleton cluster, no quotient edges)
    dec = _manual_dec(np.arange(5), np.zeros(5))
    _assert_quotient_equal(build_quotient_numpy(EdgeList(5, z, z, z), dec),
                           build_quotient(EdgeList(5, z, z, z), dec))
    # single cluster: every edge internal
    g = grid_mesh(4, "unit")
    dec1 = _manual_dec(np.zeros(g.n_nodes), np.ones(g.n_nodes))
    q_np, q_dev = build_quotient_numpy(g, dec1), build_quotient(g, dec1)
    _assert_quotient_equal(q_np, q_dev)
    assert q_dev.n_clusters == 1 and len(q_dev.src) == 0
    # disconnected graph
    u = np.array([0, 1, 2, 3, 4, 5], np.int32)
    v = np.array([1, 2, 0, 4, 5, 3], np.int32)
    gd = EdgeList.from_undirected(6, u, v, np.ones(6, np.int32))
    dec2 = _manual_dec(np.array([0, 0, 2, 3, 3, 5]), np.array([0, 1, 0, 0, 1, 0]))
    _assert_quotient_equal(build_quotient_numpy(gd, dec2),
                           build_quotient(gd, dec2))


# ---------------------------------------------------------------------------
# solve: int64 exactness + unified (diameter, connected) contract
# ---------------------------------------------------------------------------

def _synthetic_quotient(k: int, m: int, wmin: int, wmax: int, seed: int = 0):
    """Random coalesced undirected quotient (one direction per pair — the
    solvers symmetrize, matching scipy's directed=False)."""
    rng = np.random.default_rng(seed)
    iu, iv = np.triu_indices(k, 1)
    sel = rng.choice(len(iu), size=min(m, len(iu)), replace=False)
    w = rng.integers(wmin, wmax, len(sel)).astype(np.int64)
    return QuotientGraph(k, np.arange(k, dtype=np.int32),
                         iu[sel].astype(np.int32), iv[sel].astype(np.int32), w)


def test_minplus_int64_regression_above_2_24():
    """Regression: the min-plus fallback cast int64 weights to float32,
    silently corrupting anything above 2^24. Cross-check the scipy oracle
    on weights well past that."""
    q = _synthetic_quotient(24, 90, 2**24, 2**30, seed=1)
    d_sp, c_sp = quotient_diameter(q)
    d_mp, c_mp = quotient_diameter_minplus(q)
    assert d_sp > 2**24
    assert (d_mp, c_mp) == (d_sp, c_sp)


def test_device_solve_exact_int64_up_to_2_40():
    """Acceptance: the device quotient solve matches the scipy oracle
    EXACTLY on int64 weights up to 2^40."""
    q = _synthetic_quotient(30, 90, 2**39, 2**40, seed=2)
    d_sp, c_sp = quotient_diameter(q)
    d_dev, ecc, c_dev = quotient_diameter_device(q)
    assert d_sp > 2**32  # float32 would corrupt this
    assert (d_dev, c_dev) == (d_sp, c_sp)
    assert int(ecc.max()) == d_sp
    assert len(ecc) == q.n_clusters


@pytest.mark.parametrize("x", [1, 2, 3, 7, 16, 17, 240, 257, 296, 1490,
                               3250, 5208, 2**20 + 1])
def test_solve_bucket_pads_at_most_a_quarter(x):
    """The solve's size buckets: at least ``x``, at most 25 % above it, and
    a bucket is its own bucket."""
    from repro.core.quotient import solve_bucket

    b = solve_bucket(x)
    assert x <= b <= 1.25 * x
    assert solve_bucket(b) == b


def test_device_solve_shares_one_program_per_bucket():
    """Quotients whose k and m fall in one bucket reuse one compiled solve,
    and their answers stay exact (padding slots never relax)."""
    import jax
    import jax.numpy as jnp

    from repro.core.quotient import (INF64, DeviceQuotient, _solve_kernel,
                                     solve_device_quotient)

    def solve(q, cap=2048):
        src = np.concatenate([q.src, q.dst]).astype(np.int32)
        dst = np.concatenate([q.dst, q.src]).astype(np.int32)
        w = np.concatenate([q.weight, q.weight]).astype(np.int64)
        pad = cap - len(src)
        with jax.enable_x64(True):
            dq = DeviceQuotient(
                centers=jnp.arange(q.n_clusters, dtype=jnp.int32),
                src=jnp.asarray(np.pad(src, (0, pad))),
                dst=jnp.asarray(np.pad(dst, (0, pad))),
                weight=jnp.asarray(np.pad(w, (0, pad),
                                          constant_values=INF64)),
                n_clusters=jnp.int32(q.n_clusters),
                n_edges=jnp.int32(len(src)), max_weight=jnp.int64(w.max()),
                weight_sum=jnp.int64(w.sum()))
        diam, _, connected, _ = solve_device_quotient(
            dq, q.n_clusters, len(src), int(w.max()))
        return diam, connected

    # k 140..160 and m 1120..1280 share the bucket (160, 1280); a fixed
    # granularity of 16 and 128 would split them over two programs
    qs = [_synthetic_quotient(k, 4 * k, 1, 2**30, seed=k)
          for k in (140, 150, 160)]
    assert solve(qs[0]) == quotient_diameter(qs[0])
    before = _solve_kernel._cache_size()
    for q in qs[1:]:
        assert solve(q) == quotient_diameter(q)
    assert _solve_kernel._cache_size() == before


def test_quotient_solvers_agree_on_disconnected():
    """Regression: the fallback used to return a bare finite max on a
    disconnected quotient while scipy returned (diameter, connected). All
    three paths now share the contract."""
    q = QuotientGraph(4, np.arange(4, dtype=np.int32),
                      np.array([0, 1], np.int32), np.array([1, 0], np.int32),
                      np.array([7, 7], np.int64))
    assert quotient_diameter(q) == (7, False)
    assert quotient_diameter_minplus(q) == (7, False)
    d, ecc, connected = quotient_diameter_device(q)
    assert (d, connected) == (7, False)


@pytest.mark.parametrize("gen,kw,tau", [
    (grid_mesh, dict(side=16, weight_dist="uniform", high=100), 8),
    (random_connected, dict(n=400, n_edges=1400, weight_dist="uniform",
                            high=2**20), 8),
])
def test_device_solver_matches_scipy_end_to_end(gen, kw, tau):
    g = gen(**kw, seed=9)
    dev = approximate_diameter(g, tau=tau)
    ora = approximate_diameter(g, tau=tau, solver="scipy")
    assert dev.phi_approx == ora.phi_approx
    assert dev.phi_quotient == ora.phi_quotient
    assert dev.connected == ora.connected


# ---------------------------------------------------------------------------
# pipeline host-sync budget + batched entry point
# ---------------------------------------------------------------------------

def test_pipeline_host_sync_budget():
    g = random_geometric(3000, avg_degree=3.0, seed=4)
    est = approximate_diameter(g, tau=16)
    pm = est.pipeline
    assert pm is not None
    assert pm.finalize_syncs == 1
    assert pm.quotient_syncs == 1
    assert pm.solve_syncs <= 1
    assert pm.total_host_syncs <= 8, pm


def test_batch_matches_individual_runs():
    graphs = [random_geometric(600, avg_degree=3.0, seed=s) for s in range(3)]
    batch = approximate_diameter_batch(graphs, tau=8)
    for g, est in zip(graphs, batch):
        solo = approximate_diameter(g, tau=8)
        assert est.phi_approx == solo.phi_approx
        assert est.n_clusters == solo.n_clusters
        assert est.connected == solo.connected


def test_batch_mixed_sizes_and_degenerates():
    z = np.array([], np.int32)
    graphs = [grid_mesh(6, "unit"), EdgeList(3, z, z, z), grid_mesh(6, "unit", seed=1)]
    ests = approximate_diameter_batch(graphs, tau=4)
    assert len(ests) == 3
    assert ests[0].phi_approx == approximate_diameter(graphs[0], tau=4).phi_approx
    assert not ests[1].connected  # 3 isolated nodes

"""The plain reference on small graphs: exact distances, the walk, the
decomposition certificate and the quotient's eccentricities."""
import numpy as np
import pytest

from bench import spec
from bench.reference import graph as rg
from bench.reference import est_ClusterQuotientEstimator as cq


def _kron(scale=6, seed=4):
    cfg = dict(spec.read_json(spec.ROOT + "/bench/configs/kron-g500.json"))
    cfg.update(scale=scale)
    return spec.family("kronecker").generate(cfg, seed)


@pytest.mark.parametrize("scale,seed", [(6, 4), (9, 5)])
def test_generated_graph_is_connected_and_symmetric(scale, seed):
    from scipy.sparse.csgraph import connected_components

    n, src, dst, w = _kron(scale, seed)
    g = rg.RefGraph(n, src, dst, w)
    assert connected_components(g.csr(), directed=False)[0] == 1
    fwd = set(zip(src.tolist(), dst.tolist(), w.tolist()))
    assert fwd == set(zip(dst.tolist(), src.tolist(), w.tolist()))
    assert w.min() >= 1


def test_kron_family_keeps_its_largest_component():
    """Every vertex of nonzero degree in the Graph500 edge list that
    reaches the largest component is kept, and no other."""
    n, src, _, _ = _kron(scale=10)
    assert 2**10 // 2 < n < 2**10 and len(src) <= 2 * 16 * 2**10
    assert set(src.tolist()) == set(range(n))


@pytest.mark.parametrize("scale,seed", [(6, 4), (8, 3)])
def test_dijkstra_equals_bellman_ford(scale, seed):
    g = rg.RefGraph(*_kron(scale, seed))
    for s in (0, g.n // 2, g.n - 1):
        d_bf, steps = g.bellman_ford(s)
        assert (g.sssp([s])[0] == d_bf).all() and steps > 1


def test_bellman_ford_counts_supersteps_like_the_full_relaxation():
    """Relaxing only the arcs out of changed nodes leaves, after every
    superstep, what relaxing all arcs leaves."""
    g = rg.RefGraph(*_kron(scale=5))
    d = np.full(g.n, np.iinfo(np.int64).max // 2)
    d[0] = 0
    for k in range(1, g.bellman_ford(0)[1] + 1):
        cand = np.full(g.n, np.iinfo(np.int64).max // 2)
        np.minimum.at(cand, g.dst, d[g.src] + g.w)
        d = np.minimum(d, cand)
        want = np.where(d < np.iinfo(np.int64).max // 2, d, rg.UNREACHED)
        assert (g.bellman_ford(0, max_steps=k)[0] == want).all()


def test_half_steps_bellman_ford_falls_short():
    g = rg.RefGraph(*_kron())
    full, steps = g.bellman_ford(0)
    half, _ = g.bellman_ford(0, max_steps=steps // 2)
    assert (half != full).any()


def test_float32_bellman_ford_rounds_large_distances():
    g = rg.RefGraph(*_kron())
    exact, _ = g.bellman_ford(0)
    f32, _ = g.bellman_ford(0, np.float32)
    assert exact.max() > 2**24 and (f32 != exact).any()


def test_farthest_walk_reaches_a_realized_distance():
    g = rg.RefGraph(*_kron())
    lower, upper, hops = rg.farthest_walk(5, 4, lambda s: g.sssp([s])[0])
    all_pairs = g.sssp(np.arange(g.n))
    assert lower <= all_pairs.max() <= upper
    assert lower in all_pairs and 1 <= hops <= 4


def test_gap():
    assert rg.gap(5, 5) == 0 and rg.gap(5, 8) == 3
    assert rg.gap(None, 4) == 1 and rg.gap(None, None) == 0


def _singletons_then_merge(g):
    """A valid decomposition: node 0's neighbours join its cluster."""
    c = np.arange(g.n)
    p = np.zeros(g.n, np.int64)
    nb = g.dst[g.src == 0]
    c[nb] = 0
    p[nb] = g.w[g.src == 0]
    return c, p


def test_certificate_accepts_a_valid_decomposition():
    g = rg.RefGraph(*_kron())
    c, p = _singletons_then_merge(g)
    assert cq.certificate_failures(g, c, p) == 0


def _short_cluster_off_the_radius(g, c, p):
    """Two new clusters of two nodes each, away from node 0's: one whose
    member lies at its true distance and holds the radius, and one whose
    member's ``pathw`` is one short of its distance."""
    c[:], p[:] = np.arange(g.n), 0
    d = g.sssp(np.arange(g.n))
    pairs = sorted({(int(a), int(b)) for a, b in zip(g.src, g.dst)
                    if 0 not in (a, b) and d[a, b] >= 2},
                   key=lambda e: d[e])
    (z, t), (x, y) = pairs[0], next(e for e in reversed(pairs)
                                    if not {*e} & {*pairs[0]})
    c[y], p[y] = x, d[x, y]
    c[t], p[t] = z, d[z, t] - 1
    assert p[t] < p.max()


@pytest.mark.parametrize("fault", ["short_path", "center_moved",
                                   "not_a_center", "short_path_off_radius"])
def test_certificate_catches_a_broken_decomposition(fault):
    g = rg.RefGraph(*_kron())
    c, p = _singletons_then_merge(g)
    nb = g.dst[g.src == 0]
    if fault == "short_path":
        p[nb[0]] = g.sssp([0])[0][nb[0]] - 1
    elif fault == "short_path_off_radius":
        _short_cluster_off_the_radius(g, c, p)
    elif fault == "center_moved":
        p[0] = 1
    else:
        c[g.n - 1] = nb[0]   # nb[0] belongs to cluster 0: no center
    assert cq.certificate_failures(g, c, p) > 0


def test_quotient_eccentricities_against_all_pairs():
    g = rg.RefGraph(*_kron(scale=7))
    c, p = _singletons_then_merge(g)
    q = cq.quotient(g, c, p)
    ecc, connected = cq.eccentricities(q)
    full = q.sssp(np.arange(q.n))
    assert connected and (ecc == full.max(axis=1)).all()

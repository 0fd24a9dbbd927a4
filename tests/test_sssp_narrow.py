"""Narrow-first Bellman-Ford (``core/sssp.bf_from``): where the provable
``n * max_weight`` bound asks for int64, the loop runs in int32 with a
saturating add and widens to int64 on the device only if it saturated.

Every answer is checked bit for bit against scipy's Dijkstra and against a
forced int64 run of ``_bf_loop``; ``widened`` must be 0 where the realized
distances fit int32 and 1 where one reaches the saturation value ``top``
(``2^31 - 2``); ``_sssp_from`` keeps one host fetch either way."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

from repro.analysis import guard
from repro.core import open_session, sssp
from repro.core.estimators import _sssp_from
from repro.graph.structures import EdgeList
from repro.runtime import telemetry

TOP = 2**31 - 2
WMAX = 2**30 - 1


def _dijkstra(g: EdgeList, source: int) -> np.ndarray:
    """Exact int64 distances, ``INF64`` where unreached (parallel arcs keep
    their lightest weight, as relaxation does)."""
    order = np.lexsort((g.weight, g.dst, g.src))
    s, d, w = g.src[order], g.dst[order], g.weight[order]
    first = np.ones(len(s), bool)
    first[1:] = (s[1:] != s[:-1]) | (d[1:] != d[:-1])
    a = csr_matrix((w[first].astype(np.float64), (s[first], d[first])),
                   shape=(g.n_nodes, g.n_nodes))
    ref = dijkstra(a, indices=source)
    out = np.full(g.n_nodes, sssp.INF64, np.int64)
    fin = np.isfinite(ref)
    out[fin] = ref[fin].astype(np.int64)
    return out


def _forced_int64(g: EdgeList, source: int) -> np.ndarray:
    with jax.enable_x64(True):
        inf = jnp.asarray(sssp.INF64, jnp.int64)
        d0 = jnp.full(g.n_nodes, inf).at[source].set(0)
        d, _ = sssp._bf_loop(jnp.asarray(g.src), jnp.asarray(g.dst),
                             jnp.asarray(g.weight).astype(jnp.int64), d0,
                             inf, g.n_nodes)
        return guard.fetch(d, reason="test: forced int64 plane")


def _path(weights) -> EdgeList:
    n = len(weights) + 1
    u = np.arange(n - 1, dtype=np.int32)
    return EdgeList.from_undirected(n, u, u + 1,
                                    np.asarray(weights, np.int32))


def _fits_int32() -> EdgeList:
    """n * max_weight passes 2^31, the realized distances stay near 2^30."""
    r = np.random.default_rng(3)
    n, m = 200, 900
    u = np.concatenate([np.arange(n - 1), r.integers(0, n, m)])
    v = np.concatenate([np.arange(1, n), r.integers(0, n, m)])
    keep = u != v
    w = r.integers(1, 2**26 + 1, len(u))
    return EdgeList.from_undirected(n, u[keep], v[keep], w[keep])


def _disconnected() -> EdgeList:
    """Two heavy paths, no edge between them; the bound needs int64."""
    u = np.array([0, 1, 2, 4, 5], np.int32)
    v = np.array([1, 2, 3, 5, 6], np.int32)
    return EdgeList.from_undirected(8, u, v,
                                    np.array([5, WMAX, 7, WMAX, 3], np.int32))


# name -> (graph, source, widened)
CASES = {
    "fits_int32": (_fits_int32, 0, 0),
    "passes_2^31": (lambda: _path([WMAX] * 5), 0, 1),
    "lands_on_top": (lambda: _path([WMAX, WMAX]), 0, 1),
    "lands_on_top_minus_1": (lambda: _path([WMAX, WMAX - 1]), 0, 0),
    "disconnected": (_disconnected, 0, 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_bellman_ford_narrow_first_is_exact(case):
    make, source, widened = CASES[case]
    g = make()
    assert sssp.sssp_dtype_for(g.n_nodes, int(g.weight.max()))[0] == jnp.int64
    res = sssp.bellman_ford(g, source)
    assert res.inf == sssp.INF64
    assert res.widened == bool(widened)
    np.testing.assert_array_equal(res.dist, _dijkstra(g, source))
    np.testing.assert_array_equal(res.dist, _forced_int64(g, source))


@pytest.mark.parametrize("case", sorted(CASES))
def test_sssp_from_narrow_first_one_fetch(case):
    make, source, widened = CASES[case]
    g = make()
    sess = open_session(g, tau=2)
    tracer = telemetry.Tracer()
    with telemetry.tracing(tracer), guard.measured_transfers("allow") as m:
        dist, steps, inf = _sssp_from(sess, source, None)
    assert m.transfers == 1, m.by_reason()
    assert inf == sssp.INF64
    np.testing.assert_array_equal(dist, _dijkstra(g, source))
    assert steps == sssp.bellman_ford(g, source).supersteps
    (span,) = [s for s in tracer.spans if s.name == "sssp.solve"]
    assert span.attrs["widened"] == widened
    assert span.attrs["dtype"] == ("int64" if widened else "int32")
    assert span.attrs["supersteps"] == steps
    assert sess.metrics.sssp_widened == widened


def test_provable_int32_path_never_widens():
    """Where the bound proves int32 enough, the int32 run is the answer:
    the same plane, sentinel and superstep count as before the change."""
    g = _path([3, 5, 7, 11])
    assert sssp.sssp_dtype_for(g.n_nodes, 11)[0] == jnp.int32
    res = sssp.bellman_ford(g, 0)
    assert res.inf == 2**31 - 1 and res.dist.dtype == np.int32
    assert not res.widened
    np.testing.assert_array_equal(res.dist, [0, 3, 8, 15, 26])
    sess = open_session(g, tau=2)
    tracer = telemetry.Tracer()
    with telemetry.tracing(tracer):
        dist, steps, inf = _sssp_from(sess, 0, None)
    assert inf == 2**31 - 1 and steps == res.supersteps
    np.testing.assert_array_equal(dist, res.dist)
    (span,) = [s for s in tracer.spans if s.name == "sssp.solve"]
    assert span.attrs["dtype"] == "int32" and span.attrs["widened"] == 0


def test_saturating_loop_matches_int64_below_top():
    """The saturating add changes nothing below ``top``: on a graph whose
    provable bound is int32, int64 and int32 runs agree bit for bit."""
    g = _fits_int32()
    small = EdgeList(g.n_nodes, g.src, g.dst, (g.weight % 1000) + 1)
    np.testing.assert_array_equal(sssp.bellman_ford(small, 5).dist,
                                  _forced_int64(small, 5))

"""Reference of the cluster-quotient upper bound
(``ClusterQuotientEstimator``, the paper's Sections 4-5).

The decomposition under check is the program's own ``(center, pathw)`` per
node, and the bound needs ``pathw[v] >= dist(center(v), v)`` for every
node. A node's path may run through other clusters' nodes (the engine's
waves and relays carry a realized path from the center, not a path inside
the cluster), so no one-pass witness exists. The check is: every cluster
id is a center with ``pathw = 0``, no ``pathw`` is negative, and for every
cluster, a Dijkstra from its center bounded at the cluster's largest
``pathw`` reaches every member within its ``pathw`` (centers in blocks of
like bounds; a cluster whose bound is 0 holds its center alone).
``R = max pathw``. The quotient has one node per cluster and, for each
pair of clusters joined by an arc ``u -> v``, the weight
``min(pathw[u] + w + pathw[v])``; its all-pairs eccentricities come from
Dijkstra, and ``upper = Phi(G_C) + 2R``.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from bench.reference.graph import UNREACHED, RefGraph, gap

SOURCE_BLOCK = 256
# centers whose bounded searches run as one scipy call
CENTER_BLOCK = 32
# the check reads the program's decomposition of the query
CAPTURE = True


def certificate_failures(g, center: np.ndarray, pathw: np.ndarray) -> int:
    """Nodes whose cluster id or distance the check does not bear."""
    from scipy.sparse.csgraph import dijkstra

    n = g.n
    c = np.asarray(center, np.int64)
    p = np.asarray(pathw, np.int64)
    if c.shape != (n,) or p.shape != (n,) or n == 0:
        return n
    if c.min() < 0 or c.max() >= n:
        return n
    is_center = c == np.arange(n)
    bad = (int((c[c] != c).sum()) + int((is_center & (p != 0)).sum())
           + int((p < 0).sum()))
    order = np.argsort(c, kind="stable")
    ids, starts = np.unique(c[order], return_index=True)
    bound = np.maximum.reduceat(p[order], starts)
    # a bound of 0 admits the center alone: weights are at least 1
    bad += int(((bound[np.searchsorted(ids, c)] == 0) & ~is_center).sum())
    ends = np.r_[starts[1:], n]
    wide = np.flatnonzero(bound > 0)
    wide = wide[np.argsort(bound[wide], kind="stable")]
    for lo in range(0, len(wide), CENTER_BLOCK):
        ks = wide[lo:lo + CENTER_BLOCK]
        d = np.atleast_2d(dijkstra(g.csr(), directed=True, indices=ids[ks],
                                   limit=float(bound[ks].max()) + 0.5))
        for row, k in enumerate(ks):
            members = order[starts[k]:ends[k]]
            bad += int((d[row, members] > p[members]).sum())
    return bad


def quotient(g, center: np.ndarray, pathw: np.ndarray) -> RefGraph:
    ids, inv = np.unique(np.asarray(center, np.int64), return_inverse=True)
    p = np.asarray(pathw, np.int64)
    cu, cv = inv[g.src], inv[g.dst]
    cross = cu != cv
    wq = p[g.src[cross]] + g.w[cross] + p[g.dst[cross]]
    return RefGraph(len(ids), cu[cross], cv[cross], wq)


def eccentricities(q: RefGraph):
    """(eccentricity of each quotient node, every pair connected)."""
    ecc = np.zeros(q.n, np.int64)
    connected = True
    for lo in range(0, q.n, SOURCE_BLOCK):
        d = q.sssp(np.arange(lo, min(lo + SOURCE_BLOCK, q.n)))
        connected = connected and bool((d != UNREACHED).all())
        ecc[lo:lo + len(d)] = d.max(axis=1)
    return ecc, connected


def expected(g, args: dict, seed: int, capture=None, control=None):
    """The control leaves this side exact: the SSSP bounds' numbers are
    the ones it moves (``reference.graph.control_sssp``)."""
    center, pathw = capture
    q = quotient(g, center, pathw)
    ecc, connected = eccentricities(q)
    radius = int(np.asarray(pathw).max()) if g.n else 0
    diam = int(ecc.max()) if q.n > 1 else 0
    return SimpleNamespace(
        upper=diam + 2 * radius, radius=radius, n_clusters=q.n,
        quotient_ecc=ecc, connected=connected,
        certificate_failures=certificate_failures(g, center, pathw))


def compare(got, want) -> dict:
    ecc = np.asarray(got.quotient_ecc if got.quotient_ecc is not None
                     else [], np.int64)
    if ecc.shape == want.quotient_ecc.shape:
        ecc_bad = int((ecc != want.quotient_ecc).sum())
    else:
        ecc_bad = max(len(ecc), len(want.quotient_ecc))
    return {
        "certificate_failures": want.certificate_failures,
        "quotient_ecc_mismatches": ecc_bad,
        "quotient_upper_gap": gap(got.upper, want.upper),
    }

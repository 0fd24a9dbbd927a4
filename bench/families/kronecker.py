"""Graph500 Kronecker graph (R-MAT recursion), its largest component, with
integer weights.

``2^scale`` vertices and ``edge_factor * 2^scale`` undirected edges, each
placed by ``scale`` quadrant draws with probabilities A, B, C and
D = 1 - A - B - C (Graph500's A/B/C = 0.57/0.19/0.19), then vertex labels
permuted at random as Graph500 does. The graph kept is the largest
connected component: Graph500 searches from a vertex of
nonzero degree and reaches its component alone, and a diameter exists only
on a connected graph.

The edges and labels are one instance, drawn from the configuration's
``graph_seed``: the engine's blocked edge layout follows where each label
falls, so a graph drawn anew in every run would give every run other
shapes to compile. The run's seed draws the weights: Graph500's uniform
weights in fixed point, integers uniform in [``w_low``, ``w_high``], one per
generated edge. Self loops are dropped and parallel edges keep their
lightest weight, which changes no distance.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components


def _instance(cfg: dict):
    """The generated edges ``(n, u, v)`` of the largest component, its
    vertices numbered 0..n-1 in the order of their permuted labels."""
    scale, ef = int(cfg["scale"]), int(cfg["edge_factor"])
    a, b, c = float(cfg["a"]), float(cfg["b"]), float(cfg["c"])
    n, m = 1 << scale, (1 << scale) * ef
    r = np.random.default_rng(int(cfg["graph_seed"]))
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for _ in range(scale):
        p = r.random(m)
        u = (u << 1) | (p >= a + b)
        v = (v << 1) | (((p >= a) & (p < a + b)) | (p >= a + b + c))
    label = r.permutation(n)
    u, v = label[u], label[v]
    keep = u != v
    u, v = u[keep], v[keep]
    _, comp = connected_components(
        coo_matrix((np.ones(len(u)), (u, v)), shape=(n, n)), directed=False)
    giant = np.bincount(comp[u]).argmax()
    keep = comp[u] == giant
    kept = np.flatnonzero(comp == giant)
    new = np.full(n, -1, np.int64)
    new[kept] = np.arange(len(kept))
    return len(kept), new[u[keep]], new[v[keep]]


def generate(cfg: dict, seed: int):
    """``(n, src, dst, weight)``: int32 arrays holding both arcs of every
    edge of the largest component."""
    n, u, v = _instance(cfg)
    r = np.random.default_rng(seed)
    w = r.integers(int(cfg["w_low"]), int(cfg["w_high"]) + 1, size=len(u))
    src = np.concatenate([u, v])
    dst = np.concatenate([v, u])
    ww = np.concatenate([w, w])
    # lightest weight per (src, dst)
    order = np.lexsort((ww, dst, src))
    src, dst, ww = src[order], dst[order], ww[order]
    first = np.ones(len(src), bool)
    first[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
    return (n, src[first].astype(np.int32), dst[first].astype(np.int32),
            ww[first].astype(np.int32))

"""Exact shortest paths on the benchmark's graph, and the control's
Bellman-Ford in a chosen precision."""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

UNREACHED = -1


class RefGraph:
    """A directed multigraph held as its lightest arc per (src, dst)."""

    def __init__(self, n: int, src, dst, weight):
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        w = np.asarray(weight, np.int64)
        order = np.lexsort((w, dst, src))
        src, dst, w = src[order], dst[order], w[order]
        first = np.ones(len(src), bool)
        first[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
        self.n = int(n)
        self.src, self.dst, self.w = src[first], dst[first], w[first]
        self._csr: Optional[csr_matrix] = None
        self._by_src: Optional[np.ndarray] = None

    def csr(self) -> csr_matrix:
        if self._csr is None:
            self._csr = csr_matrix(
                (self.w.astype(np.float64), (self.src, self.dst)),
                shape=(self.n, self.n))
        return self._csr

    def sssp(self, sources) -> np.ndarray:
        """Exact int64 distances ``[len(sources), n]`` (``UNREACHED`` where
        none). Dijkstra in float64, exact for sums below 2^53."""
        d = dijkstra(self.csr(), directed=True, indices=np.asarray(sources))
        d = np.atleast_2d(d)
        out = np.full(d.shape, UNREACHED, np.int64)
        fin = np.isfinite(d)
        out[fin] = d[fin].astype(np.int64)
        return out

    def bellman_ford(self, source: int, dtype=np.int64,
                     max_steps: Optional[int] = None) -> Tuple[np.ndarray, int]:
        """Bellman-Ford from ``source`` with its sums rounded to ``dtype``,
        stopped after ``max_steps`` supersteps when given. Each superstep
        relaxes the arcs out of the nodes that changed in the one before,
        which leaves the same distances as relaxing every arc. Returns
        (distances with ``UNREACHED``, supersteps run, the last of them
        the one that changed nothing). The control's path, not the
        reference's."""
        if self._by_src is None:
            counts = np.bincount(self.src, minlength=self.n)
            self._by_src = np.r_[0, np.cumsum(counts)]
        ptr = self._by_src
        floating = np.issubdtype(dtype, np.floating)
        big = dtype(np.inf) if floating else np.iinfo(dtype).max // 2
        w = self.w.astype(dtype)
        d = np.full(self.n, big, dtype)
        d[source] = 0
        frontier = np.array([source])
        steps = 0
        while max_steps is None or steps < max_steps:
            steps += 1
            lo, cnt = ptr[frontier], ptr[frontier + 1] - ptr[frontier]
            idx = np.repeat(lo - np.r_[0, np.cumsum(cnt)[:-1]], cnt) \
                + np.arange(cnt.sum())
            best = np.full(self.n, big, dtype)
            np.minimum.at(best, self.dst[idx],
                          (np.repeat(d[frontier], cnt) + w[idx]).astype(dtype))
            frontier = np.flatnonzero(best < d)
            if not len(frontier):
                break
            d[frontier] = best[frontier]
        out = np.full(self.n, UNREACHED, np.int64)
        fin = d < big
        out[fin] = d[fin].astype(np.int64)
        return out, steps


def gap(got, want) -> int:
    """How far an answer lies from the reference's: 0 when equal, the
    absolute difference of two integers, and 1 where only one side gives a
    bound."""
    if got == want:
        return 0
    if got is None or want is None:
        return 1
    return abs(int(got) - int(want))


def farthest_walk(source: int, rounds: int,
                  sssp: Callable[[int], np.ndarray]):
    """The farthest-point walk: from ``source``, ``rounds`` times, take the
    eccentricity and move to the first farthest node. Returns (lower,
    ``2 * first eccentricity`` or None when some node is unreached, hops)."""
    best, first, hops, connected = 0, 0, 0, True
    s = source
    for _ in range(rounds):
        d = sssp(s)
        hops += 1
        connected = connected and bool((d != UNREACHED).all())
        far = int(d.argmax())
        best = max(best, int(d[far]))
        if hops == 1:
            first = int(d[far])
        if far == s:
            break
        s = far
    return best, (2 * first if connected else None), hops


def source_of(seed: int, n: int) -> int:
    """A query's random source: the traffic's rule, ``default_rng(seed)``'s
    first integer below n."""
    return int(np.random.default_rng(seed).integers(n))


def control_sssp(g: RefGraph, control: str) -> Callable[[int], np.ndarray]:
    """One source's distances as the control computes them:
    ``float32`` accumulates in float32; ``half_steps`` stops Bellman-Ford
    at half the supersteps that it needs."""
    if control == "float32":
        return lambda s: g.bellman_ford(s, np.float32)[0]
    if control == "half_steps":
        def run(s):
            steps = g.bellman_ford(s)[1]
            return g.bellman_ford(s, max_steps=max(steps // 2, 1))[0]
        return run
    raise ValueError(f"unknown control {control!r}")

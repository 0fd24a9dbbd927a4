"""SSSP baselines (paper Section 5 competitor + Table 1 lower bounds).

- ``bellman_ford``: the natural parallel Bellman-Ford (SSSP-BF). Each
  superstep relaxes every edge; the superstep count is the competitor's
  round complexity in the MR model (the quantity CLUSTER beats).
- ``delta_stepping``: Meyer & Sanders bucketed SSSP. The paper notes that on
  a round-driven platform the best setting degenerates to Delta = inf ==
  Bellman-Ford; we implement real buckets anyway for completeness.
- ``batched_bf_loop`` / ``multi_source_bellman_ford``: frontier Bellman-Ford
  ``vmap``ped over a batch of sources — the device-local quotient solve
  (``core/quotient.py``) runs this over ALL quotient nodes in one program.
- ``diameter_2approx_sssp``: 2-approximation from a random source.
- ``farthest_point_lower_bound``: repeated SSSP hopping to the farthest node
  (how the paper computes the Phi column of Table 1).

Distance dtype is picked from a provable bound (``sssp_dtype_for``): every
shortest path has < n edges, so when ``n * max_weight`` fits int32 the
loops run in int32; otherwise they need int64 under ``jax.enable_x64``
(legal edge weights go up to 2^30 - 1, which overflows int32 after a
handful of hops — the old int32-only loops silently wrapped negative and
reported false minima). ``SSSPResult.inf`` carries the unreached sentinel
of the chosen dtype so callers mask with the right value.

Bellman-Ford runs narrow first (``bf_from``): the bound is a worst case,
and the distances a graph realizes usually sit far below it. Where the
bound asks for int64, the loop first runs in int32 with a saturating add
(a candidate is ``min(d + w, 2^31 - 2)``, computed so that it never
wraps), whose fixpoint is ``min(true distance, 2^31 - 2)`` on every
reached node. A second device program then looks for the saturated value:
if none is there the int32 plane is exact and is widened to int64; if any
is, the loop reruns in int64 from the same source. Both steps stay on the
device, with no host sync between them, and the answer is the same int64
plane either way. Δ-stepping keeps the provable pick (its bucket bound
would need its own saturation argument).

Disconnected inputs: every estimator surfaces a ``connected`` flag
(consistent with ``DiameterEstimate.connected``) instead of silently
bounding only finite-distance pairs. Empty graphs (``n_nodes == 0``) get
the degenerate estimate (diameter 0, ``connected=True`` — the same
``n_nodes <= 1`` convention as ``DiameterEstimate``) instead of a crash.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import guard
from repro.graph.structures import EdgeList

# numpy scalars, not jax arrays: a jax array closed over by a traced
# function is copied device->host when the program is lowered
INF = np.int32(2**31 - 1)
INF64 = 2**62  # int64 unreached sentinel; guarded adds stay < 2^63


def sssp_dtype_for(n_nodes: int, max_weight: int, delta: int = 0):
    """(dtype, inf) from the provable distance bound: every shortest path
    has < n edges, so distances are < n * max_weight. int32 fast path when
    that fits, int64 (under jax.enable_x64) otherwise.

    ``delta``: headroom for Δ-stepping's bucket bound ``(b + 1) * delta``
    — it can exceed the largest distance by up to one bucket, so bucketed
    callers must pass their delta or the int32 fast path could wrap the
    bound negative and stall the bucket walk."""
    if n_nodes * max(int(max_weight), 1) + int(delta) < 2**31 - 1:
        return jnp.int32, 2**31 - 1
    return jnp.int64, INF64


@dataclass
class SSSPResult:
    dist: np.ndarray
    supersteps: int
    inf: int = int(2**31 - 1)  # unreached sentinel of dist's dtype
    widened: bool = False      # the int32 run saturated; int64 rerun


@dataclass
class MultiSSSPResult:
    dist: np.ndarray  # [S, n]
    supersteps: int
    connected: bool   # every source reaches every node


@partial(jax.jit, static_argnames=("n_nodes",))
def _bf_loop(src, dst, w, d0, inf, n_nodes: int):
    """Dtype-generic frontier Bellman-Ford; ``inf`` is the unreached
    sentinel in d0's dtype (``w`` may be narrower: int32 weights widen
    inside the add, so an int64 run makes no int64 weight copy).

    The add saturates at ``top = inf - 1``: a candidate is
    ``min(ds, top - w) + w == min(ds + w, top)``, which never wraps, so the
    fixpoint is ``min(true distance, top)`` on every reached node and
    unreached nodes stay ``inf``. Under the provable pick
    (``sssp_dtype_for``) every candidate is a walk sum of at most n edges,
    <= n * max_weight <= top, so the saturation never bites and the result
    is the exact one; ``bf_from`` runs int32 past that bound and reads a
    ``top`` in the result as "widen"."""
    top = inf - 1

    def cond(carry):
        _, changed, _ = carry
        return changed

    def body(carry):
        d, _, k = carry
        ds = d[src]
        cand = jnp.where(ds < inf, jnp.minimum(ds, top - w) + w, inf)
        dmin = jax.ops.segment_min(cand, dst, num_segments=n_nodes)
        upd = dmin < d
        return jnp.where(upd, dmin, d), jnp.any(upd), k + 1

    d, _, k = jax.lax.while_loop(cond, body, (d0, jnp.bool_(True), jnp.int32(0)))
    return d, k


def _edge_arrays(edges: EdgeList, dtype):
    return (jnp.asarray(edges.src), jnp.asarray(edges.dst),
            jnp.asarray(edges.weight).astype(dtype))


@partial(jax.jit, static_argnames=("n_nodes",))
def _widen_on_saturation(src, dst, w, d32, k32, source, n_nodes: int):
    """(int64 dist, supersteps, widened) from a saturating int32 run.

    ``widened`` is true where any node holds the int32 ``top`` (a real
    distance exactly at ``top`` counts too: a conservative rerun); the
    int64 loop then reruns from ``source``. Otherwise the int32 plane is
    exact and only maps its sentinel to ``INF64``. The caller's int32 run
    and this program follow each other on the device, with no host sync."""
    widened = jnp.any(d32 == INF - 1)

    def rerun():
        inf = jnp.asarray(INF64, jnp.int64)
        d0 = jnp.full(n_nodes, inf).at[source].set(0)
        d, k = _bf_loop(src, dst, w, d0, inf, n_nodes)
        return d, k

    def widen():
        return jnp.where(d32 < INF, d32.astype(jnp.int64), INF64), k32

    d, k = jax.lax.cond(widened, rerun, widen)
    return d, k, widened


def bf_from(src, dst, w, source: int, n_nodes: int, max_weight: int):
    """Bellman-Ford from ``source`` on device edge arrays with int32
    weights, narrow first. Call under ``jax.enable_x64(True)``.

    Returns device ``(dist, supersteps, widened)`` and the host ``inf``
    of dist's dtype. Where ``sssp_dtype_for`` proves int32 enough, the
    int32 run is the answer (``widened`` is a constant False). Otherwise
    the same int32 run saturates instead of wrapping, and
    ``_widen_on_saturation`` turns it into the exact int64 plane, rerunning
    in int64 only when the run saturated. ``_bf_loop`` is looked up here at
    call time, outside any enclosing jit."""
    dtype, inf = sssp_dtype_for(n_nodes, max_weight)
    d0 = jnp.full(n_nodes, INF, jnp.int32).at[source].set(0)
    d, k = _bf_loop(src, dst, w, d0, INF, n_nodes)
    if dtype == jnp.int32:
        return d, k, np.bool_(False), inf
    d, k, widened = _widen_on_saturation(src, dst, w, d, k,
                                         np.int32(source), n_nodes)
    return d, k, widened, inf


def bellman_ford(edges: EdgeList, source: int) -> SSSPResult:

    n = edges.n_nodes
    wmax = int(edges.weight.max()) if edges.n_edges else 1
    with jax.enable_x64(True):
        d, k, widened, inf = bf_from(
            jnp.asarray(edges.src), jnp.asarray(edges.dst),
            jnp.asarray(edges.weight), source, n, wmax)
        dist = guard.fetch(d, reason="sssp baseline: distance plane")
        k, widened = (int(x) for x in guard.fetch(
            jnp.stack([jnp.asarray(k, jnp.int32),
                       jnp.asarray(widened, jnp.int32)]),
            reason="sssp baseline: (superstep counter, widened)"))
    return SSSPResult(dist=dist, supersteps=k, inf=inf, widened=bool(widened))


@partial(jax.jit, static_argnames=("n_nodes",))
def batched_bf_loop(src, dst, w, d0, inf, n_nodes: int):
    """Frontier Bellman-Ford over a batch of sources at once.

    ``d0`` is [n_nodes, S] — NODES ALONG AXIS 0, so each superstep is one
    contiguous row-gather ``d[src]`` plus one ND ``segment_min`` (row-wise
    scatter), which XLA vectorizes ~5x better than a vmap of per-source
    scalar scatters. ``inf`` is the unreached sentinel in d0's dtype
    (int64-safe: callers trace this under ``jax.enable_x64(True)``
    with ``inf < dtype_max / 2`` so the guarded add never overflows).
    Padding edges are expressed as ``w >= inf`` and never relax. The loop
    runs until no distance changes anywhere in the batch. Returns
    (dist [n_nodes, S], supersteps).
    """
    w_ok = w < inf

    def cond(carry):
        _, changed, _ = carry
        return changed

    def body(carry):
        d, _, k = carry
        du = d[src, :]                                   # [E, S]
        ok = (du < inf) & w_ok[:, None]
        cand = jnp.where(ok, jnp.where(ok, du, 0) + w[:, None], inf)
        dmin = jax.ops.segment_min(cand, dst, num_segments=n_nodes)
        dnew = jnp.minimum(d, dmin)
        return dnew, jnp.any(dnew < d), k + 1

    d, _, k = jax.lax.while_loop(
        cond, body, (d0, jnp.bool_(True), jnp.int32(0)))
    return d, k


def multi_source_bellman_ford(edges: EdgeList, sources) -> MultiSSSPResult:
    """All-sources-at-once SSSP (one compiled program, one host sync).

    Distance dtype is picked by ``sssp_dtype_for`` from the same provable
    bound as the single-source loops.
    """

    n = edges.n_nodes
    sources = np.asarray(sources, dtype=np.int32)
    wmax = int(edges.weight.max()) if edges.n_edges else 1
    dtype, inf = sssp_dtype_for(n, wmax)
    with jax.enable_x64(True):
        infj = jnp.asarray(inf, dtype)
        d0 = jnp.full((n, len(sources)), infj, dtype=dtype)
        d0 = d0.at[jnp.asarray(sources), jnp.arange(len(sources))].set(0)
        d, k = batched_bf_loop(
            jnp.asarray(edges.src), jnp.asarray(edges.dst),
            jnp.asarray(edges.weight).astype(dtype), d0, infj, n)
        # public contract stays [S, n]
        dist = guard.fetch(d, reason="multi-sssp: distance planes").T
        k = int(guard.fetch(k, reason="multi-sssp: superstep counter"))
    return MultiSSSPResult(dist=dist, supersteps=k,
                           connected=bool((dist < inf).all()))


@partial(jax.jit, static_argnames=("n_nodes",))
def _delta_stepping_loop(src, dst, w, d0, delta, inf, n_nodes: int):
    """Dtype-generic bucketed SSSP. ``delta`` must be in d0's dtype, and
    the caller must have picked the dtype with delta headroom
    (``sssp_dtype_for(n, wmax, delta)``) so the bucket bound
    ``(b + 1) * delta`` — which can exceed the largest distance by one
    bucket — never overflows.

    Superstep accounting: each inner light-relax iteration is one
    superstep; the per-bucket heavy pass counts ONE superstep only when the
    settled bucket actually has an admissible heavy relaxation — a bucket
    with no heavy edges costs no round on a round-driven platform, and
    counting it inflated the competitor's Table-3 rounds.
    """
    light = w < delta
    one = jnp.asarray(1, d0.dtype)
    zero = jnp.asarray(0, d0.dtype)

    def outer_cond(carry):
        d, b, k = carry
        # any unsettled node in a future bucket?
        return jnp.any((d < inf) & (d >= b * delta)) & (k < jnp.int32(2**30))

    def outer_body(carry):
        d, b, k = carry
        lo, hi = b * delta, (b + one) * delta

        def inner_cond(c):
            _, changed, _ = c
            return changed

        def inner_body(c):
            d_, _, k_ = c
            in_bucket = (d_ >= lo) & (d_ < hi)
            # light-edge relaxations from the current bucket
            ds = d_[src]
            ok = (ds < inf) & in_bucket[src] & light
            cand = jnp.where(ok, jnp.where(ok, ds, 0) + w, inf)
            dmin = jax.ops.segment_min(cand, dst, num_segments=n_nodes)
            upd = dmin < d_
            return jnp.where(upd, dmin, d_), jnp.any(upd), k_ + 1

        d, _, k = jax.lax.while_loop(inner_cond, inner_body, (d, jnp.bool_(True), k))
        # one heavy pass for the settled bucket — a superstep only if any
        # heavy relaxation is admissible from this bucket
        in_bucket = (d >= lo) & (d < hi)
        ds = d[src]
        ok = (ds < inf) & in_bucket[src] & ~light
        cand = jnp.where(ok, jnp.where(ok, ds, 0) + w, inf)
        dmin = jax.ops.segment_min(cand, dst, num_segments=n_nodes)
        d = jnp.where(dmin < d, dmin, d)
        k = k + jnp.any(ok).astype(jnp.int32)
        # jump straight to the next non-empty bucket: crawling b+1 burns a
        # full inner-loop superstep per EMPTY bucket, pathological when
        # weights are large relative to delta (road graphs)
        ahead = (d >= hi) & (d < inf)
        d_next = jnp.min(jnp.where(ahead, d, inf))
        b = jnp.where(jnp.any(ahead), d_next // delta, b + one)
        return d, b, k

    d, b, k = jax.lax.while_loop(
        outer_cond, outer_body, (d0, zero, jnp.int32(0)))
    return d, k


def delta_stepping(edges: EdgeList, source: int, delta: int) -> SSSPResult:

    n = edges.n_nodes
    wmax = int(edges.weight.max()) if edges.n_edges else 1
    dtype, inf = sssp_dtype_for(n, wmax, delta)
    with jax.enable_x64(True):
        infj = jnp.asarray(inf, dtype)
        d0 = jnp.full(n, infj, dtype=dtype).at[source].set(0)
        d, k = _delta_stepping_loop(
            *_edge_arrays(edges, dtype), d0, jnp.asarray(delta, dtype),
            infj, n,
        )
        dist = guard.fetch(d, reason="delta-stepping: distance plane")
        k = int(guard.fetch(k, reason="delta-stepping: superstep counter"))
    return SSSPResult(dist=dist, supersteps=k, inf=inf)


def diameter_2approx_sssp(edges: EdgeList, seed: int = 0) -> Tuple[int, int, int, bool]:
    """(lower_bound, upper_bound, supersteps, connected) from one
    random-source SSSP. On a disconnected input the bounds only cover the
    source's component — ``connected=False`` flags that (consistent with
    ``DiameterEstimate.connected``; the true diameter is infinite).
    An empty graph returns the degenerate (0, 0, 0, True) — the same
    ``n_nodes <= 1`` convention as ``DiameterEstimate.connected``."""
    if edges.n_nodes == 0:
        return 0, 0, 0, True
    rng = np.random.default_rng(seed)
    s = int(rng.integers(edges.n_nodes))
    res = bellman_ford(edges, s)
    reached = res.dist < res.inf
    ecc = int(res.dist[reached].max())
    return ecc, 2 * ecc, res.supersteps, bool(reached.all())


def farthest_point_lower_bound(edges: EdgeList, rounds: int = 4, seed: int = 0) -> Tuple[int, bool]:
    """Paper Table 1's Phi column: repeated SSSP hopping to the farthest
    node. Returns (lower_bound, connected); on a disconnected input the
    bound only covers components the hops visited. An empty graph returns
    the degenerate (0, True)."""
    if edges.n_nodes == 0:
        return 0, True
    rng = np.random.default_rng(seed)
    s = int(rng.integers(edges.n_nodes))
    best = 0
    connected = True
    for _ in range(rounds):
        res = bellman_ford(edges, s)
        connected = connected and bool((res.dist < res.inf).all())
        dist = np.where(res.dist < res.inf, res.dist, -1)
        far = int(dist.argmax())
        best = max(best, int(dist.max()))
        if far == s:
            break
        s = far
    return best, connected

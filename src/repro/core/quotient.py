"""Quotient graph construction and local diameter solve (paper Section 4).

Nodes of G_C are clusters; for each original edge (u, v) with c_u != c_v the
quotient edge weight is w(u,v) + dist(c_u, u) + dist(c_v, v) (we use the
engine's realized path weights, which upper-bound the dists, keeping the
estimate conservative). Parallel edges keep the minimum.

The paper picks tau so the quotient fits in one reducer's local memory and is
solved locally in O(1) rounds. We mirror that fully on device:

  * ``_quotient_kernel`` — one jitted segment-ops pass over the backend's
    device edge arrays (cross-edge detection, key sort, (cluster, cluster)
    coalescing via the engine's lexicographic tuple-min from
    ``graph/segment_ops.py``). No host round-trip; composes with
    SingleDevice/Sharded/Pallas through ``backend.quotient_args()``.
  * ``_solve_kernel`` — batched multi-source SSSP (``sssp.batched_bf_loop``
    vmapped over all quotient sources), int64-safe (traced under
    ``jax.enable_x64(True)``), returning
    (diameter, eccentricities, connected) in ONE packed fetch.

scipy APSP (``quotient_diameter``) is kept as the test oracle only; the
jnp min-plus fallback is int64-safe and shares the (diameter, connected)
contract.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import guard
from repro.common import next_multiple
from repro.core.cluster import Decomposition
from repro.graph.segment_ops import segment_min_triple
from repro.graph.structures import MAX_WEIGHT, EdgeList, weight_scale_for

# Unreached sentinel for the int64 solve. Guarded adds keep everything
# strictly below 2 * INF64 < 2^63, so int64 arithmetic never overflows.
INF64 = np.int64(2**62)
# the dynamic merge pads the cached quotient's edge count to a multiple of
# 8x this, so its program re-compiles only per size bucket
K_BUCKET = 16
# the solve pads k and m up to one of this many sizes per power of two: the
# cluster and quotient-edge counts follow each query's random centers, and
# every (k_pad, m_pad) is a program the device holds (about 0.6 MB of HBM
# each on a v5e), so the buckets must be coarse enough for seeds to share
# them
SOLVE_BUCKETS_PER_OCTAVE = 4
# cascade levels pad the quotient edge arrays to a multiple of this so the
# per-level engine programs recompile only per size bucket
LEVEL_EDGE_BUCKET = 256


@dataclass
class QuotientGraph:
    n_clusters: int
    center_ids: np.ndarray  # original node id of each quotient node
    src: np.ndarray
    dst: np.ndarray
    weight: np.ndarray  # int64 (sums of three int32 terms)


class DeviceQuotient(NamedTuple):
    """Device-resident quotient: fixed [E]-length arrays + scalar counters.

    Edges are sorted by (cluster, cluster) key with exactly the first
    ``n_edges`` slots valid; invalid slots carry weight INF64 and sentinel
    endpoints, so slicing to any padded length >= n_edges stays sound.
    """

    centers: jnp.ndarray     # int32 [n], first n_clusters slots valid
    src: jnp.ndarray         # int32 [E] compact cluster labels
    dst: jnp.ndarray         # int32 [E]
    weight: jnp.ndarray      # int64 [E], INF64 on invalid slots
    n_clusters: jnp.ndarray  # int32 scalar (on device)
    n_edges: jnp.ndarray     # int32 scalar (on device)
    max_weight: jnp.ndarray  # int64 scalar — lets the solve pick an int32
                             # fast path when k_pad * max_weight < 2^31
    weight_sum: jnp.ndarray  # int64 scalar, sum of coalesced quotient
                             # weights — the cascade derives Delta_init and
                             # max_delta for the next level from it without
                             # an extra fetch


def build_quotient_numpy(edges: EdgeList, dec: Decomposition) -> QuotientGraph:
    """Host numpy reference (the parity oracle for the jitted pass)."""
    centers, inverse = np.unique(dec.final_c, return_inverse=True)
    k = len(centers)
    cu = inverse[edges.src]
    cv = inverse[edges.dst]
    cross = cu != cv
    cu, cv = cu[cross], cv[cross]
    wq = (
        edges.weight[cross].astype(np.int64)
        + dec.final_pathw[edges.src[cross]].astype(np.int64)
        + dec.final_pathw[edges.dst[cross]].astype(np.int64)
    )
    # min-coalesce parallel quotient edges
    key = cu.astype(np.int64) * k + cv.astype(np.int64)
    order = np.lexsort((wq, key))
    key_s = key[order]
    first = np.ones(len(key_s), dtype=bool)
    if len(key_s):
        first[1:] = key_s[1:] != key_s[:-1]
    idx = order[first]
    return QuotientGraph(
        n_clusters=k,
        center_ids=centers,
        src=cu[idx].astype(np.int32),
        dst=cv[idx].astype(np.int32),
        weight=wq[idx],
    )


@partial(jax.jit, static_argnames=("n",))
def _quotient_kernel(src, dst, w, mask, final_c, final_pathw, *, n: int):
    """One segment-ops pass: cross-edge detect -> key sort -> coalesce.

    ``src``/``dst`` may contain phantom ids >= n (Pallas/sharded padding);
    ``mask`` marks real edges. Traced under jax.enable_x64, so the quotient
    weight (a sum of three int32 terms) is exact int64.
    """
    E = src.shape[0]
    centers, inverse = jnp.unique(
        final_c, size=n, fill_value=jnp.int32(n), return_inverse=True)
    k = jnp.sum(centers < n).astype(jnp.int32)
    valid = mask.astype(bool) & (src >= 0) & (src < n) & (dst >= 0) & (dst < n)
    su = jnp.clip(src, 0, n - 1)
    sv = jnp.clip(dst, 0, n - 1)
    cu = inverse[su].astype(jnp.int32)
    cv = inverse[sv].astype(jnp.int32)
    cross = valid & (cu != cv)
    wq = (w.astype(jnp.int64)
          + final_pathw[su].astype(jnp.int64)
          + final_pathw[sv].astype(jnp.int64))
    wq = jnp.where(cross, wq, jnp.int64(INF64))
    key_inf = jnp.int64(INF64)
    key = jnp.where(
        cross, cu.astype(jnp.int64) * (n + 1) + cv.astype(jnp.int64), key_inf)
    # grouping by key is all the coalesce needs (the tuple-min below takes
    # each group's lightest edge); a one-key sort also compiles faster
    # for the TPU than a two-key one
    order = jnp.argsort(key)
    key_s, wq_s = key[order], wq[order]
    cu_s, cv_s = cu[order], cv[order]
    valid_s = key_s < key_inf
    first = valid_s & jnp.concatenate(
        [jnp.ones((1,), bool), key_s[1:] != key_s[:-1]])
    seg = jnp.clip(jnp.cumsum(first) - 1, 0, max(E - 1, 0)).astype(jnp.int32)
    # coalesce parallel (cluster, cluster) edges with the engine's
    # lexicographic tuple-min (within a segment cu/cv are constant, so the
    # tie-break passes just carry the endpoints through)
    q_w, q_src, q_dst = segment_min_triple(
        jnp.where(valid_s, wq_s, jnp.int64(INF64)),
        jnp.where(valid_s, cu_s, jnp.int32(n)),
        jnp.where(valid_s, cv_s, jnp.int32(n)),
        seg, num_segments=max(E, 1),
    )
    n_q = jnp.sum(first).astype(jnp.int32)
    q_w = q_w[:E]
    return DeviceQuotient(
        centers=centers.astype(jnp.int32),
        src=q_src[:E], dst=q_dst[:E], weight=q_w,
        n_clusters=k, n_edges=n_q,
        max_weight=jnp.max(jnp.where(cross, wq, jnp.int64(0))),
        weight_sum=jnp.sum(jnp.where(q_w < key_inf, q_w, jnp.int64(0))),
    )


def fetch_quotient_counters(dq: DeviceQuotient) -> Tuple[int, int, int, int]:
    """ONE packed host fetch of the four device counters:
    ``(n_clusters, n_edges, max_weight, weight_sum)``. Callers account the
    sync (``PipelineMetrics.quotient_syncs``) themselves."""
    with jax.enable_x64(True):
        kmws = guard.fetch(jnp.stack([
            dq.n_clusters.astype(jnp.int64), dq.n_edges.astype(jnp.int64),
            dq.max_weight, dq.weight_sum]),
            reason="quotient: packed (k, m, wmax, wsum) counters")
    return int(kmws[0]), int(kmws[1]), int(kmws[2]), int(kmws[3])


def _flat_quotient_args(edges: EdgeList):
    """Fallback device edge arrays when the backend doesn't expose its own."""
    return (jnp.asarray(edges.src), jnp.asarray(edges.dst),
            jnp.asarray(edges.weight),
            jnp.ones((edges.n_edges,), dtype=bool))


def _decomposition_planes(dec: Decomposition, n: int):
    fc = dec.final_c_dev if dec.final_c_dev is not None else jnp.asarray(dec.final_c)
    fp = (dec.final_pathw_dev if dec.final_pathw_dev is not None
          else jnp.asarray(dec.final_pathw))
    return fc[:n], fp[:n]


def build_quotient_device(
    edges: EdgeList,
    dec: Decomposition,
    backend=None,
) -> Optional[DeviceQuotient]:
    """Run the jitted quotient pass on the backend's device edge arrays.

    Returns None for graphs with no nodes or no edges (host shortcut — the
    quotient is trivially empty). Zero host syncs: the counters stay on
    device until the caller fetches them.
    """
    n = edges.n_nodes
    if n == 0 or edges.n_edges == 0:
        return None
    if backend is not None and hasattr(backend, "quotient_args"):
        src, dst, w, mask = backend.quotient_args()
    else:
        src, dst, w, mask = _flat_quotient_args(edges)
    fc, fp = _decomposition_planes(dec, n)
    with jax.enable_x64(True):
        return _quotient_kernel(src, dst, w, mask, fc, fp, n=n)


def build_quotient(edges: EdgeList, dec: Decomposition, backend=None) -> QuotientGraph:
    """Device-backed quotient construction, materialized to the host
    ``QuotientGraph`` (same edge order and dtypes as the numpy oracle —
    edge-for-edge comparable). The fused pipeline in ``core/diameter.py``
    skips this materialization and feeds ``DeviceQuotient`` straight into
    the solve."""
    dq = build_quotient_device(edges, dec, backend=backend)
    if dq is None:
        centers = (np.unique(dec.final_c) if edges.n_nodes
                   else np.array([], np.int32))
        z = np.array([], np.int32)
        return QuotientGraph(
            n_clusters=len(centers), center_ids=centers.astype(np.int32),
            src=z, dst=z, weight=z.astype(np.int64))
    k, m = map(int, guard.fetch(jnp.stack([dq.n_clusters, dq.n_edges]),
                                reason="host quotient: (k, m) counters"))
    with jax.enable_x64(True):  # int64 arrays must be sliced with x64 tracing on
        return QuotientGraph(
            n_clusters=k,
            center_ids=np.asarray(dq.centers[:k]),
            src=np.asarray(dq.src[:m]),
            dst=np.asarray(dq.dst[:m]),
            weight=np.asarray(dq.weight[:m]),
        )


# ---------------------------------------------------------------------------
# cascade levels: re-enter the engine on the quotient itself
# ---------------------------------------------------------------------------


class QuotientLevel(NamedTuple):
    """A ``DeviceQuotient`` re-expressed in the engine's edge layout: flat
    int32 device arrays over ``n_nodes = k`` compact cluster labels, padding
    slots rewritten as inert self-loops (0 -> 0, w = 1).

    Quotient weights are int64 sums while the engine's ``EngineState``
    planes are int32, so weights are rescaled by ``scale`` (ceiling
    division — conservative: ``scale * dist_rescaled >= dist_true`` for
    every pair, so upper bounds survive the cascade). ``scale`` is 1
    whenever the level already fits int32.
    """

    n_nodes: int          # k (host)
    n_edges: int          # real quotient edge count m (host)
    src: jnp.ndarray      # int32 [e_pad]
    dst: jnp.ndarray      # int32 [e_pad]
    weight: jnp.ndarray   # int32 [e_pad], ceil(w / scale); 1 on padding
    scale: int            # original units = scale * level units
    weight_sum: int       # upper bound on sum(weight) in LEVEL units

    def to_edgelist(self) -> EdgeList:
        """Host materialization (tests / oracles): the first ``n_edges``
        slots are exactly the coalesced quotient edges."""
        m = self.n_edges
        with jax.enable_x64(True):
            return EdgeList(
                self.n_nodes,
                np.asarray(self.src[:m]), np.asarray(self.dst[:m]),
                np.asarray(self.weight[:m]))


@jax.jit
def _level_edges_kernel(src, dst, w, scale):
    """Rewrite sliced DeviceQuotient buffers as engine-ready edges: valid
    slots keep their endpoints with ceil-rescaled int32 weight, invalid
    slots (weight >= INF64, incl. the empty-segment int64-max fill) become
    inert self-loops. Traced under jax.enable_x64 (w is int64)."""
    valid = w < jnp.int64(INF64)
    w32 = jnp.where(valid, (w + scale - 1) // scale, jnp.int64(1))
    w32 = jnp.clip(w32, 1, jnp.int64(int(MAX_WEIGHT))).astype(jnp.int32)
    s = jnp.where(valid, src, jnp.int32(0))
    t = jnp.where(valid, dst, jnp.int32(0))
    return s, t, w32


def quotient_as_edgelist(
    dq: DeviceQuotient, k: int, m: int, max_weight: int, weight_sum: int = 0,
    *, edge_bucket: int = LEVEL_EDGE_BUCKET,
) -> QuotientLevel:
    """Adapter: ``DeviceQuotient`` buffers -> the engine's edge layout,
    entirely on device (no host round-trip — the (k, m, max_weight,
    weight_sum) counters must already be fetched).

    Edge arrays are sliced to an ``edge_bucket`` multiple so same-scale
    levels share one compiled stage program. ``weight_sum`` (level units)
    uses the ceil-sum bound ``sum(ceil(w/s)) <= sum(w)/s + m``.
    """
    scale = weight_scale_for(max_weight)
    E = dq.src.shape[0]
    e_pad = min(next_multiple(max(m, 1), edge_bucket), max(E, 1))
    with jax.enable_x64(True):
        src, dst, w32 = _level_edges_kernel(
            dq.src[:e_pad], dq.dst[:e_pad], dq.weight[:e_pad],
            jnp.int64(scale))
    ws = int(weight_sum) // scale + m
    return QuotientLevel(n_nodes=k, n_edges=m, src=src, dst=dst, weight=w32,
                         scale=scale, weight_sum=ws)


def build_quotient_from_level(level: QuotientLevel, dec: Decomposition
                              ) -> DeviceQuotient:
    """One more cascade level: the jitted quotient pass over a level's
    device edge arrays and its decomposition's device planes. Padding
    self-loops are never cross edges, so no mask is needed beyond ones."""
    fc, fp = _decomposition_planes(dec, level.n_nodes)
    mask = jnp.ones(level.src.shape, dtype=bool)
    with jax.enable_x64(True):
        return _quotient_kernel(level.src, level.dst, level.weight, mask,
                                fc, fp, n=level.n_nodes)


# ---------------------------------------------------------------------------
# incremental refresh: recompute only the keys touching dirty clusters
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n",))
def _merge_quotient_kernel(cs, cd, cw, fs, fd, fw, dirty_compact, *, n: int):
    """Merge a cached quotient's CLEAN entries with freshly recomputed
    dirty-side entries.

    ``dirty_compact`` is a bool [n] mask over compact cluster labels. Every
    cached entry touching a dirty cluster is dropped (its contributing
    edges, endpoint assignments, or path-weight certificates may have
    changed); the fresh entries — produced by ``_quotient_kernel`` over
    exactly the dirty-incident edge slice — cover all such pairs, so the
    two sets are DISJOINT by construction and a key sort (no re-coalesce)
    restores the ``DeviceQuotient`` sorted-key invariant. Traced under
    jax.enable_x64 (weights are int64).
    """
    drop = (dirty_compact[jnp.clip(cs, 0, n - 1)]
            | dirty_compact[jnp.clip(cd, 0, n - 1)])
    keep = (cw < jnp.int64(INF64)) & ~drop
    src = jnp.concatenate([jnp.where(keep, cs, jnp.int32(n)), fs])
    dst = jnp.concatenate([jnp.where(keep, cd, jnp.int32(n)), fd])
    w = jnp.concatenate([jnp.where(keep, cw, jnp.int64(INF64)), fw])
    valid = w < jnp.int64(INF64)
    key = jnp.where(
        valid, src.astype(jnp.int64) * (n + 1) + dst.astype(jnp.int64),
        jnp.int64(INF64))
    order = jnp.argsort(key)
    src, dst, w = src[order], dst[order], w[order]
    valid = w < jnp.int64(INF64)
    return (src, dst, w,
            jnp.sum(valid).astype(jnp.int32),
            jnp.max(jnp.where(valid, w, jnp.int64(0))),
            jnp.sum(jnp.where(valid, w, jnp.int64(0))))


def quotient_update_device(
    cached: DeviceQuotient,
    m_cached: int,
    dirty_edge_args: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray],
    final_c_dev: jnp.ndarray,
    final_pathw_dev: jnp.ndarray,
    dirty_center_ids: np.ndarray,
    n: int,
) -> DeviceQuotient:
    """Incremental quotient refresh (the dynamic-update fast path).

    Only the (cluster, cluster) keys touching a dirty cluster are
    recomputed: ``dirty_edge_args`` is the (small, padded) device slice of
    edges with a dirty-cluster endpoint, run through the SAME
    ``_quotient_kernel`` as a full build; the cached quotient contributes
    every clean-clean pair unchanged. ONLY sound when the cluster (center)
    set is identical to the cached build's — the compact label spaces must
    agree — which the caller guarantees (a changed cluster set forces a
    full rebuild of the quotient).
    """
    sub_src, sub_dst, sub_w, sub_mask = dirty_edge_args
    with jax.enable_x64(True):
        fresh = _quotient_kernel(sub_src, sub_dst, sub_w, sub_mask,
                                 final_c_dev, final_pathw_dev, n=n)
        dirty_node = np.zeros(n + 1, bool)
        dirty_node[np.asarray(dirty_center_ids, np.int64)] = True
        # compact-label dirty mask: centers[i] is the i-th cluster's center
        dirty_compact = jnp.asarray(dirty_node)[cached.centers]
        m_pad = min(next_multiple(max(m_cached, 1), K_BUCKET * 8),
                    int(cached.src.shape[0]))
        src, dst, w, n_q, wmax, wsum = _merge_quotient_kernel(
            cached.src[:m_pad], cached.dst[:m_pad], cached.weight[:m_pad],
            fresh.src, fresh.dst, fresh.weight, dirty_compact, n=n)
        return DeviceQuotient(
            centers=cached.centers, src=src, dst=dst, weight=w,
            n_clusters=cached.n_clusters, n_edges=n_q,
            max_weight=wmax, weight_sum=wsum,
        )


# ---------------------------------------------------------------------------
# quotient solve: batched multi-source SSSP on device
# ---------------------------------------------------------------------------


def solve_bucket(x: int) -> int:
    """``x`` rounded up to one of ``SOLVE_BUCKETS_PER_OCTAVE`` sizes per
    power of two: at most 1 / SOLVE_BUCKETS_PER_OCTAVE of padding."""
    step = max(1, (1 << (max(x, 1).bit_length() - 1))
               // SOLVE_BUCKETS_PER_OCTAVE)
    return next_multiple(max(x, 1), step)


@partial(jax.jit, static_argnames=("k_pad", "m_pad", "int32"))
def _solve_kernel(qsrc, qdst, qw, k, *, k_pad: int, m_pad: int, int32: bool):
    """Exact APSP on the quotient via Bellman-Ford from ALL k_pad sources at
    once (``sssp.batched_bf_loop``, distances laid out [node, source]) over
    the first ``m_pad`` edge slots, sliced here so that one program serves
    a (k_pad, m_pad) bucket. ``int32``: the caller proved every shortest
    path fits, so the loop runs in int32 (padding slots' INF64 weights map
    to the int32 INF); int64 otherwise. Edges are directed (callers pass
    both directions of the symmetrized graph). Returns one packed int64
    vector: [diameter, connected, supersteps, ecc[0..k_pad)].
    """
    from repro.core.sssp import batched_bf_loop

    qsrc, qdst, qw = qsrc[:m_pad], qdst[:m_pad], qw[:m_pad]
    if int32:
        qw = jnp.where(qw >= jnp.int64(INF64),
                       jnp.int64(2**31 - 1), qw).astype(jnp.int32)
    inf = jnp.asarray(
        2**62 if qw.dtype == jnp.int64 else 2**31 - 1, qw.dtype)
    s = jnp.clip(qsrc, 0, k_pad - 1).astype(jnp.int32)
    t = jnp.clip(qdst, 0, k_pad - 1).astype(jnp.int32)
    eye = jnp.eye(k_pad, dtype=bool)
    d0 = jnp.where(eye, jnp.asarray(0, qw.dtype), inf)
    d, steps = batched_bf_loop(s, t, qw, d0, inf, k_pad)
    node_ok = jnp.arange(k_pad) < k
    pair_ok = node_ok[:, None] & node_ok[None, :]
    finite = pair_ok & (d < inf)
    connected = jnp.sum(finite) == k.astype(jnp.int64) * k.astype(jnp.int64)
    d_fin = jnp.where(finite, d, jnp.asarray(0, qw.dtype)).astype(jnp.int64)
    ecc = jnp.max(d_fin, axis=0)  # [node, source]: reduce over nodes
    diam = jnp.max(d_fin)
    head = jnp.stack([diam, connected.astype(jnp.int64),
                      steps.astype(jnp.int64)])
    return jnp.concatenate([head, ecc])


def solve_device_quotient(
    dq: DeviceQuotient, k: int, m: int, max_weight: int = 0,
) -> Tuple[int, np.ndarray, bool, int]:
    """(diameter, eccentricities, connected, supersteps) from a device
    quotient whose (n_clusters, n_edges, max_weight) counters have been
    fetched. Pads k and m to size buckets (``solve_bucket``) so queries and
    same-scale graphs share one compiled solve, then fetches the packed
    result — ONE host sync.

    When ``k_pad * max_weight < 2^31 - 1`` the solve runs in int32 (every
    shortest path has < k edges, so distances and guarded adds provably
    fit) — about 2x the CPU throughput of the exact-by-construction int64
    path used otherwise.
    """
    if k <= 1:
        return 0, np.zeros(k, np.int64), True, 0
    k_pad = solve_bucket(k)
    m_pad = min(solve_bucket(m), dq.src.shape[0])
    int32_safe = k_pad * max(int(max_weight), 1) < 2**31 - 1
    with jax.enable_x64(True):
        out = guard.fetch(_solve_kernel(
            dq.src, dq.dst, dq.weight, jnp.int32(k),
            k_pad=k_pad, m_pad=m_pad, int32=int32_safe),
            reason="quotient solve: packed (diam, connected, steps, ecc)")
    return int(out[0]), out[3:3 + k], bool(out[1]), int(out[2])


def quotient_diameter_device(q: QuotientGraph) -> Tuple[int, np.ndarray, bool]:
    """Device solve over a host ``QuotientGraph``: symmetrizes (matching the
    scipy oracle's ``directed=False``) and runs the batched multi-source
    SSSP. Exact for int64 weights (the acceptance bar: weights up to 2^40
    match scipy bit-for-bit). Returns (diameter, eccentricities, connected).
    """
    k = q.n_clusters
    if k <= 1:
        return 0, np.zeros(k, np.int64), True
    src = np.concatenate([q.src, q.dst]).astype(np.int32)
    dst = np.concatenate([q.dst, q.src]).astype(np.int32)
    w = np.concatenate([q.weight, q.weight]).astype(np.int64)
    wmax = int(w.max()) if len(w) else 0
    with jax.enable_x64(True):
        dq = DeviceQuotient(
            centers=jnp.asarray(q.center_ids.astype(np.int32)),
            src=jnp.asarray(src), dst=jnp.asarray(dst), weight=jnp.asarray(w),
            n_clusters=jnp.int32(k), n_edges=jnp.int32(len(src)),
            max_weight=jnp.int64(wmax),
            weight_sum=jnp.int64(int(w.sum()) if len(w) else 0),
        )
    diam, ecc, connected, _ = solve_device_quotient(dq, k, len(src), wmax)
    return diam, ecc, connected


# ---------------------------------------------------------------------------
# host oracles (tests only)
# ---------------------------------------------------------------------------


def quotient_diameter(q: QuotientGraph) -> Tuple[int, bool]:
    """Exact weighted diameter of the quotient — the scipy TEST ORACLE for
    the device solve. Returns (diameter, connected)."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import shortest_path

    if q.n_clusters <= 1:
        return 0, True
    m = sp.csr_matrix(
        (q.weight.astype(np.float64), (q.src, q.dst)),
        shape=(q.n_clusters, q.n_clusters),
    )
    dist = shortest_path(m, method="D", directed=False)
    finite = np.isfinite(dist)
    connected = bool(finite.all())
    diam = float(dist[finite].max()) if finite.any() else 0.0
    return int(diam), connected


def quotient_diameter_minplus(q: QuotientGraph) -> Tuple[int, bool]:
    """jnp min-plus matrix-squaring fallback (cross-checks scipy in tests
    and serves as the device-local path when scipy is unavailable).

    int64-safe: the squaring runs under jax.enable_x64 with guarded adds, so
    weights above 2^24 (which float32 silently rounds) stay exact. Shares
    the (diameter, connected) contract with ``quotient_diameter`` — a
    disconnected quotient is flagged instead of reporting a finite max.
    """
    k = q.n_clusters
    if k <= 1:
        return 0, True
    big = np.int64(INF64)
    m = np.full((k, k), big, dtype=np.int64)
    np.minimum.at(m, (q.src, q.dst), q.weight.astype(np.int64))
    np.minimum.at(m, (q.dst, q.src), q.weight.astype(np.int64))
    np.fill_diagonal(m, 0)

    with jax.enable_x64(True):
        d = jnp.asarray(m)
        steps = int(np.ceil(np.log2(max(k - 1, 1)))) or 1
        for _ in range(steps):
            d = _minplus_square(d)
    arr = guard.fetch(d, reason="minplus oracle: squared distance matrix")
    finite = arr < big
    connected = bool(finite.all())
    return int(arr[finite].max()), connected


@jax.jit
def _minplus_square(d):
    """One guarded int64 min-plus squaring step (d must carry INF64 for
    unreachable pairs; the guard keeps INF64 + INF64 from overflowing)."""
    big = jnp.int64(INF64)
    a = d[:, :, None]
    b = d[None, :, :]
    ok = (a < big) & (b < big)
    cand = jnp.where(ok, jnp.where(ok, a, 0) + jnp.where(ok, b, 0), big)
    return jnp.min(cand, axis=1)

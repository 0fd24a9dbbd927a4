"""Distributed Δ-growing engine: the paper's MR rounds as shard_map supersteps.

The MR(M_T, M_L) round of the paper maps onto one TPU-pod superstep:

  paper round (shuffle + reduce-by-key)  ==  one shard_map superstep:
    1. each device owns a contiguous node range (states d/c/pathw + frozen
       relay fields) and the destination-sorted edges whose *destination*
       falls in that range (so the tuple-min reduce-by-key is device-local);
    2. source states are fetched across devices — either a full all-gather
       of the node-state planes (baseline) or a static halo exchange via
       all_to_all (optimized; the edge list is static, so each device pair's
       needed ids are known ahead of time);
    3. the Bellman-Ford relax + lexicographic (d, c) tuple-min runs locally
       (jnp segment ops or the Pallas edge_relax kernel on TPU).

  The while_loop trip count of supersteps is exactly the quantity the paper
  proves small (O(min{n/τ, ℓ_R} log n)) — each trip costs one collective, as
  each MR round costs one shuffle.

Node ids are padded to a multiple of the device count; the phantom tail is
pinned at INF/covered=False and never wins a min. Partitioning is pluggable:
``range`` (contiguous) or ``cluster`` (locality-aware, derived from the
paper's own decomposition — see graph/partition.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.common import ceil_div, get_logger, next_multiple
from repro.core.state import EngineState, INF
from repro.graph.segment_ops import segment_min_triple
from repro.graph.structures import EdgeList
from repro.kernels.edge_relax.ref import edge_relax_candidates

log = get_logger("repro.distributed")


@dataclass
class ShardedGraph:
    """Edges partitioned by destination owner, padded per device.

    Per-device edge slots are padded with the phantom edge (src=dst=n_pad-1,
    w=INF-guarded) which never relaxes anything.
    """

    n_nodes: int                 # real node count
    n_pad: int                   # padded (multiple of n_devices)
    n_devices: int
    src: jnp.ndarray             # int32 [P, E_loc] global source ids
    dst_local: jnp.ndarray       # int32 [P, E_loc] destination ids local to owner
    weight: jnp.ndarray          # int32 [P, E_loc]
    edge_mask: jnp.ndarray       # bool  [P, E_loc]
    # halo exchange plan (comm="halo"): for device pair (q -> p), q != p,
    # send_ids[q, p, :] are q-local node indices whose states p needs.
    # Device-local sources are read straight from the local plane (no wire).
    send_ids: Optional[jnp.ndarray] = None   # int32 [P, P, K] q-local ids
    recv_slot: Optional[jnp.ndarray] = None  # int32 [P, E_loc] slot into the
                                             # received halo table [P*K]
    src_is_local: Optional[jnp.ndarray] = None  # bool [P, E_loc]
    src_local_idx: Optional[jnp.ndarray] = None # int32 [P, E_loc]
    halo_k: int = 0

    @property
    def nodes_per_device(self) -> int:
        return self.n_pad // self.n_devices


def shard_graph(
    edges: EdgeList,
    n_devices: int,
    build_halo: bool = True,
) -> ShardedGraph:
    """Partition destination-sorted edges by destination owner (host side)."""
    n = edges.n_nodes
    n_pad = next_multiple(n, n_devices)
    q = n_pad // n_devices

    e = edges.sorted_by_dst()
    owner = e.dst // q
    counts = np.bincount(owner, minlength=n_devices)
    e_loc = max(int(counts.max()), 1)

    src = np.full((n_devices, e_loc), n_pad - 1, dtype=np.int32)
    dstl = np.full((n_devices, e_loc), q - 1, dtype=np.int32)
    w = np.ones((n_devices, e_loc), dtype=np.int32)
    mask = np.zeros((n_devices, e_loc), dtype=bool)

    starts = np.concatenate([[0], np.cumsum(counts)])
    for p in range(n_devices):
        s, t = int(starts[p]), int(starts[p + 1])
        c = t - s
        if c == 0:
            continue
        src[p, :c] = e.src[s:t]
        dstl[p, :c] = e.dst[s:t] - p * q
        w[p, :c] = e.weight[s:t]
        mask[p, :c] = True

    g = ShardedGraph(
        n_nodes=n, n_pad=n_pad, n_devices=n_devices,
        src=jnp.asarray(src), dst_local=jnp.asarray(dstl),
        weight=jnp.asarray(w), edge_mask=jnp.asarray(mask),
    )
    if build_halo:
        _attach_halo_plan(g, src, mask, q)
    return g


def _attach_halo_plan(g: ShardedGraph, src: np.ndarray, mask: np.ndarray, q: int) -> None:
    """Static halo exchange plan. For each dst-owner p, the set of REMOTE
    sources it reads is fixed; build [P, P, K] send tables + per-edge slots.
    Local sources (owner == p) bypass the exchange entirely."""
    n_dev = g.n_devices
    uniq_per_pair = [[np.empty(0, np.int64)] * n_dev for _ in range(n_dev)]
    k_max = 1
    for p in range(n_dev):
        srcs = src[p][mask[p]]
        owners = srcs // q
        for o in range(n_dev):
            if o == p:
                continue  # local reads don't travel
            u = np.unique(srcs[owners == o])
            uniq_per_pair[o][p] = u  # device o sends these (global ids) to p
            k_max = max(k_max, len(u))
    send = np.zeros((n_dev, n_dev, k_max), dtype=np.int32)
    for o in range(n_dev):
        for p in range(n_dev):
            u = uniq_per_pair[o][p]
            if len(u):
                send[o, p, : len(u)] = u - o * q  # o-local indices
    recv_slot = np.zeros_like(src)
    is_local = np.zeros(src.shape, dtype=bool)
    local_idx = np.zeros_like(src)
    for p in range(n_dev):
        lookup = {}
        for o in range(n_dev):
            for j, gid in enumerate(uniq_per_pair[o][p]):
                lookup[int(gid)] = o * k_max + j
        owners = src[p] // q
        is_local[p] = (owners == p) & mask[p]
        local_idx[p] = np.where(is_local[p], src[p] - p * q, 0)
        recv_slot[p] = np.array(
            [lookup.get(int(s), 0) if (mm and not loc) else 0
             for s, mm, loc in zip(src[p], mask[p], is_local[p])],
            dtype=np.int32,
        )
    g.send_ids = jnp.asarray(send)
    g.recv_slot = jnp.asarray(recv_slot)
    g.src_is_local = jnp.asarray(is_local)
    g.src_local_idx = jnp.asarray(local_idx)
    g.halo_k = k_max


# ---------------------------------------------------------------------------
# The superstep
# ---------------------------------------------------------------------------

# node-state planes carried through the distributed loop (per-device shards):
#   d, c, pathw          in-stage wave
#   relay_w0             covered relay base: offset (d_cover - Delta) else INF
#   relay_c, relay_p     covered relay center / path weight
#   frozen               covered | is_center (never receives updates)
# The planes are derived ONCE per grow call from the canonical EngineState by
# ``core.state.relay_planes`` (see core/backend.ShardedBackend) — not packed
# and re-padded per call as in the seed engine.


def _relax_local(src_d, src_c, src_p, src_rw0, src_rc, src_rp,
                 w, dst_local, edge_mask, delta, q,
                 d, c, pw, frozen):
    """Device-local relax + lexicographic tuple-min (the reduce-by-key).

    Candidate rule and tuple-min are the shared canonical implementations
    (``kernels/edge_relax/ref.py`` + ``graph/segment_ops.py``) — the same
    code every other backend runs, which is what makes the backends
    byte-identical."""
    cand_d, cand_c, cand_p = edge_relax_candidates(
        src_d, src_c, src_p, src_rw0, src_rc, src_rp, w, edge_mask, delta)
    d_min, c_min, p_min = segment_min_triple(cand_d, cand_c, cand_p,
                                             dst_local, q)
    upd = (~frozen) & (d_min < d)
    return (
        jnp.where(upd, d_min, d),
        jnp.where(upd, c_min, c),
        jnp.where(upd, p_min, pw),
        jnp.any(upd),
    )


class DistributedEngine:
    """shard_map executor for Δ-growing supersteps on a device mesh.

    ``comm``: "halo" (default) exchanges only the statically-needed boundary
    states via all_to_all (bytes = 6·4·P·P·K per superstep, typically ≪ n
    with locality-aware partitions). "allgather" broadcasts the six source
    planes each superstep (baseline; collective bytes = 6·4·n_pad·P).
    Both produce byte-identical planes; comm is a pure traffic knob.

    ``graph``: optionally a prebuilt ``ShardedGraph`` (e.g. from
    ``GraphStore.sharded_graph()``) so the relabel/shard work isn't repeated;
    it is validated against the mesh and rebuilt from ``edges`` on mismatch.
    """

    def __init__(
        self,
        edges: EdgeList,
        mesh: Mesh,
        comm: str = "halo",
        axis_names: Optional[Tuple[str, ...]] = None,
        graph: Optional[ShardedGraph] = None,
    ):
        # the planes leave the shard_map bodies into plain jnp code (the
        # engine's stage logic, the quotient pass), which relies on
        # propagated shardings: re-type the axes Auto, whatever the caller's
        # mesh says (jax.make_mesh makes them Explicit)
        self.mesh = Mesh(mesh.devices, mesh.axis_names,
                         axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        self.axes = tuple(axis_names or mesh.axis_names)
        self.n_devices = int(np.prod([mesh.shape[a] for a in self.axes]))
        self.comm = comm
        if graph is not None and graph.n_devices != self.n_devices:
            log.warning(
                "prebuilt ShardedGraph has %d shards but mesh has %d devices; "
                "resharding from edges", graph.n_devices, self.n_devices,
            )
            graph = None
        if graph is not None and comm == "halo" and graph.send_ids is None:
            graph = None  # prebuilt without a halo plan; rebuild with one
        self.graph = graph if graph is not None else shard_graph(
            edges, self.n_devices, build_halo=(comm == "halo"))
        self.q = self.graph.nodes_per_device
        self._step = self._build_superstep()
        self._growth = self._build_growth_loop()
        # device-place the static edge shards once per engine, not per call
        self.gparts = self.device_put_graph()

    # -- sharding helpers ---------------------------------------------------
    def node_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axes))

    def edge_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axes, None))

    def device_put_graph(self):
        es = self.edge_sharding()
        g = self.graph
        out = [jax.device_put(x, es) for x in (g.src, g.dst_local, g.weight, g.edge_mask)]
        if self.comm == "halo":
            out.append(jax.device_put(g.send_ids, NamedSharding(self.mesh, P(self.axes, None, None))))
            out.append(jax.device_put(g.recv_slot, es))
            out.append(jax.device_put(g.src_is_local, es))
            out.append(jax.device_put(g.src_local_idx, es))
        return tuple(out)

    # -- communication accounting (bytes per superstep, whole mesh) ---------
    def comm_bytes_per_superstep(self) -> int:
        """Bytes moved across the mesh by one superstep's source-plane
        exchange (6 int32 planes per node row = 24 B/row)."""
        if self.n_devices <= 1:
            return 0
        if self.comm == "halo":
            # the all_to_all ships a fixed [P, K] table per device (the
            # self-row is allocated on the wire plan even though it stays
            # local), so the conservative count is P·P·K rows mesh-wide.
            return 24 * self.n_devices * self.n_devices * self.graph.halo_k
        return self.fullplane_bytes_per_superstep()

    def fullplane_bytes_per_superstep(self) -> int:
        """Bytes one full-plane all-gather of the six planes would move."""
        if self.n_devices <= 1:
            return 0
        return 24 * self.graph.n_pad * self.n_devices

    # -- superstep bodies (run inside shard_map; arrays are per-device) -----
    def _gather_src_planes(self, planes_local, src, recv_slot, send_ids,
                           is_local=None, local_idx=None):
        axis = self.axes
        if self.comm == "allgather":
            full = [jax.lax.all_gather(x, axis, tiled=True) for x in planes_local]
            return [f[src] for f in full]
        # halo: q sends states of send_ids[q, p] to p (all_to_all over axis 0);
        # device-local sources are read straight off the local plane.
        outs = []
        for x in planes_local:
            buf = x[send_ids]                      # [P, K] rows for each peer
            got = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                     tiled=True)
            remote = got.reshape(-1)[recv_slot]    # [E_loc]
            outs.append(jnp.where(is_local, x[local_idx], remote))
        return outs

    def _build_superstep(self) -> Callable:
        axes = self.axes
        q = self.q
        comm = self.comm

        def step(planes, gparts, delta):
            d, c, pw, rw0, rc, rp, frozen = planes
            if comm == "halo":
                src, dstl, w, emask, send_ids, recv_slot, is_loc, loc_idx = gparts
            else:
                src, dstl, w, emask = gparts
                send_ids = recv_slot = is_loc = loc_idx = None

            def body(d, c, pw, rw0, rc, rp, frozen, src, dstl, w, emask, *halo):
                # edge shards arrive as [1, E_loc] (leading sharded axis of
                # extent 1 per device) — drop it for the local compute.
                src, dstl, w, emask = src[0], dstl[0], w[0], emask[0]
                send_ids_l = halo[0][0] if halo else None   # [P, K]
                recv_slot_l = halo[1][0] if halo else None  # [E_loc]
                is_loc_l = halo[2][0] if halo else None
                loc_idx_l = halo[3][0] if halo else None
                srcs = self._gather_src_planes(
                    (d, c, pw, rw0, rc, rp), src, recv_slot_l, send_ids_l,
                    is_loc_l, loc_idx_l,
                )
                nd, nc, npw, ch = _relax_local(
                    srcs[0], srcs[1], srcs[2], srcs[3], srcs[4], srcs[5],
                    w, dstl, emask, delta, q, d, c, pw, frozen,
                )
                ch = jax.lax.all_gather(ch[None], axes, tiled=True).any()
                return nd, nc, npw, ch

            in_specs = [P(axes)] * 7 + [P(axes, None)] * 4
            out_specs = (P(axes), P(axes), P(axes), P())
            args = [d, c, pw, rw0, rc, rp, frozen, src, dstl, w, emask]
            if comm == "halo":
                in_specs += [P(axes, None, None)] + [P(axes, None)] * 3
                args += [send_ids, recv_slot, is_loc, loc_idx]
            nd, nc, npw, ch = jax.shard_map(
                body, mesh=self.mesh, in_specs=tuple(in_specs),
                out_specs=out_specs, check_vma=False,
            )(*args)
            return (nd, nc, npw, rw0, rc, rp, frozen), ch

        return step

    def _build_growth_loop(self) -> Callable:
        step = self._step

        @partial(jax.jit, static_argnames=("variant",))
        def growth(planes, gparts, delta, half_target, num_it, variant="stop"):
            def reached(pl_):
                d, _, _, _, _, _, frozen = pl_
                return jnp.sum((~frozen) & (d < delta))

            def cond(carry):
                pl_, k, ch = carry
                more = ch & (k < num_it)
                if variant == "stop":
                    more = more & (reached(pl_) < half_target)
                return more

            def body(carry):
                pl_, k, _ = carry
                pl2, ch = step(pl_, gparts, delta)
                return pl2, k + 1, ch

            planes, k, ch = jax.lax.while_loop(cond, body, (planes, jnp.int32(0), jnp.bool_(True)))
            return planes, k, reached(planes), ch

        return growth

    # -- public API matching cluster()'s relax_fn hook ----------------------
    def make_relax_fn(self):
        """Adapter: cluster(..., relax_fn=engine.make_relax_fn()).

        Returns a ``ShardedBackend`` over this engine: the decomposition
        engine keeps the canonical planes sharded and device-resident for
        the whole run (one pack, zero per-grow host round-trips)."""
        from repro.core.backend import ShardedBackend

        return ShardedBackend(self)

    # -- dry-run entry: one compiled superstep ------------------------------
    def lower_superstep(self, delta: int = 1 << 20):
        """lower+compile one superstep from ShapeDtypeStructs (no data)."""
        ns, es = self.node_sharding(), self.edge_sharding()
        g = self.graph
        sds = jax.ShapeDtypeStruct
        planes = tuple(
            sds((g.n_pad,), jnp.bool_ if i == 6 else jnp.int32, sharding=ns)
            for i in range(7)
        )
        eshape = g.src.shape
        gparts = [
            sds(eshape, jnp.int32, sharding=es),
            sds(eshape, jnp.int32, sharding=es),
            sds(eshape, jnp.int32, sharding=es),
            sds(eshape, jnp.bool_, sharding=es),
        ]
        if self.comm == "halo":
            gparts.append(sds(g.send_ids.shape, jnp.int32,
                              sharding=NamedSharding(self.mesh, P(self.axes, None, None))))
            gparts.append(sds(eshape, jnp.int32, sharding=es))
            gparts.append(sds(eshape, jnp.bool_, sharding=es))
            gparts.append(sds(eshape, jnp.int32, sharding=es))

        def one_step(planes, gparts):
            out, ch = self._step(planes, tuple(gparts), jnp.int32(delta))
            return out, ch

        return jax.jit(one_step).lower(planes, gparts)

"""RelaxBackend: one Δ-growing engine, three interchangeable executions.

The decomposition engine (``core/engine.py``) operates on the canonical
plane-based state (``EngineState``, padded once per decomposition by
``state.pad_state``) and delegates every grow call to a backend:

  * ``SingleDeviceBackend`` — flat edge arrays + the jitted
    ``partial_growth`` while_loop (today's laptop path);
  * ``ShardedBackend`` — wraps ``DistributedEngine`` (allgather or halo
    shard_map supersteps on a device mesh);
  * ``PallasBackend`` — routes the local relax through the fused
    ``kernels/edge_relax`` kernel (compiled Pallas on TPU; the jnp oracle
    or the interpreted kernel when asked for by ``impl``).

All three share the same per-edge candidate rule
(``kernels/edge_relax/ref.edge_relax_candidates``) and the same
lexicographic (d, c, pathw) tuple-min, so for a fixed seed they produce
byte-identical decompositions. ``grow`` is traceable: the engine calls it
from inside one jitted per-stage program, so a stage costs a single host
synchronization regardless of how many supersteps or Δ-doublings it runs.

``transfers`` counts host->device state placements (the pack/pad the seed
engine paid on every grow call); the engine bench asserts it is at most one
per ``cluster()`` call.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.delta_growing import GrowthStats, growth_loop, partial_growth
from repro.core.state import EngineState, init_state, pad_state, relay_planes
from repro.graph.storage import EdgeStore, GraphStore
from repro.graph.structures import EdgeList


@runtime_checkable
class RelaxBackend(Protocol):
    """What the decomposition engine needs from an execution backend."""

    kind: str          # "single" | "sharded" | "pallas"
    n_nodes: int       # real node count
    n_pad: int         # padded plane length (backend-specific layout)
    transfers: int     # host->device state placements (pack/pad events)

    def init_state(self) -> EngineState:
        """Padded, device-resident initial planes. Called once per
        decomposition — the ONLY place planes are packed/padded."""
        ...

    def grow(
        self,
        state: EngineState,
        delta: jnp.ndarray,
        half_target: jnp.ndarray,
        num_it: jnp.ndarray,
        variant: str,
    ) -> Tuple[EngineState, GrowthStats]:
        """One PartialGrowth call on the padded planes. Must be traceable
        (the engine invokes it inside its jitted stage program)."""
        ...

    def grow_spec(self) -> "GrowSpec":
        """Hashable-by-value jit cache key for the engine's stage program."""
        ...

    def graph_args(self) -> Tuple[jnp.ndarray, ...]:
        """Device edge arrays, passed as TRACED operands through the stage
        jit — so re-clustering the same-shaped graph (even via a fresh
        backend instance) hits the compile cache instead of retracing."""
        ...

    def quotient_args(self) -> Tuple[jnp.ndarray, ...]:
        """Flat device ``(src, dst, weight, mask)`` edge views for the
        quotient pass (``core/quotient.py``) — the SAME device buffers the
        backend already holds, so building the quotient costs no host
        round-trip. ``mask`` marks real (non-padding) edges; padded entries
        may carry phantom node ids >= n_nodes."""
        ...


class GrowSpec(tuple):
    """(kind, *static_meta) — the static half of a backend's grow call.

    Value-hashable for the single/pallas kinds, so distinct backend
    instances over same-shaped graphs share one compiled stage program. The
    sharded kind embeds its (long-lived) backend instance, which keys by
    identity — reusing a DistributedEngine reuses its compilation.
    """

    def __new__(cls, *items):
        return super().__new__(cls, items)


def dispatch_grow(spec: GrowSpec, graph_args, state, delta, half_target,
                  num_it, variant: str):
    """Route a grow call from (static spec, traced graph arrays)."""
    kind = spec[0]
    if kind == "single":
        (n_pad,) = spec[1:]
        src, dst, weight = graph_args
        return partial_growth(state, src, dst, weight,
                              jnp.int32(delta), jnp.int32(half_target),
                              jnp.int32(num_it), n_pad, variant=variant)
    if kind == "pallas":
        n_tiles, node_tile, edge_block, impl, fuse = spec[1:]
        bsrc, bdst, bw, bmask, btile = graph_args
        if fuse:
            return _megakernel_growth(state, bsrc, bdst, bw, bmask, btile,
                                      jnp.int32(delta), jnp.int32(half_target),
                                      jnp.int32(num_it), n_tiles, node_tile,
                                      edge_block, impl, fuse, variant)
        return _pallas_growth(state, bsrc, bdst, bw, bmask, btile,
                              jnp.int32(delta), jnp.int32(half_target),
                              jnp.int32(num_it), n_tiles, node_tile,
                              edge_block, impl, variant)
    if kind == "sharded":
        (backend,) = spec[1:]
        return _sharded_growth(backend.eng, state, graph_args, delta,
                               half_target, num_it, variant)
    raise ValueError(f"unknown grow spec kind {kind!r}")


# ---------------------------------------------------------------------------
# single device
# ---------------------------------------------------------------------------


class SingleDeviceBackend:
    """Flat destination-indexed edge arrays + jitted while_loop growth.

    Accepts either a host ``EdgeList`` (uploaded here, the classic path)
    or an ``EdgeStore``/``GraphStore`` — then the store's RESIDENT device
    buffers are bound directly (no re-upload; inert free slots are the
    same 0->0/w=1 padding pooled sessions use, invisible to relaxation)
    and the store keeps ownership: dynamic updates scatter in place and
    ``rebind`` after capacity growth.
    """

    kind = "single"

    def __init__(self, edges):
        if isinstance(edges, EdgeStore):
            store = edges
            store.ensure_device()
            self.n_nodes = store.n_nodes
            self.n_pad = store.n_nodes
            self.src = store.src
            self.dst = store.dst
            self.weight = store.weight
            self.transfers = 0
            return
        self.n_nodes = edges.n_nodes
        self.n_pad = edges.n_nodes
        self.src = jnp.asarray(edges.src)
        self.dst = jnp.asarray(edges.dst)
        self.weight = jnp.asarray(edges.weight)
        self.transfers = 0

    @classmethod
    def from_device(cls, n_nodes: int, src: jnp.ndarray, dst: jnp.ndarray,
                    weight: jnp.ndarray) -> "SingleDeviceBackend":
        """Wrap ALREADY-RESIDENT device edge arrays (int32, inert-padded)
        — the cascade re-enters the engine on a quotient level without a
        host round-trip or re-upload (``core/quotient.QuotientLevel``)."""
        be = cls.__new__(cls)
        be.n_nodes = n_nodes
        be.n_pad = n_nodes
        be.src, be.dst, be.weight = src, dst, weight
        be.transfers = 0
        return be

    def rebind(self, src: jnp.ndarray, dst: jnp.ndarray,
               weight: jnp.ndarray) -> None:
        """Swap the resident edge arrays IN PLACE (dynamic updates mutate
        the graph under a live backend: scatter-updated buffers keep their
        shape and every compiled program; a capacity-grown store re-lands
        here with a longer shape, costing one retrace per capacity bucket).
        Node count and grow spec are unchanged — only the edges move."""
        self.src, self.dst, self.weight = src, dst, weight

    def init_state(self) -> EngineState:
        self.transfers += 1
        return init_state(self.n_pad)

    def grow_spec(self) -> GrowSpec:
        return GrowSpec("single", self.n_pad)

    def graph_args(self):
        return (self.src, self.dst, self.weight)

    def quotient_args(self):
        return (self.src, self.dst, self.weight,
                jnp.ones(self.src.shape, dtype=bool))

    def grow(self, state, delta, half_target, num_it, variant):
        return partial_growth(
            state, self.src, self.dst, self.weight,
            jnp.int32(delta), jnp.int32(half_target), jnp.int32(num_it),
            self.n_pad, variant=variant,
        )


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=(
    "n_tiles", "node_tile", "edge_block", "impl", "variant"))
def _pallas_growth(
    state: EngineState,
    bsrc, bdst, bw, bmask, block_tile,
    delta, half_target, num_it,
    n_tiles: int, node_tile: int, edge_block: int, impl: str,
    variant: str,
):
    """PartialGrowth where each superstep is one fused edge_relax pass."""
    from repro.kernels.edge_relax.ops import edge_relax

    rw0, rc, rp, frozen = relay_planes(state)

    def relax_step(s):
        return edge_relax(
            (s.d, s.c, s.pathw, rw0, rc, rp),
            bsrc, bdst, bw, bmask, block_tile, delta,
            n_tiles, node_tile=node_tile, edge_block=edge_block, impl=impl,
        )

    return growth_loop(state, relax_step, frozen, delta, half_target, num_it,
                       variant)


@partial(jax.jit, static_argnames=(
    "n_tiles", "node_tile", "edge_block", "impl", "fuse", "variant"))
def _megakernel_growth(
    state: EngineState,
    bsrc, bdst, bw, bmask, block_tile,
    delta, half_target, num_it,
    n_tiles: int, node_tile: int, edge_block: int, impl: str, fuse: int,
    variant: str,
):
    """PartialGrowth where each while-body is ONE persistent fused kernel
    running up to ``fuse`` supersteps with resident planes + on-chip stop
    rule (``kernels/edge_relax/megakernel.py``)."""
    from repro.kernels.edge_relax.megakernel import megakernel_growth_loop

    return megakernel_growth_loop(
        state, bsrc, bdst, bw, bmask, block_tile,
        delta, half_target, num_it,
        n_tiles, node_tile, edge_block,
        k_fused=fuse, interpret=impl == "interpret", variant=variant)


class PallasBackend:
    """Blocked dst-sorted edge layout + fused one-pass relax kernel.

    ``impl="auto"`` picks the compiled kernel on a TPU and the jnp
    reference elsewhere; ``impl="pallas"`` off a TPU is an error.

    ``fuse > 0`` switches grow calls to the persistent megakernel: each
    while-loop body runs up to ``fuse`` supersteps in one pallas_call with
    VMEM-resident planes and an on-chip frontier bitmap. The TPU compiler
    refuses the megakernel's in-kernel 1-D gather, so it runs only with
    ``impl="interpret"`` (parity/testing only — slow).
    """

    kind = "pallas"

    def __init__(self, edges: EdgeList, impl: str = "auto",
                 node_tile: Optional[int] = None,
                 edge_block: Optional[int] = None,
                 fuse: int = 0):
        from repro.kernels.edge_relax.kernel import (
            EDGE_BLOCK, NODE_TILE, edge_slabs, validate_tiling)
        from repro.kernels.edge_relax.ops import block_edges_host

        self.node_tile = node_tile or NODE_TILE
        self.edge_block = edge_block or EDGE_BLOCK
        validate_tiling(self.node_tile, self.edge_block)
        from repro.kernels.edge_relax.ops import resolve_impl

        if impl == "auto":
            impl = "pallas" if jax.default_backend() == "tpu" else "ref"
        if fuse < 0:
            raise ValueError(f"fuse must be >= 0, got {fuse}")
        if fuse and impl != "interpret":
            raise ValueError(
                f"fuse={fuse} needs impl='interpret', got impl={impl!r}: the "
                "TPU compiler refuses the fused megakernel's in-kernel 1-D "
                "gather (ref[...].reshape(-1)[srcv]: 'Only 2D gather is "
                "supported'); use fuse=0 for the chained kernel")
        self.impl = resolve_impl(impl)
        blk = block_edges_host(edges.src, edges.dst, edges.weight,
                               edges.n_nodes, self.node_tile, self.edge_block)
        self.n_nodes = edges.n_nodes
        self.n_pad = blk["n_pad_nodes"]
        self.n_tiles = blk["n_tiles"]
        if fuse:
            from repro.kernels.edge_relax.megakernel import fits_vmem
            if not fits_vmem(self.n_pad, self.node_tile, self.edge_block):
                import warnings
                warnings.warn(
                    f"megakernel resident planes for n_pad={self.n_pad} "
                    "exceed the VMEM budget; falling back to the unfused "
                    "pallas grow path", RuntimeWarning, stacklevel=2)
                fuse = 0
        self.fuse = int(fuse)
        # uploaded in the kernels' [n_blocks, 1, edge_block] slab layout
        slabs = lambda x: jnp.asarray(edge_slabs(x, self.edge_block))
        self._bsrc = slabs(blk["src"])
        self._bdst = slabs(blk["dst"])
        self._bw = slabs(blk["w"])
        self._bmask = slabs(blk["mask"])
        self._btile = jnp.asarray(blk["block_tile"])
        self.transfers = 0

    def init_state(self) -> EngineState:
        self.transfers += 1
        return pad_state(init_state(self.n_nodes), self.n_pad)

    def grow_spec(self) -> GrowSpec:
        return GrowSpec("pallas", self.n_tiles, self.node_tile,
                        self.edge_block, self.impl, self.fuse)

    def graph_args(self):
        return (self._bsrc, self._bdst, self._bw, self._bmask, self._btile)

    def quotient_args(self):
        # the blocked layout, flattened: padding slots point at the phantom
        # node and are masked out
        return (self._bsrc.reshape(-1), self._bdst.reshape(-1),
                self._bw.reshape(-1), self._bmask.reshape(-1).astype(bool))

    def grow(self, state, delta, half_target, num_it, variant):
        if self.fuse:
            return _megakernel_growth(
                state, self._bsrc, self._bdst, self._bw, self._bmask,
                self._btile, jnp.int32(delta), jnp.int32(half_target),
                jnp.int32(num_it), self.n_tiles, self.node_tile,
                self.edge_block, self.impl, self.fuse, variant,
            )
        return _pallas_growth(
            state, self._bsrc, self._bdst, self._bw, self._bmask, self._btile,
            jnp.int32(delta), jnp.int32(half_target), jnp.int32(num_it),
            self.n_tiles, self.node_tile, self.edge_block, self.impl,
            variant,
        )


# ---------------------------------------------------------------------------
# sharded (allgather / halo)
# ---------------------------------------------------------------------------


def _sharded_growth(eng, state, gparts, delta, half_target, num_it,
                    variant: str):
    """One grow call on the engine's sharded planes. ``gparts`` (the edge
    shards) arrive as operands: closed over by the engine's stage program,
    they would be embedded in it as constants."""
    rw0, rc, rp, frozen = relay_planes(state)
    planes = (state.d, state.c, state.pathw, rw0, rc, rp, frozen)
    planes, k, reached, changed = eng._growth(
        planes, gparts, jnp.int32(delta),
        jnp.int32(half_target), jnp.int32(num_it), variant=variant,
    )
    state = state._replace(d=planes[0], c=planes[1], pathw=planes[2])
    return state, GrowthStats(steps=k, reached=reached,
                              changed_last=changed)


class ShardedBackend:
    """Wraps ``DistributedEngine``: shard_map supersteps on a device mesh.

    The canonical planes live sharded on the mesh; each grow call derives the
    relay planes (elementwise, on device) and runs the engine's jitted
    superstep while_loop. No per-grow pack or host round-trip.
    """

    kind = "sharded"

    def __init__(self, engine):
        self.eng = engine
        self.n_nodes = engine.graph.n_nodes
        self.n_pad = engine.graph.n_pad
        self.transfers = 0

    def init_state(self) -> EngineState:
        self.transfers += 1
        st = pad_state(init_state(self.n_nodes), self.n_pad)
        ns = self.eng.node_sharding()
        return EngineState(*(jax.device_put(x, ns) for x in st))

    def grow_spec(self) -> GrowSpec:
        # identity-keyed: the mesh/shard_map closures live on the (long-
        # lived) DistributedEngine, so reuse of the engine reuses the
        # compiled stage program.
        return GrowSpec("sharded", self)

    def graph_args(self):
        return self.eng.gparts

    def quotient_args(self):
        # per-device [P, E_loc] shards, flattened with destinations mapped
        # back to global ids (dst_local + owner * nodes_per_device)
        g = self.eng.graph
        P = g.src.shape[0]
        offs = (jnp.arange(P, dtype=jnp.int32)
                * jnp.int32(g.nodes_per_device))[:, None]
        return (g.src.reshape(-1), (g.dst_local + offs).reshape(-1),
                g.weight.reshape(-1), g.edge_mask.reshape(-1).astype(bool))

    def grow(self, state, delta, half_target, num_it, variant):
        return _sharded_growth(self.eng, state, self.eng.gparts, delta,
                               half_target, num_it, variant)

    # -- wire-byte accounting (read by engine._comm_accounting) ----------

    @property
    def halo_bytes_per_step(self) -> int:
        """Collective plane-row bytes one superstep moves under the
        engine's comm mode — exact: the plan is static, no sync needed."""
        return self.eng.comm_bytes_per_superstep()

    @property
    def fullplane_bytes_per_step(self) -> int:
        """What the full-plane all-gather baseline would move."""
        return self.eng.fullplane_bytes_per_superstep()


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------


def make_backend(
    edges,
    spec="single",
    *,
    mesh=None,
    comm: str = "halo",
    impl: str = "auto",
    node_tile: int = 0,
    edge_block: int = 0,
    fuse: int = 0,
) -> RelaxBackend:
    """Resolve a backend from a config spec (or pass one through).

    ``edges`` may be an ``EdgeList`` or a ``graph.storage`` store: the
    single kind binds the store's resident device buffers directly, the
    sharded kind reuses a ``GraphStore``'s prebuilt slab/halo layout via
    ``sharded_graph()`` when the shard count matches the mesh, and the
    pallas kind re-blocks from the store's valid edges.

    ``comm`` defaults to ``"halo"``: supersteps exchange ONLY the static
    halo plan's boundary plane rows (``"allgather"`` — the full-plane
    baseline the halo_bytes metric is measured against — remains
    selectable and byte-identical in results).

    ``node_tile`` / ``edge_block`` / ``fuse`` apply to the pallas kind only
    (0 = kernel defaults / unfused); typically filled in by the autotuner.
    """
    if not isinstance(spec, str):
        return spec  # already a RelaxBackend
    store = edges if isinstance(edges, EdgeStore) else None
    if spec in ("", "single"):
        return SingleDeviceBackend(edges)
    if spec == "pallas":
        e = store.edge_list() if store is not None else edges
        return PallasBackend(e, impl=impl, node_tile=node_tile or None,
                             edge_block=edge_block or None, fuse=fuse)
    if spec == "sharded":
        from repro.core.distributed import DistributedEngine

        if mesh is None:
            from repro.launch.mesh import host_device_mesh

            mesh = host_device_mesh()
        graph = None
        if isinstance(store, GraphStore) and store.n_shards > 1:
            graph = store.sharded_graph(build_halo=(comm == "halo"))
        e = store.edge_list() if store is not None else edges
        return ShardedBackend(DistributedEngine(e, mesh, comm=comm,
                                                graph=graph))
    raise ValueError(f"unknown backend {spec!r} "
                     "(expected single | sharded | pallas)")

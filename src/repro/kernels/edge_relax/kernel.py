"""Pallas TPU kernel for the fused Δ-growing relaxation.

The reference does 3 full HBM passes over the per-edge arrays (one
``segment_min`` per plane of the lexicographic (d, c, pathw) tuple-min) plus
the mask intermediates XLA materializes between them. This kernel makes ONE
pass: per edge block it computes the candidates on the VPU and reduces the
tuple-min into the owning node tile entirely in VMEM, carrying the partial
result across the edge blocks of a tile (blocks of one tile are consecutive
in the destination-sorted layout, so the output block stays resident).

Layout contract (produced by ``graph.structures.DeviceGraph.build``):
  * edges destination-sorted, segmented so no edge block straddles a node
    tile; padding edges point at the phantom node with mask=False;
  * ``block_tile[b]`` = node tile owning edge block b (scalar-prefetched so
    Pallas can map output blocks before the body runs);

Grid: one step per edge block (sequential — "arbitrary" dimension semantics),
output node-tile block revisited by consecutive steps. The edge arrays are
laid out ``[n_blocks, 1, edge_block]`` (``edge_slabs``) and the outputs
``[n_tiles, 1, node_tile]``, each grid step taking one squeezed ``(1, ·)``
slab: the TPU compiler (Mosaic) requires the last two block dims to be
divisible by (8, 128) or equal to the array's, which a ``(1, edge_block)``
block of a ``[n_blocks, edge_block]`` array is not. The within-block
reduce-by-key is a broadcast-compare + row-min over a [node_tile, edge_block]
match matrix: a VPU-native realization of the scatter that would be a serial
loop on TPU. int32 throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# default tiling: 256-node tiles, 512-edge blocks -> match matrix 256x512
NODE_TILE = 256
EDGE_BLOCK = 512


def validate_tiling(node_tile: int, edge_block: int) -> None:
    """Reject tilings the kernels cannot execute correctly.

    ``edge_block`` must be a positive multiple of 128 (TPU lane width: edge
    blocks are the minor dimension of every streamed array) and ``node_tile``
    a positive power of two (``dst // node_tile`` tile assignment and the
    phantom-node padding in ``block_edges_host`` assume it).
    """
    if edge_block <= 0 or edge_block % 128 != 0:
        raise ValueError(
            f"edge_block must be a positive multiple of 128, got {edge_block}")
    if node_tile <= 0 or (node_tile & (node_tile - 1)) != 0:
        raise ValueError(
            f"node_tile must be a positive power of two, got {node_tile}")


def validate_block_tile(block_tile, n_tiles: int) -> None:
    """Check a concrete block->tile map: every block owned by a valid tile,
    and each tile's blocks CONSECUTIVE (monotone non-decreasing) — the
    carried-partial merge in ``_relax_kernel`` revisits the same output
    block across consecutive grid steps and would silently lose updates on
    an interleaved map."""
    import numpy as np
    bt = np.asarray(block_tile)
    if bt.ndim != 1 or bt.size == 0:
        raise ValueError("block_tile must be a non-empty 1-D array")
    if bt.min() < 0 or bt.max() >= n_tiles:
        raise ValueError(
            f"block_tile entries must be in [0, {n_tiles}), got range "
            f"[{int(bt.min())}, {int(bt.max())}]")
    if np.any(np.diff(bt) < 0):
        raise ValueError(
            "block_tile must be monotone non-decreasing: the kernel carries "
            "each tile's partial tuple-min across consecutive edge blocks")


def edge_slabs(x, edge_block: int):
    """``[n_blocks, 1, edge_block]`` view of blocked per-edge data (a jax or
    numpy array): the layout the kernels stream. Free when ``x`` already has
    it; on TPU a ``[n_blocks, edge_block]`` array is tiled differently, so
    reshaping one costs a copy — ``PallasBackend`` uploads its edges in this
    layout so no superstep pays it."""
    return x.reshape(x.shape[0], 1, edge_block)


def _relax_kernel(
    # scalar-prefetch
    block_tile,            # int32 [n_blocks]  node tile of each edge block
    delta_ref,             # int32 [1]
    # per-edge inputs, one [1, EDGE_BLOCK] slab of [n_blocks, 1, EDGE_BLOCK]
    d_src, c_src, p_src, rw0, rc, rp, w, dst, mask,
    # outputs, one [1, NODE_TILE] slab of [n_tiles, 1, NODE_TILE]
    # (revisited across a tile's blocks)
    d_out, c_out, p_out,
    *, node_tile: int, edge_block: int,
):
    INF = jnp.int32(2**31 - 1)   # created inside the traced body: Pallas
    BIG = jnp.int32(2**30)       # forbids captured outer-scope constants
    b = pl.program_id(0)
    delta = delta_ref[0]
    tile = block_tile[b]

    # --- candidate computation (VPU elementwise) -------------------------
    dsv, wv, mk = d_src[0], w[0], mask[0] != 0
    rw0v = rw0[0]
    live_ok = (dsv < delta) & (wv < delta) & mk
    live_d = jnp.where(live_ok, jnp.where(live_ok, dsv, 0) + wv, INF)
    w_red = jnp.maximum(wv + jnp.where(rw0v >= BIG, BIG, rw0v), 0)
    relay_ok = (rw0v < BIG) & (w_red < delta) & mk
    cand_d = jnp.where(relay_ok, w_red, live_d)
    cand_c = jnp.where(relay_ok, rc[0], jnp.where(live_ok, c_src[0], INF))
    p_base = jnp.where(relay_ok, rp[0], jnp.where(live_ok, p_src[0], 0))
    p_safe = jnp.where(p_base >= BIG, 0, p_base)
    cand_p = jnp.where(relay_ok | live_ok, p_safe + wv, INF)

    # --- within-block tuple-min by destination row ------------------------
    local_dst = dst[0] - tile * node_tile                       # [E]
    rows = jax.lax.broadcasted_iota(jnp.int32, (node_tile, edge_block), 0)
    match = local_dst[None, :] == rows                          # [T, E]
    dmat = jnp.where(match, cand_d[None, :], INF)
    d_blk = jnp.min(dmat, axis=1)                               # [T]
    w1 = match & (cand_d[None, :] == d_blk[:, None])
    c_blk = jnp.min(jnp.where(w1, cand_c[None, :], INF), axis=1)
    w2 = w1 & (cand_c[None, :] == c_blk[:, None])
    p_blk = jnp.min(jnp.where(w2, cand_p[None, :], INF), axis=1)

    # --- merge with the carried partial result for this tile --------------
    first = jnp.where(b > 0, block_tile[jnp.maximum(b - 1, 0)] != tile, True)

    @pl.when(first)
    def _init():
        d_out[0, :] = jnp.full((node_tile,), INF, jnp.int32)
        c_out[0, :] = jnp.full((node_tile,), INF, jnp.int32)
        p_out[0, :] = jnp.full((node_tile,), INF, jnp.int32)

    d_prev, c_prev, p_prev = d_out[0, :], c_out[0, :], p_out[0, :]
    take = (d_blk < d_prev) | (
        (d_blk == d_prev) & ((c_blk < c_prev) | ((c_blk == c_prev) & (p_blk < p_prev)))
    )
    d_out[0, :] = jnp.where(take, d_blk, d_prev)
    c_out[0, :] = jnp.where(take, c_blk, c_prev)
    p_out[0, :] = jnp.where(take, p_blk, p_prev)


@functools.partial(
    jax.jit,
    static_argnames=("n_tiles", "node_tile", "edge_block", "interpret"),
)
def _edge_relax_pallas_jit(
    d_src: jnp.ndarray,     # int32 [n_blocks, (1,) EDGE_BLOCK] gathered planes
    c_src: jnp.ndarray,
    p_src: jnp.ndarray,
    rw0: jnp.ndarray,
    rc: jnp.ndarray,
    rp: jnp.ndarray,
    w: jnp.ndarray,
    dst: jnp.ndarray,
    mask: jnp.ndarray,      # int32 0/1 (TPU-friendly; bool also accepted)
    block_tile: jnp.ndarray,  # int32 [n_blocks]
    delta: jnp.ndarray,       # int32 [1]
    n_tiles: int,
    node_tile: int = NODE_TILE,
    edge_block: int = EDGE_BLOCK,
    interpret: bool = False,
):
    """Fused relax + lexicographic segment-min. Returns (d, c, p) [n_tiles*T]."""
    n_blocks = d_src.shape[0]
    e3 = lambda x: edge_slabs(x, edge_block)
    edge_args = (e3(d_src), e3(c_src), e3(p_src), e3(rw0), e3(rc), e3(rp),
                 e3(w), e3(dst), e3(mask))

    edge_spec = pl.BlockSpec((None, 1, edge_block), lambda b, *_: (b, 0, 0))
    out_spec = pl.BlockSpec((None, 1, node_tile),
                            lambda b, bt, _d: (bt[b], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[edge_spec] * 9,
        out_specs=[out_spec] * 3,
    )
    out_shape = [
        jax.ShapeDtypeStruct((n_tiles, 1, node_tile), jnp.int32)
        for _ in range(3)
    ]
    kern = functools.partial(_relax_kernel, node_tile=node_tile, edge_block=edge_block)
    d, c, p = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
    )(block_tile, delta, *edge_args)
    return d.reshape(-1), c.reshape(-1), p.reshape(-1)


def edge_relax_pallas(
    d_src, c_src, p_src, rw0, rc, rp, w, dst, mask, block_tile, delta,
    n_tiles: int,
    node_tile: int = NODE_TILE,
    edge_block: int = EDGE_BLOCK,
    interpret: bool = False,
):
    """Validated entry point for the fused relax kernel.

    Custom tilings that break the layout contract produced a silently wrong
    answer before; now they raise. The monotone block_tile check only runs
    on concrete (non-traced) arrays — inside a jit the map was already
    validated when the caller built it on the host.
    """
    validate_tiling(node_tile, edge_block)
    if not isinstance(block_tile, jax.core.Tracer):
        validate_block_tile(block_tile, n_tiles)
    return _edge_relax_pallas_jit(
        d_src, c_src, p_src, rw0, rc, rp, w, dst, mask, block_tile, delta,
        n_tiles, node_tile=node_tile, edge_block=edge_block,
        interpret=interpret)

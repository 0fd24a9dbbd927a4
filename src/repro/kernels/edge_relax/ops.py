"""jit'd public wrapper for the edge_relax kernel.

``edge_relax(...)`` takes flat destination-sorted per-edge arrays (the layout
``DeviceGraph.build`` produces, or any dst-sorted edge list — this wrapper
re-blocks on the fly), pre-gathers the source planes, dispatches to the
Pallas kernel (``impl="pallas"``, TPU only; ``"interpret"`` anywhere) or
the jnp oracle (``impl="ref"``), and returns per-node (d_min, c_min, p_min).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import next_multiple
from repro.kernels.edge_relax.kernel import (
    EDGE_BLOCK,
    NODE_TILE,
    edge_relax_pallas,
)
from repro.kernels.edge_relax.ref import INF, edge_relax_ref


def resolve_impl(impl: str) -> str:
    """Compiled Pallas needs a TPU: asking for it elsewhere is an error, not
    a quiet switch to the reference. ``interpret`` runs the kernel body on
    any backend and ``ref`` is the jnp oracle."""
    if impl == "pallas" and jax.default_backend() != "tpu":
        raise RuntimeError(
            "edge_relax: impl='pallas' needs a TPU, but the default JAX "
            f"backend is {jax.default_backend()!r}; use impl='interpret' to "
            "run the kernel body here or impl='ref' for the jnp oracle")
    if impl not in ("pallas", "interpret", "ref"):
        raise ValueError(
            f"unknown edge_relax impl {impl!r} (expected pallas | interpret "
            "| ref)")
    return impl


def block_edges_host(
    src: np.ndarray,
    dst: np.ndarray,
    w: np.ndarray,
    n_nodes: int,
    node_tile: int = NODE_TILE,
    edge_block: int = EDGE_BLOCK,
):
    """Host-side preprocessing: dst-sort + segment per node tile + pad.

    Returns dict of [n_blocks, edge_block] arrays + block_tile [n_blocks]
    + n_tiles. Pure numpy; do once per graph.
    """
    order = np.lexsort((src, dst))
    src, dst, w = src[order], dst[order], w[order]
    n_pad_nodes = next_multiple(n_nodes + 1, node_tile)
    n_tiles = n_pad_nodes // node_tile
    phantom = n_pad_nodes - 1

    tile_of_edge = dst // node_tile
    counts = np.bincount(tile_of_edge, minlength=n_tiles)
    # every tile gets >= 1 (possibly all-phantom) block so its output block
    # is always visited and initialized by the kernel
    padded = np.maximum(-(-counts // edge_block) * edge_block, edge_block)
    total = int(padded.sum())

    sp = np.full(total, phantom, np.int32)
    dp = np.full(total, phantom, np.int32)
    wp = np.ones(total, np.int32)
    mk = np.zeros(total, np.int32)
    si = np.concatenate([[0], np.cumsum(counts)])
    so = np.concatenate([[0], np.cumsum(padded)])
    for t in range(n_tiles):
        c = int(counts[t])
        if c == 0:
            continue
        a, b = int(si[t]), int(so[t])
        sp[b : b + c] = src[a : a + c]
        dp[b : b + c] = dst[a : a + c]
        wp[b : b + c] = w[a : a + c]
        mk[b : b + c] = 1
    # phantom padding rows must still map into their block's tile
    for t in range(n_tiles):
        a, b = int(so[t]), int(so[t] + padded[t])
        dp[a:b][mk[a:b] == 0] = min(t * node_tile, phantom)
        if padded[t]:
            dp[a:b][mk[a:b] == 0] = t * node_tile  # any row in tile t

    n_blocks = total // edge_block
    block_tile = np.repeat(np.arange(n_tiles, dtype=np.int32), padded // edge_block)
    shape = (n_blocks, edge_block)
    return {
        "src": sp.reshape(shape),
        "dst": dp.reshape(shape),
        "w": wp.reshape(shape),
        "mask": mk.reshape(shape),
        "block_tile": block_tile,
        "n_tiles": n_tiles,
        "n_pad_nodes": n_pad_nodes,
    }


@partial(jax.jit, static_argnames=("n_tiles", "node_tile", "edge_block", "impl"))
def edge_relax(
    planes: Tuple[jnp.ndarray, ...],  # (d, c, p, rw0, rc, rp) node planes [n_pad]
    blocked_src: jnp.ndarray,         # [n_blocks, E_B] or [n_blocks, 1, E_B]
    blocked_dst: jnp.ndarray,
    blocked_w: jnp.ndarray,
    blocked_mask: jnp.ndarray,
    block_tile: jnp.ndarray,
    delta: jnp.ndarray,
    n_tiles: int,
    node_tile: int = NODE_TILE,
    edge_block: int = EDGE_BLOCK,
    impl: str = "ref",
):
    """One fused relaxation pass. Gathers source planes then reduces."""
    impl = resolve_impl(impl)
    d, c, p, rw0, rc, rp = planes
    g = lambda x: x[blocked_src]
    if impl == "pallas" or impl == "interpret":
        return edge_relax_pallas(
            g(d), g(c), g(p), g(rw0), g(rc), g(rp),
            blocked_w, blocked_dst, blocked_mask, block_tile,
            jnp.asarray(delta, jnp.int32).reshape(1),
            n_tiles=n_tiles, node_tile=node_tile, edge_block=edge_block,
            interpret=(impl == "interpret"),
        )
    n = n_tiles * node_tile
    flat = lambda x: x.reshape(-1)
    return edge_relax_ref(
        flat(g(d)), flat(g(c)), flat(g(p)), flat(g(rw0)), flat(g(rc)), flat(g(rp)),
        flat(blocked_w), flat(blocked_dst), flat(blocked_mask).astype(bool),
        jnp.asarray(delta, jnp.int32), n,
    )

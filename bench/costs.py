"""Least bytes a kernel call must move, from its shapes.

The chained edge-relax kernel (``kernels/edge_relax/kernel.py``) takes one
``[1, edge_block]`` slab of each of nine int32 per-edge arrays per grid
step (the three gathered source planes, the three relay planes, weight,
destination and mask), writes three int32 ``[n_tiles, node_tile]`` node
planes, and prefetches the int32 block-to-tile map and delta. Each byte is
counted once: the least HBM traffic of one call. The algorithm needs a few
integer operations per edge against 36 bytes, so HBM bandwidth is its roof.
"""
from __future__ import annotations

INT32 = 4
EDGE_PLANES = 9
NODE_PLANES = 3


def edge_relax_bytes(n_blocks: int, edge_block: int, n_tiles: int,
                     node_tile: int) -> int:
    return INT32 * (EDGE_PLANES * n_blocks * edge_block
                    + NODE_PLANES * n_tiles * node_tile
                    + n_blocks + 1)

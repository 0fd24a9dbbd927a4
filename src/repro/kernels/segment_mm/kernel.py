"""Pallas TPU kernel: fused scale + scatter-sum as an MXU matmul.

TPU adaptation of GE-SpMM-style gather-GEMM-scatter: the scatter-sum (which
would be a serial read-modify-write loop on the VPU) is restated as a
one-hot matmul on the systolic array:

    Y_tile [T, D] += onehot(dst_local) [T, E_B]  @  (coeff * X_src) [E_B, D]

Edges are destination-sorted and blocked so each edge block feeds exactly one
node tile (same layout contract as edge_relax); the output tile stays in VMEM
across its consecutive edge blocks. The gather X[src] is pre-staged by XLA
outside the kernel (TPU gathers from HBM are efficient; in-kernel per-row
indirection is not) — the kernel fuses everything after the gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NODE_TILE = 256
EDGE_BLOCK = 512


def _segment_mm_kernel(
    block_tile,             # scalar-prefetch int32 [n_blocks]
    xsrc_ref,               # [EDGE_BLOCK, D] pre-gathered rows
    coeff_ref,              # [1, EDGE_BLOCK]
    dst_ref,                # int32 [1, EDGE_BLOCK]
    y_ref,                  # [NODE_TILE, D] (revisited per tile)
    *, node_tile: int, edge_block: int,
):
    b = pl.program_id(0)
    tile = block_tile[b]
    first = jnp.where(b > 0, block_tile[jnp.maximum(b - 1, 0)] != tile, True)

    @pl.when(first)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    local = dst_ref[0] - tile * node_tile                        # [E]
    rows = jax.lax.broadcasted_iota(jnp.int32, (node_tile, edge_block), 0)
    onehot = (local[None, :] == rows).astype(jnp.float32)        # [T, E]
    msgs = xsrc_ref[...].astype(jnp.float32) * coeff_ref[0][:, None]
    y_ref[...] += jax.lax.dot_general(
        onehot, msgs, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n_tiles", "node_tile", "edge_block", "interpret")
)
def segment_mm_pallas(
    x_src: jnp.ndarray,       # [n_blocks*E_B, D] pre-gathered X[src]
    coeff: jnp.ndarray,       # [n_blocks, E_B] (0 on padding edges)
    dst: jnp.ndarray,         # int32 [n_blocks, E_B]
    block_tile: jnp.ndarray,  # int32 [n_blocks]
    n_tiles: int,
    node_tile: int = NODE_TILE,
    edge_block: int = EDGE_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    n_blocks = coeff.shape[0]
    d = x_src.shape[-1]
    x_src = x_src.reshape(n_blocks * edge_block, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((edge_block, d), lambda b, bt: (b, 0)),
            pl.BlockSpec((1, edge_block), lambda b, bt: (b, 0)),
            pl.BlockSpec((1, edge_block), lambda b, bt: (b, 0)),
        ],
        out_specs=pl.BlockSpec((node_tile, d), lambda b, bt: (bt[b], 0)),
    )
    kern = functools.partial(
        _segment_mm_kernel, node_tile=node_tile, edge_block=edge_block
    )
    # pallas: tiles validated by edge_relax.validate_tiling in the calling backend
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles * node_tile, d), x_src.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
    )(block_tile, x_src, coeff, dst)

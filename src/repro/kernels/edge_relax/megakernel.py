"""Persistent fused grow-superstep megakernel (Pallas TPU).

``kernel.py`` fuses ONE relaxation superstep into one pass over the edge
blocks, but a grow call is a *loop* of supersteps: between kernel launches
the planes round-trip through HBM, XLA re-issues the gather / candidate /
tuple-min chain per superstep, and the while_loop re-dispatches one
``pallas_call`` per iteration. This module runs K supersteps (K static) in a
SINGLE ``pallas_call``:

  * grid = (K, n_blocks), both dimensions "arbitrary" (sequential), so the
    Pallas pipeline double-buffers the edge-block DMA along the inner
    dimension while compute runs — edges stream HBM -> VMEM exactly once per
    superstep;
  * the node planes (d, c, pathw), the relay planes, and the frontier bitmap
    stay RESIDENT in VMEM for all K supersteps (BlockSpec index maps pin
    them to block (0, 0));
  * an on-chip frontier bitmap (``front``: 1 where the node's tuple changed
    in the previous superstep) lets dead edge blocks — blocks none of whose
    masked sources changed — skip the candidate/tuple-min compute entirely,
    with no host round-trip. Skipped blocks are counted (their DMA still
    streams: a pure DMA-stall slot the ``EngineMetrics.dma_stall_blocks``
    counter surfaces);
  * the PartialGrowth stopping rule (``core.delta_growing.growth_loop``)
    is evaluated ON CHIP before every superstep, so a fused chunk that
    reaches the stop/quiescence condition early freezes the remaining
    supersteps — the result is byte-identical to the unfused loop, never
    "K supersteps no matter what".

Frontier-skip soundness: a candidate from edge (u, v) depends only on u's
in-stage tuple (d, c, pathw), the relay planes (constant within a grow
call), the edge weight, and Delta (constant within a call). If u did not
change in superstep k-1, it emits the same candidates in superstep k that
were already merged in k-1 — merging is idempotent — so only blocks with a
changed source can produce an update. The bitmap starts all-ones, so every
block is processed at least once per grow call.

``ref.py`` (via ``core.delta_growing.growth_loop`` + ``edge_relax_ref``)
remains the byte-identical parity oracle; the megakernel parity suite
(``tests/test_megakernel.py``) runs this kernel in interpret mode on CPU.

Interpret mode is the only way this kernel runs today: the TPU compiler
(Mosaic) refuses the in-kernel source gathers ``ref[...].reshape(-1)[srcv]``
with "Only 2D gather is supported", so ``PallasBackend`` rejects ``fuse > 0``
with any ``impl`` other than ``"interpret"``. Returning it to the chip means
dropping that 1-D gather first.

VMEM contract: 15 int32 planes of ``n_pad`` slots stay resident (8 inputs,
4 outputs, 3 accumulator scratch) plus the [node_tile, edge_block] match
matrix. ``fits_vmem`` checks the footprint against a conservative budget;
``PallasBackend`` falls back to the unfused path when it does not fit.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.edge_relax.kernel import edge_slabs


# stats layout: one row per fused superstep + one summary row (index K).
# Per-superstep rows: executed flag, nodes changed, reached count after the
# merge, cumulative dead (frontier-skipped) blocks, continue flag.
# Summary row: supersteps executed this call, final reached count, final
# changed flag, total dead blocks, continue flag for the NEXT chunk.
STATS_W = 8
COL_EXECUTED = 0   # summary: supersteps executed in this call
COL_CHANGED = 1    # summary: changed flag after the last executed superstep
COL_REACHED = 2    # summary: |{~frozen: d < delta}| on the final planes
COL_DEAD = 3       # summary: frontier-skipped edge blocks (DMA-stall slots)
COL_CONT = 4       # summary: growth_loop cond for the next superstep

DEFAULT_K_FUSED = 8

# Conservative VMEM budget for the resident planes + match matrix (v5e has
# ~16 MiB/core; leave headroom for the streamed edge blocks and spills).
VMEM_BUDGET_BYTES = 8 * 2**20
_RESIDENT_PLANES = 15  # 8 inputs + 4 outputs + 3 accumulator scratch


def vmem_footprint_bytes(n_pad: int, node_tile: int, edge_block: int) -> int:
    """Bytes of VMEM the fused kernel keeps live: resident int32 planes,
    the [node_tile, edge_block] match matrix (×4 for the masked candidate
    intermediates), and the double-buffered edge blocks (4 arrays × 2)."""
    planes = _RESIDENT_PLANES * n_pad * 4
    match = 4 * node_tile * edge_block * 4
    edges = 2 * 4 * edge_block * 4
    return planes + match + edges


def fits_vmem(n_pad: int, node_tile: int, edge_block: int,
              budget: int = VMEM_BUDGET_BYTES) -> bool:
    return vmem_footprint_bytes(n_pad, node_tile, edge_block) <= budget


def _mega_kernel(
    # scalar prefetch
    block_tile,            # int32 [n_blocks]  node tile of each edge block
    params,                # int32 [8]: delta, half_target, num_it,
                           #            steps_base, stop_variant, ...
    # resident inputs [n_tiles, node_tile]
    d0, c0, p0, rw0, rc, rp, frozen, front0,
    # per-edge inputs, one [1, edge_block] slab per step of grid dim 1
    bsrc, bdst, bw, bmask,
    # resident outputs
    d, c, p, front,        # [n_tiles, node_tile]
    stats,                 # [k_fused + 1, STATS_W]
    # scratch
    acc_d, acc_c, acc_p,   # VMEM [n_tiles, node_tile] superstep accumulators
    flags,                 # SMEM [8]: running, steps, changed, dead_blocks
    *, node_tile: int, edge_block: int,
):
    INF = jnp.int32(2**31 - 1)   # traced-body constants (Pallas forbids
    BIG = jnp.int32(2**30)       # captured outer-scope arrays)
    k = pl.program_id(0)
    b = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    delta = params[0]
    half_target = params[1]
    num_it = params[2]
    steps_base = params[3]
    stop_variant = params[4]

    def reached_count():
        return jnp.sum(((frozen[...] == 0) & (d[...] < delta))
                       .astype(jnp.int32))

    def cond_flag(changed_i32, steps_done, reached):
        """growth_loop.cond: changed & steps < num_it [& reached < target]."""
        more = (changed_i32 == 1) & (steps_base + steps_done < num_it)
        return more & ((stop_variant == 0) | (reached < half_target))

    # ---- once per call: land the carried planes in VMEM -------------------
    @pl.when((k == 0) & (b == 0))
    def _init_call():
        d[...] = d0[...]
        c[...] = c0[...]
        p[...] = p0[...]
        front[...] = front0[...]
        stats[...] = jnp.zeros(stats.shape, jnp.int32)
        flags[0] = 1  # running
        flags[1] = 0  # supersteps executed
        flags[2] = 1  # changed (growth_loop's initial True)
        flags[3] = 0  # dead blocks

    # ---- once per superstep: on-chip stop rule + fresh accumulators -------
    @pl.when(b == 0)
    def _start_superstep():
        live = cond_flag(flags[2], k, reached_count())
        flags[0] = jnp.where(flags[0] == 1, live.astype(jnp.int32), 0)
        acc_d[...] = jnp.full(acc_d.shape, INF, jnp.int32)
        acc_c[...] = jnp.full(acc_c.shape, INF, jnp.int32)
        acc_p[...] = jnp.full(acc_p.shape, INF, jnp.int32)

    # ---- per edge block: frontier check, candidates, tuple-min ------------
    running = flags[0] == 1
    tile = block_tile[b]
    srcv = bsrc[0]
    mk = bmask[0] != 0
    live_block = jnp.any((front[...].reshape(-1)[srcv] == 1) & mk)

    @pl.when(running & live_block)
    def _relax_block():
        gather = lambda ref: ref[...].reshape(-1)[srcv]
        dsv, csv, psv = gather(d), gather(c), gather(p)
        rw0v, rcv, rpv = gather(rw0), gather(rc), gather(rp)
        wv = bw[0]
        # candidate rule — mirror of ref.edge_relax_candidates
        live_ok = (dsv < delta) & (wv < delta) & mk
        live_d = jnp.where(live_ok, jnp.where(live_ok, dsv, 0) + wv, INF)
        w_red = jnp.maximum(wv + jnp.where(rw0v >= BIG, BIG, rw0v), 0)
        relay_ok = (rw0v < BIG) & (w_red < delta) & mk
        cand_d = jnp.where(relay_ok, w_red, live_d)
        cand_c = jnp.where(relay_ok, rcv, jnp.where(live_ok, csv, INF))
        p_base = jnp.where(relay_ok, rpv, jnp.where(live_ok, psv, 0))
        p_safe = jnp.where(p_base >= BIG, 0, p_base)
        cand_p = jnp.where(relay_ok | live_ok, p_safe + wv, INF)
        # within-block tuple-min by destination row (VPU match matrix)
        local_dst = bdst[0] - tile * node_tile
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (node_tile, edge_block), 0)
        match = local_dst[None, :] == rows
        d_blk = jnp.min(jnp.where(match, cand_d[None, :], INF), axis=1)
        w1 = match & (cand_d[None, :] == d_blk[:, None])
        c_blk = jnp.min(jnp.where(w1, cand_c[None, :], INF), axis=1)
        w2 = w1 & (cand_c[None, :] == c_blk[:, None])
        p_blk = jnp.min(jnp.where(w2, cand_p[None, :], INF), axis=1)
        # lexicographic merge into the owning tile's accumulator row
        idx = (pl.ds(tile, 1), pl.ds(0, node_tile))
        ad = acc_d[idx][0]
        ac = acc_c[idx][0]
        ap = acc_p[idx][0]
        take = (d_blk < ad) | ((d_blk == ad) & (
            (c_blk < ac) | ((c_blk == ac) & (p_blk < ap))))
        acc_d[idx] = jnp.where(take, d_blk, ad)[None]
        acc_c[idx] = jnp.where(take, c_blk, ac)[None]
        acc_p[idx] = jnp.where(take, p_blk, ap)[None]

    @pl.when(running & ~live_block)
    def _dead_block():
        flags[3] = flags[3] + 1

    # ---- once per superstep: merge + stats ---------------------------------
    @pl.when(b == n_blocks - 1)
    def _finish_superstep():
        @pl.when(flags[0] == 1)
        def _merge():
            upd = (frozen[...] == 0) & (acc_d[...] < d[...])
            d[...] = jnp.where(upd, acc_d[...], d[...])
            c[...] = jnp.where(upd, acc_c[...], c[...])
            p[...] = jnp.where(upd, acc_p[...], p[...])
            front[...] = upd.astype(jnp.int32)
            n_changed = jnp.sum(upd.astype(jnp.int32))
            flags[1] = flags[1] + 1
            flags[2] = (n_changed > 0).astype(jnp.int32)
            reached = reached_count()
            cont = cond_flag(flags[2], flags[1], reached)
            row = jnp.zeros((STATS_W,), jnp.int32)
            row = row.at[COL_EXECUTED].set(1)
            row = row.at[COL_CHANGED].set(n_changed)
            row = row.at[COL_REACHED].set(reached)
            row = row.at[COL_DEAD].set(flags[3])
            row = row.at[COL_CONT].set(cont.astype(jnp.int32))
            stats[pl.ds(k, 1), pl.ds(0, STATS_W)] = row[None]

        @pl.when(k == pl.num_programs(0) - 1)
        def _summary():
            reached = reached_count()
            cont = cond_flag(flags[2], flags[1], reached)
            row = jnp.zeros((STATS_W,), jnp.int32)
            row = row.at[COL_EXECUTED].set(flags[1])
            row = row.at[COL_CHANGED].set(flags[2])
            row = row.at[COL_REACHED].set(reached)
            row = row.at[COL_DEAD].set(flags[3])
            row = row.at[COL_CONT].set(cont.astype(jnp.int32))
            stats[pl.ds(pl.num_programs(0), 1), pl.ds(0, STATS_W)] = row[None]


@functools.partial(jax.jit, static_argnames=(
    "k_fused", "n_tiles", "node_tile", "edge_block", "interpret"))
def fused_grow_supersteps(
    d: jnp.ndarray,          # [n_tiles, node_tile] in-stage planes
    c: jnp.ndarray,
    p: jnp.ndarray,
    rw0: jnp.ndarray,        # relay planes (constant within a grow call)
    rc: jnp.ndarray,
    rp: jnp.ndarray,
    frozen: jnp.ndarray,     # int32 0/1
    front: jnp.ndarray,      # int32 0/1 frontier bitmap (carried)
    bsrc: jnp.ndarray,       # [n_blocks, (1,) edge_block] blocked edges
    bdst: jnp.ndarray,
    bw: jnp.ndarray,
    bmask: jnp.ndarray,
    block_tile: jnp.ndarray,  # int32 [n_blocks]
    params: jnp.ndarray,      # int32 [8]; see _mega_kernel
    k_fused: int,
    n_tiles: int,
    node_tile: int,
    edge_block: int,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """Up to ``k_fused`` supersteps in one pallas_call.

    Returns ``(d, c, p, front, stats)``; ``stats[k_fused]`` is the summary
    row (see the COL_* constants).
    """
    n_blocks = bsrc.shape[0]
    plane_spec = pl.BlockSpec((n_tiles, node_tile), lambda k, b, *_: (0, 0))
    edge_spec = pl.BlockSpec((None, 1, edge_block),
                             lambda k, b, *_: (b, 0, 0))
    stats_spec = pl.BlockSpec((k_fused + 1, STATS_W), lambda k, b, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(k_fused, n_blocks),
        in_specs=[plane_spec] * 8 + [edge_spec] * 4,
        out_specs=[plane_spec] * 4 + [stats_spec],
        scratch_shapes=[
            pltpu.VMEM((n_tiles, node_tile), jnp.int32),
            pltpu.VMEM((n_tiles, node_tile), jnp.int32),
            pltpu.VMEM((n_tiles, node_tile), jnp.int32),
            pltpu.SMEM((8,), jnp.int32),
        ],
    )
    out_shape = (
        [jax.ShapeDtypeStruct((n_tiles, node_tile), jnp.int32)] * 4
        + [jax.ShapeDtypeStruct((k_fused + 1, STATS_W), jnp.int32)]
    )
    kern = functools.partial(_mega_kernel, node_tile=node_tile,
                             edge_block=edge_block)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(block_tile, params, d, c, p, rw0, rc, rp, frozen, front,
      edge_slabs(bsrc, edge_block), edge_slabs(bdst, edge_block),
      edge_slabs(bw, edge_block), edge_slabs(bmask, edge_block))


def megakernel_growth_loop(
    state,
    bsrc, bdst, bw, bmask, block_tile,
    delta, half_target, num_it,
    n_tiles: int, node_tile: int, edge_block: int,
    k_fused: int, interpret: bool, variant: str,
):
    """PartialGrowth where the while_loop body is one FUSED K-superstep
    kernel call instead of one superstep.

    Byte-identical to ``growth_loop`` + ``edge_relax_ref``: the kernel
    evaluates the same per-superstep stopping condition on chip, so early
    stop/quiescence freezes the remaining fused slots. Traceable — the
    engine calls this from inside its jitted stage program.

    Returns ``(state, GrowthStats)`` with the kernel-level counters
    (``kernel_launches``, ``kernel_supersteps``, ``dead_blocks``) filled in.
    """
    from repro.core.delta_growing import GrowthStats
    from repro.core.state import relay_planes

    rw0, rc, rp, frozen = relay_planes(state)
    shape2 = (n_tiles, node_tile)
    r2 = lambda x: x.reshape(shape2)
    froz2 = frozen.astype(jnp.int32).reshape(shape2)
    planes_const = (r2(rw0), r2(rc), r2(rp), froz2)
    stop_flag = jnp.int32(1 if variant == "stop" else 0)
    zeros3 = jnp.zeros((3,), jnp.int32)

    def body(carry):
        d2, c2, p2, fr, steps, _, launches, dead, _, _ = carry
        params = jnp.concatenate([
            jnp.stack([jnp.int32(delta), jnp.int32(half_target),
                       jnp.int32(num_it), steps, stop_flag]), zeros3])
        d2, c2, p2, fr, stats = fused_grow_supersteps(
            d2, c2, p2, *planes_const, fr, bsrc, bdst, bw, bmask,
            block_tile, params, k_fused=k_fused, n_tiles=n_tiles,
            node_tile=node_tile, edge_block=edge_block, interpret=interpret)
        summ = stats[k_fused]
        return (d2, c2, p2, fr, steps + summ[COL_EXECUTED],
                summ[COL_CONT] == 1, launches + 1, dead + summ[COL_DEAD],
                summ[COL_REACHED], summ[COL_CHANGED])

    init = (r2(state.d), r2(state.c), r2(state.pathw),
            jnp.ones(shape2, jnp.int32), jnp.int32(0), jnp.bool_(True),
            jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(1))
    (d2, c2, p2, _, steps, _, launches, dead, reached,
     changed) = jax.lax.while_loop(lambda cr: cr[5], body, init)
    new_state = state._replace(d=d2.reshape(-1), c=c2.reshape(-1),
                               pathw=p2.reshape(-1))
    return new_state, GrowthStats(
        steps=steps, reached=reached, changed_last=changed == 1,
        kernel_launches=launches, kernel_supersteps=steps, dead_blocks=dead)

"""Pallas TPU flash attention (forward) for the LM archs.

Online-softmax blocked attention (FlashAttention recomputation-free forward),
adapted to the TPU memory hierarchy: q/k/v tiles staged HBM->VMEM by
BlockSpecs, the (bq x bk) score tile lives only in VMEM/VREGs, MXU does both
GEMMs per tile. Supports the variants the assigned archs need:

  * GQA            (kv-head block index = q-head // group)
  * causal masking (+ dynamic q_offset for decode: query at cache position)
  * sliding window (mistral / gemma2 alternating-local layers)
  * logit softcap  (gemma2: cap * tanh(s / cap))
  * dynamic kv_len (decode against a partially filled cache)

Grid: (B, Hq, Sq/bq, Skv/bk); kv is the innermost "arbitrary" dim so the
running (m, l, acc) scratch carries across kv tiles of one query tile.
Fully-masked kv tiles short-circuit via @pl.when (no MXU work; the DMA cost
of skipped K/V tiles is noted in DESIGN.md as the known gap vs a fused
iteration-space — hillclimbed in §Perf by block-pruned index maps).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 512
DEFAULT_BK = 512


def _flash_kernel(
    # scalar prefetch: [0] kv_len, [1] q_offset
    meta,                       # int32 [2]
    q_ref, k_ref, v_ref,        # [1, 1, bq, D], [1, 1, bk, D] x2
    o_ref,                      # [1, 1, bq, D]
    m_scr, l_scr, acc_scr,      # VMEM scratch: [bq,128], [bq,128], [bq,D]
    *,
    bq: int,
    bk: int,
    causal: bool,
    window: int,
    softcap: float,
    scale: float,
):
    neg_inf = jnp.float32(-1e30)
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    n_kb = pl.num_programs(3)
    kv_len = meta[0]
    q_off = meta[1]

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, neg_inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # global positions of this tile's queries / keys
    q_pos = q_off + qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # tile-level pruning: skip tiles with no unmasked entry
    first_q = q_off + qb * bq
    last_q = first_q + bq - 1
    first_k = kb * bk
    live = first_k < kv_len
    if causal:
        live &= first_k <= last_q
    if window > 0:
        live &= (first_q - (first_k + bk - 1)) < window

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [bq, bk]
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = k_pos < kv_len
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, neg_inf)

        m_prev = m_scr[:, :1]                              # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)          # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # [bq, bk]
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                    # [bq, 1]
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [bq, D]
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kb == n_kb - 1)
    def _emit():
        l = l_scr[:, :1]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "scale", "bq", "bk", "interpret",
    ),
)
def flash_attention_pallas(
    q: jnp.ndarray,            # [B, Hq, Sq, D]; Sq padded to multiple of bq
    k: jnp.ndarray,            # [B, Hkv, Skv, D]; Skv padded to multiple of bk
    v: jnp.ndarray,
    kv_len: jnp.ndarray,       # int32 [] — valid kv prefix (Skv when full)
    q_offset: jnp.ndarray,     # int32 [] — global position of q[:, :, 0]
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    scale = float(scale if scale is not None else D ** -0.5)
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)

    meta = jnp.stack([kv_len.astype(jnp.int32), q_offset.astype(jnp.int32)])

    grid = (B, Hq, Sq // bq, Skv // bk)
    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, qb, kb, m: (b, h, qb, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bk, D), lambda b, h, qb, kb, m: (b, h // group, kb, 0)
    )
    o_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, qb, kb, m: (b, h, qb, 0))

    kern = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, window=window,
        softcap=softcap, scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        scratch_shapes=[  # pallas: bq <= seq block, footprint bounded by block sizing above
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
    )
    # pallas: attention blocks are lane-padded by the caller, not the graph tiler
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(meta, q, k, v)

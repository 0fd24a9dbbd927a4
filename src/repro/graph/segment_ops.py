"""Segment reductions with the paper's tie-break semantics.

A Delta-growing step (paper Section 3) updates node v from edge (u, v) with
  candidate d = d_u + w(u,v), candidate center c = c_u
choosing, per v, the candidate with the *smallest d, then smallest center
index*. We realize this lexicographic argmin with a cascade of segment_min
passes (TPU/int64-free). A third pass carries the realized-path weight
(`pathw`) of the winning candidate, used for exact cluster radii and quotient
edge weights (see DESIGN.md Section 5.2).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# numpy scalars, not jax arrays: a jax array closed over by a traced
# function is copied device->host when the program is lowered
INF = np.int32(2**31 - 1)


def _sentinel(x: jnp.ndarray):
    """Dtype-matched masking sentinel for the tie-break passes.

    For int32 this is exactly the engine's INF; for wider integer dtypes
    (the quotient pass coalesces int64 weights) it is the dtype max, so a
    masked-out candidate can never beat a real one.
    """
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)
    return jnp.asarray(jnp.iinfo(x.dtype).max, x.dtype)


@partial(jax.jit, static_argnames=("num_segments",))
def segment_min_pair(
    cand_d: jnp.ndarray,
    cand_c: jnp.ndarray,
    seg: jnp.ndarray,
    num_segments: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Lexicographic (d, c) segment-min. Returns per-segment (d_min, c_min)."""
    d_min = jax.ops.segment_min(cand_d, seg, num_segments=num_segments)
    is_winner = cand_d == d_min[seg]
    c_masked = jnp.where(is_winner, cand_c, _sentinel(cand_c))
    c_min = jax.ops.segment_min(c_masked, seg, num_segments=num_segments)
    return d_min, c_min


@partial(jax.jit, static_argnames=("num_segments",))
def segment_min_triple(
    cand_d: jnp.ndarray,
    cand_c: jnp.ndarray,
    cand_p: jnp.ndarray,
    seg: jnp.ndarray,
    num_segments: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(d, c, pathw) lexicographic segment-min (three chained passes)."""
    d_min = jax.ops.segment_min(cand_d, seg, num_segments=num_segments)
    w1 = cand_d == d_min[seg]
    c_min = jax.ops.segment_min(
        jnp.where(w1, cand_c, _sentinel(cand_c)), seg, num_segments=num_segments)
    w2 = w1 & (cand_c == c_min[seg])
    p_min = jax.ops.segment_min(
        jnp.where(w2, cand_p, _sentinel(cand_p)), seg, num_segments=num_segments)
    return d_min, c_min, p_min


def relax_candidates(
    d_src: jnp.ndarray,
    w: jnp.ndarray,
    active_src: jnp.ndarray,
    light: jnp.ndarray,
) -> jnp.ndarray:
    """Per-edge candidate distances; INF where the relaxation is inadmissible.

    ``d_src`` values at INF are masked *before* the add, so int32 arithmetic
    never overflows (admissible d_src < Delta <= 2^30 and w < 2^30).
    """
    ok = active_src & light
    return jnp.where(ok, jnp.where(ok, d_src, 0) + w, INF)


@partial(jax.jit, static_argnames=("num_segments", "agg"))
def segment_aggregate(values: jnp.ndarray, seg: jnp.ndarray, num_segments: int, agg: str = "sum"):
    """Shared GNN aggregation entry point (sum/mean/max/min)."""
    if agg == "sum":
        return jax.ops.segment_sum(values, seg, num_segments=num_segments)
    if agg == "mean":
        s = jax.ops.segment_sum(values, seg, num_segments=num_segments)
        ones = jnp.ones(values.shape[:1] + (1,) * (values.ndim - 1), dtype=values.dtype)
        cnt = jax.ops.segment_sum(jnp.broadcast_to(ones, values.shape[:1] + (1,) * (values.ndim - 1)), seg, num_segments=num_segments)
        return s / jnp.maximum(cnt, 1)
    if agg == "max":
        return jax.ops.segment_max(values, seg, num_segments=num_segments)
    if agg == "min":
        return jax.ops.segment_min(values, seg, num_segments=num_segments)
    raise ValueError(f"unknown agg {agg!r}")

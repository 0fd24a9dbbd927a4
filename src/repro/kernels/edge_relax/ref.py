"""Pure-jnp oracle for the Δ-growing edge relaxation (paper Section 3).

Semantics (identical to core/distributed._relax_local, restated standalone so
the kernel test suite depends only on this file):

Per edge e = (src, dst, w), with pre-gathered source planes:
  live candidate   d_src + w      when d_src < Δ and w < Δ       (light edge)
  relay candidate  max(w+rw0, 0)  when rw0 < BIG and that value < Δ
                                  (covered source relays its center's wave
                                  with the contraction rescaling folded in)
Relay beats live on the same edge (a covered source has no live wave).

Per destination node: lexicographic (d, c, pathw) tuple-min over incident
edges — smallest distance, then smallest center id (the paper's tie-break),
then the realized original-graph path weight of that winner.
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
BIG = np.int32(2**30)


def edge_relax_candidates(
    d_src: jnp.ndarray,
    c_src: jnp.ndarray,
    p_src: jnp.ndarray,
    rw0_src: jnp.ndarray,
    rc_src: jnp.ndarray,
    rp_src: jnp.ndarray,
    w: jnp.ndarray,
    mask: jnp.ndarray,
    delta: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    live_ok = (d_src < delta) & (w < delta) & mask
    live_d = jnp.where(live_ok, jnp.where(live_ok, d_src, 0) + w, INF)
    w_red = jnp.maximum(w + jnp.where(rw0_src >= BIG, BIG, rw0_src), 0)
    relay_ok = (rw0_src < BIG) & (w_red < delta) & mask
    cand_d = jnp.where(relay_ok, w_red, live_d)
    cand_c = jnp.where(relay_ok, rc_src, jnp.where(live_ok, c_src, INF))
    p_base = jnp.where(relay_ok, rp_src, jnp.where(live_ok, p_src, 0))
    p_safe = jnp.where(p_base >= BIG, 0, p_base)
    cand_p = jnp.where(relay_ok | live_ok, p_safe + w, INF)
    return cand_d, cand_c, cand_p


@partial(jax.jit, static_argnames=("n_nodes",))
def edge_relax_ref(
    d_src: jnp.ndarray,
    c_src: jnp.ndarray,
    p_src: jnp.ndarray,
    rw0_src: jnp.ndarray,
    rc_src: jnp.ndarray,
    rp_src: jnp.ndarray,
    w: jnp.ndarray,
    dst: jnp.ndarray,
    mask: jnp.ndarray,
    delta: jnp.ndarray,
    n_nodes: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns per-node (d_min, c_min, p_min); INF where no candidate."""
    cand_d, cand_c, cand_p = edge_relax_candidates(
        d_src, c_src, p_src, rw0_src, rc_src, rp_src, w, mask, delta
    )
    d_min = jax.ops.segment_min(cand_d, dst, num_segments=n_nodes)
    w1 = cand_d == d_min[dst]
    c_min = jax.ops.segment_min(jnp.where(w1, cand_c, INF), dst, num_segments=n_nodes)
    w2 = w1 & (cand_c == c_min[dst])
    p_min = jax.ops.segment_min(jnp.where(w2, cand_p, INF), dst, num_segments=n_nodes)
    # nodes with no candidate at all keep INF in all three planes
    return d_min, c_min, p_min

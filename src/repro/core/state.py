"""Engine state for the weighted decomposition (paper Alg. 1/2).

Per-node arrays (all int32 unless noted):

  in-stage (reset when a new batch of centers is sampled):
    d       tentative distance in the *reduced* graph from the owning center
    c       tentative center id (INF = unassigned)
    pathw   realized path weight from the center in the ORIGINAL graph along
            the relaxation tree (exact upper bound on dist(c_u, u))

  persistent:
    final_c     cluster assignment (INF until covered)
    final_pathw dist-from-center upper bound frozen at cover time
    offset      for covered nodes: d_at_cover - Delta_at_cover  (paper's
                reduced-edge rescaling w(u,v) - (Delta - d_u), Section 3);
                0 otherwise. May be negative.
    covered     bool: assigned in a previous stage (frozen, emits as relay)
    is_center   bool: permanent cluster center (paper: C_{i+1} = X superset C_i)

The contraction G^reduced(Delta) is realized *semantically*: covered nodes
relay their center's wave with the rescaled weight folded in; centers always
sit at d = 0, so a relay edge (u,v) re-expands a contracted cluster in a
single growing step, exactly like the paper's contracted edge (c_u, v).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# numpy scalars, not jax arrays: a jax array closed over by a traced
# function is copied device->host when the program is lowered
INF = np.int32(2**31 - 1)


class EngineState(NamedTuple):
    d: jnp.ndarray
    c: jnp.ndarray
    pathw: jnp.ndarray
    final_c: jnp.ndarray
    final_pathw: jnp.ndarray
    offset: jnp.ndarray
    covered: jnp.ndarray
    is_center: jnp.ndarray

    @property
    def n(self) -> int:
        return self.d.shape[0]


def init_state(n_nodes: int) -> EngineState:
    z = jnp.zeros(n_nodes, dtype=jnp.int32)
    inf = jnp.full(n_nodes, INF, dtype=jnp.int32)
    f = jnp.zeros(n_nodes, dtype=bool)
    return EngineState(d=inf, c=inf, pathw=inf, final_c=inf, final_pathw=inf,
                       offset=z, covered=f, is_center=f)


def pad_state(state: EngineState, n_pad: int) -> EngineState:
    """Pad the canonical planes to ``n_pad`` slots.

    Tail slots are inert permanent centers: they are never sampled
    (``eligible`` excludes centers), never receive updates (receivers are
    non-centers), never counted (uncovered/reached counts exclude centers),
    and never emit candidates (every padded edge is masked by its backend).
    This is done ONCE per decomposition — backends keep the padded state
    device-resident across all stages.
    """
    n = state.n
    if n_pad == n:
        return state
    if n_pad < n:
        raise ValueError(f"n_pad {n_pad} < n {n}")

    def padto(x, fill):
        return jnp.concatenate([x, jnp.full((n_pad - n,), fill, x.dtype)])

    return EngineState(
        d=padto(state.d, INF),
        c=padto(state.c, INF),
        pathw=padto(state.pathw, INF),
        final_c=padto(state.final_c, INF),
        final_pathw=padto(state.final_pathw, INF),
        offset=padto(state.offset, 0),
        covered=padto(state.covered, False),
        is_center=padto(state.is_center, True),
    )


def relay_planes(state: EngineState):
    """Branch-free relay candidate planes ``(rw0, rc, rp, frozen)``.

    Covered nodes relay their center's wave with the contraction rescaling
    (``offset``) folded in; everyone else gets an additive-safe BIG so the
    relay branch is inadmissible. ``frozen`` marks nodes that never receive
    updates. These planes only change at ``cover()`` time, so backends derive
    them once per grow call (cheap elementwise ops that stay on device).
    """
    big = jnp.int32(2**30)
    relay = state.covered
    rw0 = jnp.where(relay, state.offset, big)
    rc = jnp.where(relay, state.final_c, INF)
    rp = jnp.where(relay, state.final_pathw, INF)
    frozen = state.covered | state.is_center
    return rw0, rc, rp, frozen


def promote_centers(state: EngineState, new_centers: jnp.ndarray) -> EngineState:
    """Mark ``new_centers`` (bool mask) as permanent centers with state
    (self, 0). Centers self-assign: final_c = self, final_pathw = 0."""
    ids = jnp.arange(state.n, dtype=jnp.int32)
    sel = new_centers & ~state.is_center & ~state.covered
    return state._replace(
        d=jnp.where(sel, 0, state.d),
        c=jnp.where(sel, ids, state.c),
        pathw=jnp.where(sel, 0, state.pathw),
        final_c=jnp.where(sel, ids, state.final_c),
        final_pathw=jnp.where(sel, 0, state.final_pathw),
        is_center=state.is_center | sel,
    )


def promote_centers_shifted(state: EngineState, new_centers: jnp.ndarray,
                            start_d: jnp.ndarray) -> EngineState:
    """One-shot mode promote: centers enter the wave at ``d = start_d``
    (the exponential start shift folded into the initial distance, MPVX
    style) instead of 0. ``pathw`` still starts at 0, so ``final_pathw``
    remains a realized path weight from the owning center — the radius
    certificate is identical to the staged engine's."""
    ids = jnp.arange(state.n, dtype=jnp.int32)
    sel = new_centers & ~state.is_center & ~state.covered
    return state._replace(
        d=jnp.where(sel, start_d, state.d),
        c=jnp.where(sel, ids, state.c),
        pathw=jnp.where(sel, 0, state.pathw),
        final_c=jnp.where(sel, ids, state.final_c),
        final_pathw=jnp.where(sel, 0, state.final_pathw),
        is_center=state.is_center | sel,
    )


def reset_in_stage(state: EngineState) -> EngineState:
    """Reset in-stage wave state: centers at (self,0), others unreached.

    Used at the start of a stage (a new PartialGrowth call in the paper).
    Covered nodes keep final_* / offset and never receive updates.
    """
    ids = jnp.arange(state.n, dtype=jnp.int32)
    is_c = state.is_center
    return state._replace(
        d=jnp.where(is_c, 0, INF),
        c=jnp.where(is_c, ids, INF),
        pathw=jnp.where(is_c, 0, INF),
    )


def cover(state: EngineState, delta: jnp.ndarray) -> EngineState:
    """Freeze every uncovered non-center node with in-stage d < delta
    (paper: ``Assign each u in V' to the cluster centered at c_u``) and fold
    the reduction rescaling into its relay offset."""
    newly = (~state.covered) & (~state.is_center) & (state.d < delta)
    return state._replace(
        final_c=jnp.where(newly, state.c, state.final_c),
        final_pathw=jnp.where(newly, state.pathw, state.final_pathw),
        offset=jnp.where(newly, state.d - delta, state.offset),
        covered=state.covered | newly,
    )


def uncovered_count(state: EngineState) -> jnp.ndarray:
    return jnp.sum((~state.covered) & (~state.is_center))


def finalize_singletons(state: EngineState) -> EngineState:
    """Remaining uncovered nodes become singleton clusters centered at
    themselves (last line of Alg. 1)."""
    ids = jnp.arange(state.n, dtype=jnp.int32)
    rem = (~state.covered) & (~state.is_center)
    return state._replace(
        final_c=jnp.where(rem, ids, state.final_c),
        final_pathw=jnp.where(rem, 0, state.final_pathw),
        is_center=state.is_center | rem,
    )

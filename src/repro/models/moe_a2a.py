"""Explicit all-to-all expert parallelism (shard_map), the production path.

GSPMD cannot shard a scatter whose destination dim ('expert') is indexed by
data-dependent values: it materializes the full [E, C, d] dispatch buffer on
every data rank and reduce-scatters it (measured 891 GB wire/chip at
moonshot/train_4k even after constraint pinning). This module hand-writes
what the hardware should do — the DeepSeek/MaxText dispatch:

  1. tokens are already sharded over EVERY mesh axis (the residual stream is
     sequence-sharded over 'model' by act_spec);
  2. each chip routes its local tokens, sorts the (token, choice) pairs by
     destination model-rank, and packs a [M, C_s, d] send buffer;
  3. one all_to_all over 'model' delivers tokens to their experts' owner;
  4. the owner runs its E/M experts as dense local GEMMs (position-in-expert
     sort again, all chip-local);
  5. the reverse all_to_all returns expert outputs to the token owners, which
     combine with their locally-kept gates.

Wire bytes per chip per layer = 2 x (M-1)/M x C_s x M x d x 2B (+ the same in
bwd) — activations only, no replication. Differentiable end-to-end (a2a
transposes to a2a; scatters/gathers are local).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P



def _pack_by_destination(h, flat_dest, tok_idx, n_dest, cap, keep_extra=None):
    """Sort (token, choice) pairs by destination, pack into [n_dest, cap, d].
    Returns (buffer, slot, keep). Dropped pairs write to a pad column."""
    n = flat_dest.shape[0]
    order = jnp.argsort(flat_dest, stable=True)
    sorted_d = flat_dest[order]
    seg_start = jnp.searchsorted(sorted_d, jnp.arange(n_dest, dtype=flat_dest.dtype))
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - seg_start[sorted_d].astype(jnp.int32)
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < cap
    if keep_extra is not None:
        keep = keep & keep_extra
    slot = jnp.where(keep, pos, cap)              # pad column
    buf = jnp.zeros((n_dest, cap + 1, h.shape[-1]), h.dtype)
    buf = buf.at[flat_dest, slot].add(h[tok_idx] * keep.astype(h.dtype)[:, None])
    return buf[:, :cap], slot, keep


def moe_ffn_a2a(
    mesh: Mesh,
    x2d: jnp.ndarray,          # [T, d] tokens (sharded over ALL axes outside)
    exp_idx: jnp.ndarray,      # [T, k] global expert ids
    gate_vals: jnp.ndarray,    # [T, k] f32
    w_gate: jnp.ndarray,       # [E, d, f]
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,       # [E, f, d]
    act_fn,
    capacity_factor: float = 1.25,
    model_axis: str = "model",
) -> jnp.ndarray:
    T, d = x2d.shape
    E, _, f = w_gate.shape
    k = exp_idx.shape[1]
    flat = tuple(mesh.axis_names)
    M = mesh.shape[model_axis]

    if E < M:
        # VIRTUAL EXPERTS (mixtral: 8 experts on a 16-wide axis): each
        # expert's FFN width splits across v ranks; a token sends one copy
        # per f-shard and the combine's existing sum adds the partials —
        # exact TP-within-expert, expressed as EP so the same a2a works.
        assert M % E == 0, (E, M)
        v = M // E
        f2 = f // v
        w_gate = jnp.concatenate(
            [w_gate[:, :, i * f2:(i + 1) * f2] for i in range(v)], axis=0)
        w_up = jnp.concatenate(
            [w_up[:, :, i * f2:(i + 1) * f2] for i in range(v)], axis=0)
        w_down = jnp.concatenate(
            [w_down[:, i * f2:(i + 1) * f2, :] for i in range(v)], axis=0)
        exp_idx = jnp.concatenate(
            [exp_idx + i * E for i in range(v)], axis=1)      # [T, k*v]
        gate_vals = jnp.concatenate([gate_vals] * v, axis=1)
        E, f, k = E * v, f2, k * v

    E_loc = E // M
    n_chips = int(np.prod([mesh.shape[a] for a in flat]))
    Tl = T // n_chips
    C_s = max(int(math.ceil(Tl * k / M * capacity_factor)), 4)
    C_e = max(int(math.ceil(M * C_s / E_loc * capacity_factor)), 4)

    def body(h, exp, gate, wg, wu, wd):
        # h [Tl, d]; exp/gate [Tl, k]; wg/wu [E_loc, d, f]; wd [E_loc, f, d]
        dest = (exp // E_loc).reshape(-1)               # [Tl*k] model rank
        e_loc = (exp % E_loc).reshape(-1)
        tok_idx = jnp.repeat(jnp.arange(Tl, dtype=jnp.int32), k)

        send_x, slot, keep = _pack_by_destination(h, dest, tok_idx, M, C_s)
        # expert-id metadata travels in its own (tiny) a2a
        e_buf = jnp.full((M, C_s + 1), E_loc, jnp.int32)  # E_loc = invalid
        e_buf = e_buf.at[dest, slot].set(
            jnp.where(keep, e_loc, E_loc).astype(jnp.int32))
        e_send = e_buf[:, :C_s]

        recv_x = jax.lax.all_to_all(send_x, model_axis, 0, 0, tiled=True)
        recv_e = jax.lax.all_to_all(e_send, model_axis, 0, 0, tiled=True)

        # ---- local expert compute --------------------------------------
        fx = recv_x.reshape(M * C_s, d)
        fe = recv_e.reshape(M * C_s)
        valid = fe < E_loc
        # invalid slots get their own destination bucket (E_loc) so padding
        # cannot crowd out the last expert's capacity
        x_disp_all, slot2, keep2 = _pack_by_destination(
            fx, jnp.where(valid, fe, E_loc).astype(jnp.int32),
            jnp.arange(M * C_s, dtype=jnp.int32), E_loc + 1, C_e,
            keep_extra=valid)
        x_disp = x_disp_all[:E_loc]
        g = act_fn(jnp.einsum("ecd,edf->ecf", x_disp, wg))
        u = jnp.einsum("ecd,edf->ecf", x_disp, wu)
        y = jnp.einsum("ecf,efd->ecd", g * u, wd)       # [E_loc, C_e, d]
        y_pad = jnp.concatenate(
            [y, jnp.zeros((E_loc, 1, d), y.dtype)], axis=1)
        fe_safe = jnp.where(valid, fe, 0)
        y_rows = y_pad[fe_safe, jnp.where(keep2, slot2, C_e)]  # [M*C_s, d]
        y_back = y_rows.reshape(M, C_s, d)

        back = jax.lax.all_to_all(y_back, model_axis, 0, 0, tiled=True)
        back_pad = jnp.concatenate(
            [back, jnp.zeros((M, 1, d), back.dtype)], axis=1)
        y_tok = back_pad[dest, jnp.where(keep, slot, C_s)]     # [Tl*k, d]
        y_tok = y_tok * (gate.reshape(-1) * keep.astype(jnp.float32))[:, None]
        return jax.ops.segment_sum(y_tok, tok_idx, num_segments=Tl)

    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(flat, None), P(flat, None), P(flat, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=P(flat, None),
        check_vma=False,
    )(x2d, exp_idx, gate_vals, w_gate, w_up, w_down)
    return out

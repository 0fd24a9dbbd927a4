"""Pipeline parallelism over the 'pod' axis (GPipe via collective_permute).

For multi-pod meshes the default is DP over 'pod'; this module provides the
alternative: each pod owns a contiguous block of layers, microbatches stream
through pods with ppermute handoffs — the cross-pod DCI link then carries
activations (B_micro x S x D) instead of a full gradient all-reduce, which
wins when params >> activations (the usual regime for the big LM archs; the
trade is quantified in EXPERIMENTS.md §Perf).

shard_map formulation: the layer-stacked params [L, ...] shard their L axis
over 'pod' (each pod holds L/P layers). One pipeline step runs the classic
GPipe schedule: n_micro + n_stage - 1 ticks; tick t has stage s processing
microbatch t - s. Activations hop stages via ppermute; the bubble fraction
(n_stage - 1)/(n_micro + n_stage - 1) is the known GPipe overhead.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def gpipe_forward(
    mesh: Mesh,
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    n_micro: int,
    pod_axis: str = "pod",
):
    """Build a pipelined forward: params_stacked [P_stages, ...] x [B, ...].

    stage_fn(stage_params, x) -> x : one pod's chunk of the network.
    Returns fn(params_stacked, batch) -> out with batch split into n_micro
    microbatches along axis 0.
    """
    n_stage = mesh.shape[pod_axis]

    def pipelined(stage_params, batch):
        # inside shard_map: stage_params is this pod's slice (leading dim 1)
        sp = jax.tree.map(lambda x: x[0], stage_params)
        stage = jax.lax.axis_index(pod_axis)
        micro = jnp.split(batch, n_micro, axis=0)
        micro = jnp.stack(micro)                      # [M, mB, ...]
        m_shape = micro.shape[1:]

        n_tick = n_micro + n_stage - 1
        fwd_perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

        def tick(carry, t):
            buf, outs = carry                          # buf: [mB, ...] in-flight
            mb_idx = t - stage                         # microbatch at this stage
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            # stage 0 ingests a fresh microbatch; others take the handoff
            take = jnp.clip(mb_idx, 0, n_micro - 1)
            x_in = jnp.where(stage == 0, micro[take], buf)
            y = stage_fn(sp, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage emits; others pass along the ring
            out_idx = t - (n_stage - 1)
            emit = (stage == n_stage - 1) & active
            outs = jax.lax.cond(
                (out_idx >= 0) & (out_idx < n_micro),
                lambda o: o.at[jnp.clip(out_idx, 0, n_micro - 1)].set(
                    jnp.where(emit, y, o[jnp.clip(out_idx, 0, n_micro - 1)])
                ),
                lambda o: o,
                outs,
            )
            nxt = jax.lax.ppermute(y, pod_axis, fwd_perm)
            return (nxt, outs), None

        buf0 = jnp.zeros(m_shape, batch.dtype)
        outs0 = jnp.zeros((n_micro,) + m_shape, batch.dtype)
        (_, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_tick, dtype=jnp.int32)
        )
        # every pod holds the last stage's emissions only on the last pod;
        # broadcast so outputs are replicated over 'pod'
        outs = jax.lax.all_gather(outs, pod_axis)[n_stage - 1]
        return outs.reshape((-1,) + m_shape[1:])

    other_axes = tuple(a for a in mesh.axis_names if a != pod_axis)

    def run(params_stacked, batch):
        return jax.shard_map(
            pipelined,
            mesh=mesh,
            in_specs=(P(pod_axis), P(other_axes[0] if other_axes else None)),
            out_specs=P(other_axes[0] if other_axes else None),
            check_vma=False,
        )(params_stacked, batch)

    return run


def stage_split(params_layers, n_stage: int):
    """Reshape layer-stacked params [L, ...] -> [n_stage, L/n_stage, ...]."""
    def f(x):
        L = x.shape[0]
        assert L % n_stage == 0, (L, n_stage)
        return x.reshape(n_stage, L // n_stage, *x.shape[1:])
    return jax.tree.map(f, params_layers)

"""Compiles for a described (not attached) TPU v5e at the shapes
``chip_smoke.py`` runs, so a kernel or program the chip's compiler refuses
fails here without a chip. Nothing runs: these say nothing about results or
times.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler's library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# chip_smoke.py phase A: road_like(2**18, seed=0) has 1,572,852 arcs, which
# block_edges_host(..., node_tile=256, edge_block=512) lays out as 3,073
# edge blocks over 1,025 node tiles; its decomposition (session default tau)
# has 4,029 clusters and 10,720 quotient arcs, padded to 16 and 128
N_SCALE = 1 << 18
N_BLOCKS = 3_073
N_TILES = 1_025
NODE_TILE, EDGE_BLOCK = 256, 512
K_PAD = 4_032
M_PAD = 10_752


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler library here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_edge_relax_kernel_compiles_at_smoke_scale(one_chip):
    from repro.kernels.edge_relax.kernel import _edge_relax_pallas_jit

    # the [n_blocks, 1, edge_block] slabs PallasBackend keeps resident
    e = _spec(one_chip, (N_BLOCKS, 1, EDGE_BLOCK))
    compiled = _edge_relax_pallas_jit.lower(
        e, e, e, e, e, e, e, e, e,
        _spec(one_chip, (N_BLOCKS,)), _spec(one_chip, (1,)),
        n_tiles=N_TILES, node_tile=NODE_TILE, edge_block=EDGE_BLOCK,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # nine int32 edge arrays in, three int32 node planes out, and no
    # relayout copy of any edge array
    assert mem.argument_size_in_bytes >= 9 * N_BLOCKS * EDGE_BLOCK * 4
    assert mem.temp_size_in_bytes < N_BLOCKS * EDGE_BLOCK * 4


def test_quotient_kernel_compiles_under_x64(one_chip):
    from repro.core.quotient import _quotient_kernel

    e = _spec(one_chip, (N_BLOCKS * EDGE_BLOCK,))
    with jax.enable_x64(True):
        compiled = _quotient_kernel.lower(
            e, e, e, _spec(one_chip, (N_BLOCKS * EDGE_BLOCK,), jnp.bool_),
            _spec(one_chip, (N_SCALE,)), _spec(one_chip, (N_SCALE,)),
            n=N_SCALE,
        ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 12 * 2**30


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.int64])
def test_quotient_solve_loop_compiles_under_x64(one_chip, dtype):
    from repro.core.sssp import batched_bf_loop

    with jax.enable_x64(True):
        q = _spec(one_chip, (M_PAD,))
        compiled = batched_bf_loop.lower(
            q, q, _spec(one_chip, (M_PAD,), dtype),
            _spec(one_chip, (K_PAD, K_PAD), dtype), _spec(one_chip, (), dtype),
            n_nodes=K_PAD,
        ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 12 * 2**30


# bench cell kron-g500: its largest component at scale 16
KRON_N, KRON_ARCS = 46_668, 1_819_286


def test_narrow_first_bellman_ford_compiles(one_chip):
    """The two programs of ``sssp.bf_from`` at the Kronecker cell's size:
    the int32 superstep keeps no arc-length temporary, and the widening
    program's int64 rerun adds int32 weights inside the add, so it holds
    no int64 weight copy besides its int64 gather."""
    from repro.core.sssp import _bf_loop, _widen_on_saturation

    arcs = _spec(one_chip, (KRON_ARCS,))
    plane = _spec(one_chip, (KRON_N,))
    scalar = _spec(one_chip, ())
    with jax.enable_x64(True):
        loop = _bf_loop.lower(arcs, arcs, arcs, plane, scalar,
                              n_nodes=KRON_N).compile()
        widen = _widen_on_saturation.lower(arcs, arcs, arcs, plane, scalar,
                                           scalar, n_nodes=KRON_N).compile()
    assert loop.memory_analysis().temp_size_in_bytes < KRON_ARCS * 4
    assert widen.memory_analysis().temp_size_in_bytes < 2 * KRON_ARCS * 8

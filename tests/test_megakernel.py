"""Persistent fused megakernel vs the unfused growth loop: byte-identical
(d, c, pathw) planes AND identical GrowthStats on every problem, interpret
mode on CPU (``ref.py``-backed ``growth_loop`` is the oracle)."""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.backend import PallasBackend, SingleDeviceBackend
from repro.core.engine import run_cluster
from repro.graph.structures import EdgeList
from repro.kernels.edge_relax.kernel import (
    validate_block_tile,
    validate_tiling,
)
from repro.kernels.edge_relax.megakernel import fits_vmem, vmem_footprint_bytes

INF, BIG = 2**31 - 1, 2**30


def _random_edges(n, e, wmax, seed):
    r = np.random.default_rng(seed)
    return EdgeList(
        n,
        r.integers(0, n, e).astype(np.int32),
        r.integers(0, n, e).astype(np.int32),
        r.integers(1, wmax + 1, e).astype(np.int32),
    )


def _seed_growth_state(backend, seed, center_frac=0.05, covered_frac=0.2,
                       wmax=100):
    """A mid-decomposition state on the backend's padded layout: some
    permanent centers (d=0 wavefronts), some covered relays with realistic
    offsets (including negative, the contraction rescaling), rest unreached."""
    r = np.random.default_rng(seed)
    st_ = backend.init_state()
    n, n_pad = backend.n_nodes, backend.n_pad
    roles = r.random(n)
    cen = roles < center_frac
    cen[0] = True  # at least one wave source
    cov = (roles >= center_frac) & (roles < center_frac + covered_frac)
    ids = np.arange(n_pad, dtype=np.int32)

    d = np.asarray(st_.d).copy(); c = np.asarray(st_.c).copy()
    p = np.asarray(st_.pathw).copy()
    fc = np.asarray(st_.final_c).copy()
    fp = np.asarray(st_.final_pathw).copy()
    off = np.asarray(st_.offset).copy()
    covered = np.asarray(st_.covered).copy()
    is_c = np.asarray(st_.is_center).copy()

    cen_idx = np.where(cen)[0]
    d[cen_idx] = 0; c[cen_idx] = cen_idx; p[cen_idx] = 0
    fc[cen_idx] = cen_idx; fp[cen_idx] = 0
    is_c[cen_idx] = True

    cov_idx = np.where(cov)[0]
    covered[cov_idx] = True
    fc[cov_idx] = r.choice(np.maximum(cen_idx, 0), cov_idx.size) \
        if cen_idx.size else 0
    fp[cov_idx] = r.integers(0, 4 * wmax, cov_idx.size)
    off[cov_idx] = r.integers(-wmax, 1, cov_idx.size)

    return st_._replace(
        d=jnp.asarray(d), c=jnp.asarray(c), pathw=jnp.asarray(p),
        final_c=jnp.asarray(fc), final_pathw=jnp.asarray(fp),
        offset=jnp.asarray(off), covered=jnp.asarray(covered),
        is_center=jnp.asarray(is_c))


def _assert_grow_parity(edges, delta, num_it, variant, seed, k_fused=4,
                        node_tile=256, edge_block=512):
    """fused (megakernel, interpret) vs unfused (ref growth_loop) on the
    SAME blocked layout and the SAME seeded state."""
    kw = dict(node_tile=node_tile, edge_block=edge_block)
    be_ref = PallasBackend(edges, impl="ref", **kw)
    be_mk = PallasBackend(edges, impl="interpret", fuse=k_fused, **kw)
    assert be_mk.fuse == k_fused
    st0 = _seed_growth_state(be_ref, seed)
    half = jnp.int32(max(edges.n_nodes // 2, 1))
    s1, g1 = be_ref.grow(st0, jnp.int32(delta), half, jnp.int32(num_it),
                         variant)
    s2, g2 = be_mk.grow(st0, jnp.int32(delta), half, jnp.int32(num_it),
                        variant)
    for name in ("d", "c", "pathw"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s1, name)), np.asarray(getattr(s2, name)),
            err_msg=f"plane {name} ({variant}, delta={delta})")
    assert int(g1.steps) == int(g2.steps)
    assert int(g1.reached) == int(g2.reached)
    assert bool(g1.changed_last) == bool(g2.changed_last)
    assert int(g2.kernel_launches) >= 1
    assert int(g2.kernel_supersteps) == int(g2.steps)
    return g2


# ---------------------------------------------------------------------------
# parity: random graphs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["stop", "complete"])
@pytest.mark.parametrize("n,e,wmax,delta", [
    (100, 400, 16, 40), (400, 1600, 100, 256), (700, 1500, 2**20, 2**21),
])
def test_megakernel_matches_growth_loop(n, e, wmax, delta, variant):
    edges = _random_edges(n, e, wmax, seed=n + e)
    _assert_grow_parity(edges, delta, num_it=24, variant=variant, seed=n)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(16, 300), e=st.integers(16, 900),
       wmax=st.sampled_from([3, 50, 1 << 16]), seed=st.integers(0, 999),
       k_fused=st.integers(1, 6),
       variant=st.sampled_from(["stop", "complete"]))
def test_megakernel_property(n, e, wmax, seed, k_fused, variant):
    edges = _random_edges(n, e, wmax, seed)
    _assert_grow_parity(edges, delta=2 * wmax, num_it=16, variant=variant,
                        seed=seed, k_fused=k_fused)


# ---------------------------------------------------------------------------
# parity: degenerate tilings and sentinel boundaries
# ---------------------------------------------------------------------------

def test_megakernel_single_node_tiles():
    # node_tile=1: every node is its own tile; every block is owned by one
    # node and the tile-straddling guard is exercised maximally
    edges = _random_edges(13, 60, 9, seed=7)
    _assert_grow_parity(edges, delta=20, num_it=16, variant="complete",
                        seed=7, k_fused=3, node_tile=1, edge_block=128)


def test_megakernel_all_padding_blocks():
    # 3 real edges over 300 nodes at edge_block=512: nearly every block is
    # pure phantom padding — the frontier must still converge and the
    # phantom slots stay inert
    src = np.array([0, 1, 2], np.int32)
    dst = np.array([1, 2, 0], np.int32)
    w = np.array([5, 7, 11], np.int32)
    edges = EdgeList(300, src, dst, w)
    g = _assert_grow_parity(edges, delta=64, num_it=16, variant="complete",
                            seed=3, k_fused=4)
    assert int(g.dead_blocks) > 0  # padding tiles are frontier-skipped


def test_megakernel_tile_straddling_boundary():
    # every edge lands on a tile-boundary destination (multiples of the
    # node_tile) — the local_dst arithmetic must keep them in-tile
    node_tile = 64
    n = 8 * node_tile
    r = np.random.default_rng(11)
    dst = (r.integers(0, 8, 500) * node_tile).astype(np.int32)
    src = r.integers(0, n, 500).astype(np.int32)
    w = r.integers(1, 50, 500).astype(np.int32)
    edges = EdgeList(n, src, dst, w)
    _assert_grow_parity(edges, delta=128, num_it=16, variant="stop", seed=11,
                        node_tile=node_tile, edge_block=128)


def test_megakernel_sentinel_boundaries():
    # weights at the top of the legal range (just under BIG=2^30) with a
    # delta beyond it: candidate arithmetic must not wrap past INF and the
    # BIG relay clamp must match the reference exactly
    r = np.random.default_rng(5)
    n, e = 64, 300
    w = np.concatenate([
        np.full(20, BIG - 1, np.int32),
        np.full(20, BIG - 2, np.int32),
        r.integers(1, 1000, e - 40).astype(np.int32)])
    edges = EdgeList(n, r.integers(0, n, e).astype(np.int32),
                     r.integers(0, n, e).astype(np.int32), w)
    for delta in (BIG - 1, BIG, 1000):
        _assert_grow_parity(edges, delta=delta, num_it=12, variant="complete",
                            seed=5, node_tile=64, edge_block=128)


# ---------------------------------------------------------------------------
# full-decomposition byte-identity
# ---------------------------------------------------------------------------

def test_fused_decomposition_matches_single_backend():
    edges = _random_edges(500, 2000, 100, seed=42)
    ref = run_cluster(edges, SingleDeviceBackend(edges), tau=8, seed=1)
    fused = run_cluster(edges,
                        PallasBackend(edges, impl="interpret", fuse=4),
                        tau=8, seed=1)
    np.testing.assert_array_equal(ref.final_c, fused.final_c)
    np.testing.assert_array_equal(ref.final_pathw, fused.final_pathw)
    assert ref.radius == fused.radius
    assert ref.growing_steps == fused.growing_steps
    m = fused.metrics
    assert m.kernel_launches > 0
    assert m.kernel_supersteps == fused.growing_steps
    assert ref.metrics.kernel_launches == 0  # unfused path stays at zero


# ---------------------------------------------------------------------------
# tiling validation (satellite: clean errors, not wrong answers)
# ---------------------------------------------------------------------------

def test_validate_tiling_rejects_bad_shapes():
    validate_tiling(256, 512)  # defaults pass
    validate_tiling(1, 128)    # degenerate-but-legal
    with pytest.raises(ValueError, match="multiple of 128"):
        validate_tiling(256, 100)
    with pytest.raises(ValueError, match="multiple of 128"):
        validate_tiling(256, 0)
    with pytest.raises(ValueError, match="power of two"):
        validate_tiling(96, 512)
    with pytest.raises(ValueError, match="power of two"):
        validate_tiling(0, 512)


def test_validate_block_tile_rejects_interleaved_map():
    validate_block_tile(np.array([0, 0, 1, 2, 2]), n_tiles=3)
    with pytest.raises(ValueError, match="monotone"):
        validate_block_tile(np.array([0, 1, 0]), n_tiles=2)
    with pytest.raises(ValueError, match="in \\[0, 2\\)"):
        validate_block_tile(np.array([0, 1, 2]), n_tiles=2)


def test_pallas_backend_rejects_bad_tiling():
    edges = _random_edges(50, 100, 9, seed=0)
    with pytest.raises(ValueError, match="multiple of 128"):
        PallasBackend(edges, impl="ref", edge_block=100)
    with pytest.raises(ValueError, match="power of two"):
        PallasBackend(edges, impl="ref", node_tile=100)


def test_megakernel_vmem_guard_falls_back_to_unfused(monkeypatch):
    from repro.kernels.edge_relax import megakernel

    edges = _random_edges(40, 80, 9, seed=0)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        be = PallasBackend(edges, impl="interpret", fuse=4)
    assert be.fuse == 4 and not rec  # small graph fits; no warning path
    assert fits_vmem(be.n_pad, 256, 512)
    assert not fits_vmem(10**9, 256, 512)
    assert vmem_footprint_bytes(10**9, 256, 512) > megakernel.VMEM_BUDGET_BYTES

    # an over-budget graph degrades to the unfused path with ONE warning,
    # not a crash mid-decomposition
    monkeypatch.setattr(megakernel, "fits_vmem", lambda *a, **k: False)
    with pytest.warns(RuntimeWarning, match="VMEM budget"):
        be2 = PallasBackend(edges, impl="interpret", fuse=4)
    assert be2.fuse == 0
    with pytest.raises(ValueError, match="fuse"):
        PallasBackend(edges, impl="interpret", fuse=-1)


@pytest.mark.parametrize("impl", ["pallas", "ref", "auto"])
def test_pallas_backend_rejects_fuse_outside_interpret(impl):
    """The TPU compiler refuses the megakernel's in-kernel 1-D gather, so
    fuse > 0 is legal only in interpret mode — on every platform."""
    edges = _random_edges(40, 80, 9, seed=0)
    with pytest.raises(ValueError, match="1-D gather"):
        PallasBackend(edges, impl=impl, fuse=4)


# ---------------------------------------------------------------------------
# dispatch: compiled Pallas off-TPU is an error, never a quiet ref fallback
# ---------------------------------------------------------------------------

def test_edge_relax_pallas_impl_falls_back_on_cpu():
    import jax

    from repro.kernels.edge_relax.ops import block_edges_host, edge_relax

    if jax.default_backend() == "tpu":
        pytest.skip("the error only engages off-TPU")
    r = np.random.default_rng(2)
    n, e = 100, 400
    src = r.integers(0, n, e).astype(np.int32)
    dst = r.integers(0, n, e).astype(np.int32)
    w = r.integers(1, 20, e).astype(np.int32)
    blk = block_edges_host(src, dst, w, n)
    n_pad = blk["n_pad_nodes"]
    d = np.full(n_pad, INF, np.int32); d[:5] = 0
    c = np.full(n_pad, INF, np.int32); c[:5] = np.arange(5)
    p = np.full(n_pad, INF, np.int32); p[:5] = 0
    rw0 = np.full(n_pad, BIG, np.int32)
    rc = np.full(n_pad, INF, np.int32)
    rp = np.full(n_pad, INF, np.int32)
    planes = tuple(jnp.asarray(x) for x in (d, c, p, rw0, rc, rp))
    args = (planes, jnp.asarray(blk["src"]), jnp.asarray(blk["dst"]),
            jnp.asarray(blk["w"]), jnp.asarray(blk["mask"]),
            jnp.asarray(blk["block_tile"]), jnp.int32(19), blk["n_tiles"])

    with pytest.raises(RuntimeError, match="needs a TPU"):
        edge_relax(*args, impl="pallas")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        PallasBackend(EdgeList(n, src, dst, w), impl="pallas")
    # the interpreted kernel still answers, identical to the oracle
    ref = edge_relax(*args, impl="ref")
    pal = edge_relax(*args, impl="interpret")
    for r_, p_ in zip(ref, pal):
        np.testing.assert_array_equal(np.asarray(r_), np.asarray(p_))

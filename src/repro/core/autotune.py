"""Graph-statistics autotuner for the decomposition pipeline knobs.

The pipeline's warm latency is dominated by a handful of knobs the paper
leaves to the operator: ``delta_init`` (first Δ-doubling rung), ``tau``
(center budget → quotient size), ``tau_solve``/``levels`` (cascade solve
budget — the bench's 460 → 151 solve-superstep win), and the Pallas kernel
tiling (``node_tile``/``edge_block``). This module derives all of them from
ONE cheap device pass over the edges:

  * degree + weight log2 histograms (32 buckets each), max degree, min/max
    weight — computed on device via ``graph/segment_ops.segment_aggregate``
    and fetched in a single packed int32 vector (one host sync);
  * ``derive_tuning`` turns the statistics into a ``TuningRecord``;
  * kernel tiling candidates are scored with the ``runtime/roofline.py``
    peaks of the chip (HBM stream time vs VPU match-matrix time), and
    ``validate_tuning`` re-checks the chosen tiling against the model and
    the kernel preconditions (``kernels/edge_relax/kernel.validate_tiling``);
  * records are cached in-process keyed by a graph signature; ``record``
    mode persists the cache to JSON so later processes can ``load_cache``.

Pin/override semantics (see ``GraphSession``): explicit ``tau``/``tau_solve``
arguments and numeric ``delta_init`` configs always win over the autotuner;
only symbolic/default knobs are tuned.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import guard
from repro.common import get_logger, next_multiple
from repro.graph.segment_ops import segment_aggregate
from repro.graph.structures import EdgeList
from repro.kernels.edge_relax.kernel import validate_tiling
from repro.runtime.roofline import V5E, ChipPeaks, peaks_for

log = get_logger("repro.autotune")

N_BUCKETS = 32  # log2 histogram buckets (covers the int32 weight range)

# tiling candidates scored by the roofline model; every pair satisfies the
# kernel preconditions (edge_block % 128 == 0, node_tile power of two)
NODE_TILE_CANDIDATES = (128, 256, 512)
EDGE_BLOCK_CANDIDATES = (128, 256, 512, 1024)
# match matrix + streamed intermediates must stay well inside VMEM
_MAX_MATRIX_BYTES = 4 * 2**20

# int32 relax runs on the VPU, not the bf16 MXU the roofline peak describes;
# the effective elementwise int throughput is roughly peak/16 on v5e.
_VPU_DISCOUNT = 16.0

# cluster-count model k_hat ~ C * tau * log n, calibrated on the bench graph
# (n=20000 road-like, tau=32 -> 677 clusters => C ~ 2.1); used only to pick
# the cascade depth, which tolerates a 2x miss either way.
_CLUSTERS_PER_TAU_LOG_N = 2.2

# a source skew (max_degree / avg_degree) beyond this marks a hub-heavy
# graph: clusters cover faster, so a larger tau cuts radius without blowing
# up the quotient
_HUB_SKEW = 32.0

# host round-trip cost per stage-loop sync (dispatch + scalar fetch); the
# stage engine pays one per stage, the one-shot engine one total, so mode
# selection compares predicted_stages * this against the one-shot fixpoint's
# extra device work (~ one wave over the hop radius at the roofline rate)
_HOST_SYNC_S = 2e-4

TUNE_EVENTS: Dict[str, int] = {"hits": 0, "misses": 0}


class AutotuneError(ValueError):
    """A derived tuning record failed validation."""


@dataclass(frozen=True)
class GraphStats:
    """One-pass device statistics of an edge list."""

    n_nodes: int
    n_edges: int
    avg_degree: float
    max_degree: int
    min_weight: int
    avg_weight: int
    max_weight: int
    weight_sum: int
    degree_hist: Tuple[int, ...]  # log2-bucketed in-degree counts
    weight_hist: Tuple[int, ...]  # log2-bucketed edge-weight counts


@dataclass(frozen=True)
class TuningRecord:
    """Derived pipeline knobs + the model predictions behind them."""

    signature: str
    tau: int
    tau_solve: int
    levels: int               # cascade depth (0 = direct quotient solve)
    delta_init: int
    node_tile: int
    edge_block: int
    fuse: int                 # megakernel fusion depth (0 = unfused)
    predicted_superstep_s: float  # roofline estimate for one relax pass
    padded_edges: int             # edge slots after blocking at this tiling
    # decomposition mode (core/engine.py) for sessions opened with
    # cfg.mode="auto": "oneshot" when the predicted stage-loop sync overhead
    # exceeds the one-shot fixpoint's superstep roofline. Appended LAST with
    # a default so JSON caches recorded before this field load cleanly.
    mode: str = "stages"


@partial(jax.jit, static_argnames=("n_nodes",))
def _stats_pass(dst, weight, n_nodes: int):
    """Everything histogram-shaped, in one device program: returns a packed
    int32 vector [deg_hist(32) | weight_hist(32) | max_deg, min_w, max_w]."""
    ones = jnp.ones_like(dst)
    deg = segment_aggregate(ones, dst, n_nodes, "sum")

    def lg(x):
        f = jnp.maximum(x, 1).astype(jnp.float32)
        return jnp.clip(jnp.floor(jnp.log2(f)).astype(jnp.int32),
                        0, N_BUCKETS - 1)

    deg_hist = jnp.bincount(lg(deg), length=N_BUCKETS)
    w_hist = jnp.bincount(lg(weight), length=N_BUCKETS)
    scalars = jnp.stack([deg.max(), weight.min(), weight.max()])
    return jnp.concatenate([deg_hist, w_hist, scalars]).astype(jnp.int32)


def compute_graph_stats(edges: EdgeList) -> GraphStats:
    """Device histograms + ONE packed host fetch. The weight sum (which can
    overflow int32) is reduced on the host from the resident numpy mirror."""
    n, e = edges.n_nodes, edges.n_edges
    if n == 0 or e == 0:
        zeros = (0,) * N_BUCKETS
        return GraphStats(n, e, 0.0, 0, 1, 1, 1, 0, zeros, zeros)
    vec = guard.fetch(_stats_pass(jnp.asarray(edges.dst),
                                  jnp.asarray(edges.weight), n),
                      reason="autotune: packed degree/weight histograms")
    deg_hist = tuple(int(x) for x in vec[:N_BUCKETS])
    w_hist = tuple(int(x) for x in vec[N_BUCKETS:2 * N_BUCKETS])
    max_deg, min_w, max_w = (int(x) for x in vec[2 * N_BUCKETS:])
    w_sum = int(edges.weight.astype(np.int64).sum())
    return GraphStats(
        n_nodes=n, n_edges=e, avg_degree=e / n, max_degree=max_deg,
        min_weight=min_w, avg_weight=max(w_sum // e, 1), max_weight=max_w,
        weight_sum=w_sum, degree_hist=deg_hist, weight_hist=w_hist)


def graph_signature(stats: GraphStats) -> str:
    """Stable content key: graphs with identical coarse statistics share a
    tuning record (and the cache entry that goes with it)."""
    payload = (stats.n_nodes, stats.n_edges, stats.max_degree,
               stats.min_weight, stats.max_weight, stats.weight_sum,
               stats.degree_hist, stats.weight_hist)
    return hashlib.md5(repr(payload).encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# knob derivation
# ---------------------------------------------------------------------------


def _tiling_time(n_nodes: int, n_edges: int, node_tile: int,
                 edge_block: int, peaks: ChipPeaks) -> Tuple[float, int]:
    """Roofline estimate (seconds, padded edge slots) for one relax pass.

    HBM term: the blocked (src, dst, w, mask) int32 arrays stream once.
    Compute term: the [node_tile, edge_block] match matrix costs ~3 compare/
    select passes per cell on the VPU. The kernel double-buffers DMA against
    compute, so the pass time is the max of the two, not the sum.
    Padding model: each tile rounds up to whole edge blocks (+ half a block
    for destination skew), with at least one block per tile.
    """
    n_pad = next_multiple(n_nodes + 1, node_tile)
    n_tiles = n_pad // node_tile
    per_tile = n_edges / n_tiles
    blocks_per_tile = max(math.ceil((per_tile + edge_block / 2) / edge_block), 1)
    padded = n_tiles * blocks_per_tile * edge_block
    t_hbm = (padded * 4 * 4) / peaks.hbm_bw
    t_compute = (padded * node_tile * 3) / (peaks.flops / _VPU_DISCOUNT)
    return max(t_hbm, t_compute), padded


def _best_tiling(stats: GraphStats,
                 peaks: ChipPeaks) -> Tuple[int, int, float, int]:
    best = None
    for nt in NODE_TILE_CANDIDATES:
        for eb in EDGE_BLOCK_CANDIDATES:
            if nt * eb * 4 * 4 > _MAX_MATRIX_BYTES:
                continue
            t, padded = _tiling_time(stats.n_nodes, stats.n_edges, nt, eb,
                                     peaks)
            if best is None or t < best[2]:
                best = (nt, eb, t, padded)
    assert best is not None
    return best


def _median_weight_bucket(stats: GraphStats) -> int:
    half = max(stats.n_edges, 1) / 2
    acc = 0
    for b, cnt in enumerate(stats.weight_hist):
        acc += cnt
        if acc >= half:
            return b
    return 0


def kernel_peaks() -> ChipPeaks:
    """Peaks the kernel tiling is priced against: the attached TPU's table
    entry (a TPU kind missing from the table raises). Off a TPU the kernel
    never runs compiled and the tiling only shapes the blocked layout, so it
    is priced for the v5e the kernel is written for."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return peaks_for(dev.device_kind)
    return V5E


def derive_tuning(stats: GraphStats, *, peaks: ChipPeaks,
                  tau_fraction: float = 1e-3) -> TuningRecord:
    """Map graph statistics to pipeline knobs. Every choice here is a
    PERFORMANCE decision — the pipeline is correct for any legal value —
    so the formulas are deliberately simple and documented in place.
    ``peaks`` prices the kernel tiling (see ``kernel_peaks``).

    ``fuse`` is always 0: the TPU compiler refuses the fused megakernel's
    in-kernel 1-D gather (``kernels/edge_relax/megakernel.py``)."""
    n = max(stats.n_nodes, 1)
    logn = max(math.log(max(n, 2)), 1.0)

    # tau: the session default (n * fraction / log n), doubled on hub-heavy
    # graphs where coverage per stage is fast and a larger quotient is the
    # cheaper way to shrink the radius term of Phi_approx.
    tau = max(int(n * tau_fraction / logn), 4)
    skew = stats.max_degree / max(stats.avg_degree, 1.0)
    if skew > _HUB_SKEW:
        tau = min(tau * 2, max(n // 8, 4))
    tau = max(4, min(tau, n))

    # cascade depth from the expected cluster count: every level divides the
    # solve frontier by ~ (k_hat / tau_solve)^(1/levels); two levels covers
    # every graph the bench exercises.
    k_hat = min(n, max(1, int(_CLUSTERS_PER_TAU_LOG_N * tau * logn)))
    tau_solve = max(64, min(1024, int(math.sqrt(n))))
    if k_hat <= tau_solve:
        levels = 0
    else:
        levels = min(2, math.ceil(math.log(k_hat / tau_solve) / math.log(3)))

    # delta_init: one bucket above the median edge weight — the mean (the
    # "avg" default) overshoots badly on heavy-tailed weights, wasting the
    # first stage on an over-wide Δ.
    b = _median_weight_bucket(stats)
    delta_init = max(1, min(2 ** (b + 1), 2**30 - 1))

    node_tile, edge_block, pred_t, padded = _best_tiling(stats, peaks)

    # engine mode for cfg.mode="auto" sessions: the stage loop halves the
    # uncovered set per stage until the 8*tau*log n threshold, so it needs
    # ~ log2(n / threshold) stages, each costing one host round-trip; the
    # one-shot alternative pays a single sync but its fixpoint must sweep
    # the whole hop radius (~ sqrt(n) on the road-like graphs the paper
    # targets) in one grow call. Pick whichever the model prices cheaper.
    s_hat = max(1, math.ceil(math.log2(max(n / max(8.0 * tau * logn, 1.0),
                                           2.0))))
    hop_hat = max(int(math.sqrt(n)), 1)
    mode = ("oneshot" if s_hat * _HOST_SYNC_S > hop_hat * pred_t
            else "stages")

    return TuningRecord(
        signature=graph_signature(stats), tau=tau, tau_solve=tau_solve,
        levels=levels, delta_init=delta_init, node_tile=node_tile,
        edge_block=edge_block, fuse=0, predicted_superstep_s=pred_t,
        padded_edges=padded, mode=mode)


def validate_tuning(rec: TuningRecord, stats: GraphStats,
                    peaks: ChipPeaks) -> None:
    """Re-check a record against the kernel preconditions and the roofline
    model (guards hand-edited or stale cache entries)."""
    validate_tiling(rec.node_tile, rec.edge_block)
    if not 1 <= rec.tau <= max(stats.n_nodes, 4):
        raise AutotuneError(f"tau {rec.tau} out of range for n={stats.n_nodes}")
    if rec.tau_solve < 2:
        raise AutotuneError(f"tau_solve must be >= 2, got {rec.tau_solve}")
    if not 0 <= rec.levels <= 4:
        raise AutotuneError(f"levels must be in [0, 4], got {rec.levels}")
    if not 1 <= rec.delta_init < 2**30:
        raise AutotuneError(f"delta_init {rec.delta_init} outside [1, 2^30)")
    if rec.fuse < 0:
        raise AutotuneError(f"fuse must be >= 0, got {rec.fuse}")
    if rec.mode not in ("stages", "oneshot"):
        raise AutotuneError(
            f"mode must be 'stages' or 'oneshot' (a record stores the "
            f"RESOLVED mode, never 'auto'), got {rec.mode!r}")
    t, _ = _tiling_time(stats.n_nodes, stats.n_edges,
                        rec.node_tile, rec.edge_block, peaks)
    best_t = _best_tiling(stats, peaks)[2]
    if t > best_t * 1.05:
        raise AutotuneError(
            f"tiling ({rec.node_tile}, {rec.edge_block}) predicted "
            f"{t:.3e}s vs best {best_t:.3e}s — record is stale for this "
            "graph shape")


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

_CACHE: Dict[str, TuningRecord] = {}


def _default_cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache",
                     "repro_autotune.json"))


def _cache_key(sig: str, backend: str) -> str:
    return f"{sig}:{backend}:{jax.default_backend()}"


def clear_cache() -> None:
    _CACHE.clear()
    TUNE_EVENTS["hits"] = TUNE_EVENTS["misses"] = 0


def save_cache(path: Optional[str] = None) -> str:
    path = path or _default_cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    payload = {k: dataclasses.asdict(v) for k, v in _CACHE.items()}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    return path


def load_cache(path: Optional[str] = None) -> int:
    """Populate the in-process cache from a recorded JSON file; returns the
    number of records loaded (0 when the file is absent)."""
    path = path or _default_cache_path()
    if not os.path.exists(path):
        return 0
    with open(path) as f:
        payload = json.load(f)
    for k, d in payload.items():
        _CACHE[k] = TuningRecord(**d)
    return len(payload)


def get_tuning(edges: EdgeList, *, backend: str = "single",
               record: bool = False,
               cache_path: Optional[str] = None) -> TuningRecord:
    """Stats pass + derivation with in-process caching. ``record=True``
    additionally persists the cache file after a miss."""
    stats = compute_graph_stats(edges)
    key = _cache_key(graph_signature(stats), backend)
    hit = _CACHE.get(key)
    if hit is not None:
        TUNE_EVENTS["hits"] += 1
        return hit
    TUNE_EVENTS["misses"] += 1
    peaks = kernel_peaks()
    rec = derive_tuning(stats, peaks=peaks)
    validate_tuning(rec, stats, peaks)
    _CACHE[key] = rec
    if record:
        save_cache(cache_path)
    log.info("autotuned %s: tau=%d tau_solve=%d levels=%d delta0=%d "
             "tiling=(%d,%d) fuse=%d", key, rec.tau, rec.tau_solve,
             rec.levels, rec.delta_init, rec.node_tile, rec.edge_block,
             rec.fuse)
    return rec

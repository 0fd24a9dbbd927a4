"""DiameterEstimator: interchangeable diameter queries over a GraphSession.

The paper's experimental core (Table 3) is a head-to-head between the
cluster-quotient pipeline and SSSP-based estimators. Each method is a
``DiameterEstimator`` — ``estimate(session) -> DiameterEstimate`` — running
against the session's RESIDENT device buffers, so methods can be compared on
the same graph without re-uploading or rebuilding anything:

  * ``ClusterQuotientEstimator`` — the paper pipeline (Sections 4+5):
    decompose -> device quotient -> batched multi-source solve. Conservative
    UPPER bound (Phi_approx >= Phi(G) when connected).
  * ``DeltaSteppingEstimator`` — the Section 5 competitor: one SSSP from a
    random source gives ecc <= Phi <= 2 ecc. ``delta=None`` degenerates to
    Bellman-Ford, the paper's optimal setting on a round-driven platform
    (and byte-identical to the legacy ``diameter_2approx_sssp``).
  * ``LowerBoundEstimator`` — repeated SSSP hopping to the farthest node
    (how the paper computes the Phi column of Table 1). LOWER bound only.
  * ``IntervalEstimator`` — composite: runs a panel of estimators and
    returns a certified ``[lower, upper]`` bracket (``DiameterInterval``)
    with per-estimator results and merged ``PipelineMetrics``.
  * ``DynamicQuotientEstimator`` — the dynamic-graph subsystem's query
    side (``core/dynamic.py``): serves the decomposition the session
    maintains under ``apply_updates`` with incremental quotient refresh
    and a cached solve.

Every estimator surfaces the same ``connected`` flag contract: on a
disconnected input the bounds cover only finite-distance pairs and
``connected`` is False (the true diameter is infinite).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import ClassVar, Dict, List, Optional, Protocol, Tuple, runtime_checkable

import numpy as np

from repro.common import Timer, get_logger
from repro.core.cluster import Decomposition, cluster, cluster2
from repro.core.engine import resolve_engine_mode
from repro.core.quotient import (
    build_quotient_device,
    build_quotient_from_level,
    build_quotient_numpy,
    quotient_as_edgelist,
    quotient_diameter,
    solve_device_quotient,
)
from repro.analysis import guard
from repro.core.session import GraphSession, tau_for
from repro.runtime import telemetry

log = get_logger("repro.estimators")


@dataclass
class PipelineMetrics:
    """Host-sync accounting for one estimator query.

    Every field counts device->host fetches (the paper's round-overhead
    analogue); device supersteps are tracked separately. The end-to-end
    budget the bench asserts is ``total_host_syncs <= 8``. Metrics add:
    ``a + b`` (or ``sum([...])``) is the field-wise aggregate, so batch and
    interval queries report one combined sync total.
    """

    decompose_syncs: int = 0   # one per engine stage (stop-decision scalars)
    finalize_syncs: int = 0    # packed final-plane fetch (1 per decomposition)
    checkpoint_syncs: int = 0  # device leaves materialized for durability
                               # (stage-boundary checkpoints). Deliberately
                               # NOT in total_host_syncs: durability cost is
                               # a knob (checkpoint_every), not part of the
                               # algorithmic round budget the bench asserts.
    halo_bytes: int = 0        # plane-row bytes the sharded comm plan moved
    fullplane_bytes: int = 0   # what a full-plane all-gather would have moved
                               # (both 0 on single-device backends; bytes,
                               # not syncs — never in total_host_syncs)
    quotient_syncs: int = 0    # (k, m, max_w, w_sum) counter fetch, 1 / level
    solve_syncs: int = 0       # packed (diameter, connected, steps, ecc) fetch
    solve_supersteps: int = 0  # device BF supersteps inside the solve
    n_quotient_edges: int = 0  # level-0 quotient edge count
    # cascade accounting (CascadeEstimator): one list entry per EXTRA level
    # (the flat pipeline is level 0 and keeps these empty, so a level-0
    # cascade stays field-identical to ClusterQuotientEstimator). Lists
    # concatenate under ``+`` like the scalar counters add.
    cascade_levels: int = 0              # extra decomposition levels run
    level_syncs: List[int] = field(default_factory=list)       # per level
    level_supersteps: List[int] = field(default_factory=list)  # per level
    level_clusters: List[int] = field(default_factory=list)    # quotient k
                                                               # after level

    @property
    def total_host_syncs(self) -> int:
        return (self.decompose_syncs + self.finalize_syncs
                + self.quotient_syncs + self.solve_syncs)

    def __add__(self, other: "PipelineMetrics") -> "PipelineMetrics":
        if not isinstance(other, PipelineMetrics):
            return NotImplemented
        return PipelineMetrics(**{
            f.name: getattr(self, f.name) + getattr(other, f.name)
            for f in dataclasses.fields(self)})

    def __radd__(self, other) -> "PipelineMetrics":
        if other == 0:  # support sum([...]) with the default start
            return self
        return self.__add__(other)

    @staticmethod
    def merge(items) -> "PipelineMetrics":
        """Field-wise aggregate of many metrics (None entries skipped)."""
        return sum((m for m in items if m is not None), PipelineMetrics())


@dataclass
class DiameterEstimate:
    phi_approx: int
    phi_quotient: int
    radius: int
    n_clusters: int
    growing_steps: int
    n_stages: int
    delta_end: int
    seconds: float
    connected: bool
    # phi_approx is a conservative estimate of the diameter ONLY when
    # ``connected`` — for a disconnected graph it upper-bounds the largest
    # finite-distance pair (the true diameter is infinite).
    pipeline: Optional[PipelineMetrics] = None
    # int64 eccentricities of the SOLVED quotient's clusters: length
    # n_clusters for the flat pipeline; for a cascade that ran extra levels
    # it covers the FINAL level's clusters (pipeline.level_clusters[-1] of
    # them), in original units (scaled back by the cumulative rescale).
    quotient_ecc: Optional[np.ndarray] = None
    # which estimator produced this, and the certified bracket it provides:
    # ``lower <= Phi(G) <= upper`` (each may be None when the method gives
    # no bound on that side; bounds cover finite pairs when disconnected).
    method: str = "cluster-quotient"
    lower: Optional[int] = None
    upper: Optional[int] = None


@dataclass
class DiameterInterval:
    """Certified diameter bracket from a panel of estimators."""

    lower: int
    upper: int
    connected: bool
    estimates: Dict[str, DiameterEstimate]
    pipeline: PipelineMetrics   # merged host-sync totals across the panel
    seconds: float


@runtime_checkable
class DiameterEstimator(Protocol):
    """One diameter-query method over a resident ``GraphSession``."""

    name: str

    def estimate(self, session: GraphSession) -> DiameterEstimate:
        ...


# ---------------------------------------------------------------------------
# the paper pipeline
# ---------------------------------------------------------------------------


def _fetch_quotient_counters(dq, pm: PipelineMetrics):
    """ONE packed fetch of the four device counters:
    (n_clusters, n_edges, max_weight, weight_sum)."""
    from repro.core.quotient import fetch_quotient_counters

    pm.quotient_syncs += 1
    return fetch_quotient_counters(dq)


def _device_quotient_solve(edges, dec: Decomposition, backend,
                           pm: PipelineMetrics):
    """quotient + local solve, device-resident. Returns
    (phi_quotient, eccentricities, connected)."""
    with telemetry.span("quotient.build") as sp:
        dq = build_quotient_device(edges, dec, backend=backend)
        if dq is None:  # no nodes or no edges: quotient is trivially empty
            k = dec.n_clusters
            return 0, np.zeros(k, np.int64), k <= 1
        k, m, wmax, _ = _fetch_quotient_counters(dq, pm)
        pm.n_quotient_edges = m
        sp.set(clusters=k, edges=m)
    if k <= 1:
        return 0, np.zeros(k, np.int64), True
    with telemetry.span("quotient.solve", clusters=k) as sp:
        diam, ecc, connected, steps = solve_device_quotient(dq, k, m, wmax)
        pm.solve_syncs += 1
        pm.solve_supersteps = steps
        sp.set(supersteps=steps)
    return diam, ecc, connected


def _cascade_quotient_solve(edges, dec: Decomposition, backend,
                            pm: PipelineMetrics, cfg, tau_solve: int,
                            max_levels: int, level_mode: str = "stages"):
    """Multi-level quotient cascade (companion paper arXiv:1407.3144 applies
    the decomposition RECURSIVELY until the residual graph is small).

    While the quotient still exceeds the solve budget (``k > tau_solve``)
    and levels remain, re-enter the engine ON THE QUOTIENT: rescale its
    int64 weights into the engine's int32 planes (``quotient_as_edgelist``,
    ceiling division — conservative), decompose with a device-resident
    ``SingleDeviceBackend`` over the resident buffers, and quotient again.
    Per-level cluster radii accumulate into the upper bound:

        Phi(G) <= 2 R_0 + sum_{l>=1} S_l * 2 R_l + S_L * diam(Q_L)

    with S_l the cumulative rescale factor (1 unless weights overflowed
    int32). Returns (phi_quotient_tail, ecc, connected, extra_steps) where
    ``phi_quotient_tail`` is everything except level-0's ``2 R_0`` — so
    ``phi = tail + 2 * dec.radius`` holds at every level count, and a
    level-0 cascade is field-identical to the flat pipeline.

    ``level_mode`` selects the decomposition mode for the RE-ENTRANT levels
    ("stages" or "oneshot"): quotient levels are small and stage-count
    bound, so oneshot's single-fixpoint growth often wins there even when
    level 0 runs staged.
    """
    from repro.core.backend import SingleDeviceBackend
    from repro.core.engine import run_cluster, run_oneshot

    with telemetry.span("quotient.build") as sp:
        dq = build_quotient_device(edges, dec, backend=backend)
        if dq is None:  # no nodes or no edges: quotient is trivially empty
            k = dec.n_clusters
            return 0, np.zeros(k, np.int64), k <= 1, 0
        k, m, wmax, wsum = _fetch_quotient_counters(dq, pm)
        pm.n_quotient_edges = m
        sp.set(clusters=k, edges=m)
    scale_total = 1
    radius_tail = 0   # sum_{l>=1} S_l * 2 R_l
    extra_steps = 0
    level = 0
    while level < max_levels and k > max(tau_solve, 1) and m > 0:
        level += 1
        with telemetry.span("cascade.level", level=level) as sp:
            lv = quotient_as_edgelist(dq, k, m, wmax, wsum)
            be = SingleDeviceBackend.from_device(lv.n_nodes, lv.src, lv.dst,
                                                 lv.weight)
            if level_mode == "oneshot":
                dec_l = run_oneshot(
                    None, be, tau_for(k, cfg.tau_fraction),
                    gamma=cfg.gamma, seed=cfg.seed + level,
                    deterministic=cfg.deterministic,
                    max_steps_per_phase=cfg.max_steps_per_phase,
                    max_delta=lv.weight_sum + 1,
                )
            else:
                dec_l = run_cluster(
                    None, be, tau_for(k, cfg.tau_fraction),
                    gamma=cfg.gamma, variant=cfg.variant,
                    delta0=max(lv.weight_sum // max(m, 1), 1),
                    seed=cfg.seed + level, max_stages=cfg.max_stages,
                    max_steps_per_phase=cfg.max_steps_per_phase,
                    max_delta=lv.weight_sum + 1,
                )
            scale_total *= lv.scale
            radius_tail += scale_total * 2 * dec_l.radius
            extra_steps += dec_l.growing_steps
            pm.decompose_syncs += dec_l.metrics.host_syncs
            pm.finalize_syncs += dec_l.metrics.finalize_syncs
            dq = build_quotient_from_level(lv, dec_l)
            k, m, wmax, wsum = _fetch_quotient_counters(dq, pm)
            pm.level_syncs.append(dec_l.metrics.host_syncs
                                  + dec_l.metrics.finalize_syncs + 1)
            pm.level_supersteps.append(dec_l.growing_steps)
            pm.level_clusters.append(k)
            sp.set(clusters=k, supersteps=dec_l.growing_steps,
                   syncs=pm.level_syncs[-1])
        log.info("cascade level %d: %d clusters -> %d (scale=%d steps=%d)",
                 level, lv.n_nodes, k, lv.scale, dec_l.growing_steps)
        if k == lv.n_nodes:
            # no shrinkage (the level's stage threshold exceeded its node
            # count -> all singletons): further levels would repeat the
            # same non-progress, so solve what we have
            log.info("cascade level %d did not shrink the quotient; "
                     "solving at %d clusters", level, k)
            break
    pm.cascade_levels = level
    if k <= 1:
        return radius_tail, np.zeros(k, np.int64), True, extra_steps
    with telemetry.span("quotient.solve", clusters=k) as sp:
        diam, ecc, connected, steps = solve_device_quotient(dq, k, m, wmax)
        pm.solve_syncs += 1
        pm.solve_supersteps = steps
        sp.set(supersteps=steps)
    return (radius_tail + scale_total * diam,
            np.asarray(ecc, np.int64) * scale_total, connected, extra_steps)


def _resolve_query_cfg(session: GraphSession, est) -> Tuple[object, int]:
    """Apply an estimator's per-query overrides to the session config and
    resolve tau. Shared by ClusterQuotientEstimator and CascadeEstimator."""
    cfg = session.cfg
    delta_init = est.delta_init
    if delta_init is not None:
        # resolve symbolic modes through the session: on a pooled
        # (padded) session "avg"/"min" must reflect the REAL edges
        delta_init = str(session.resolve_delta_init(delta_init))
    overrides = {k: v for k, v in (
        ("variant", est.variant), ("seed", est.seed),
        ("delta_init", delta_init),
        ("use_cluster2", est.use_cluster2),
        ("mode", getattr(est, "mode", None))) if v is not None}
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    # "auto" resolves against the session's autotuning record (if any);
    # explicit per-query "stages"/"oneshot" always wins, and bad names
    # raise before any device work
    mode = resolve_engine_mode(cfg.mode, session.tuning)
    if mode != cfg.mode:
        cfg = dataclasses.replace(cfg, mode=mode)
    tau = est.tau if est.tau is not None else session.tau
    if tau < 1:
        raise ValueError(f"tau must be >= 1, got {tau}")
    return cfg, tau


def _run_decomposition(edges, backend, cfg, tau: int,
                       pm: PipelineMetrics,
                       checkpointer=None) -> Decomposition:
    """Level-0 decomposition on the session's resident backend."""
    if cfg.use_cluster2:
        dec: Decomposition = cluster2(
            edges, tau, gamma=cfg.gamma, seed=cfg.seed,
            delta_init=cfg.delta_init, relax_fn=backend,
        )
    else:
        dec = cluster(
            edges, tau, gamma=cfg.gamma, variant=cfg.variant,
            delta_init=cfg.delta_init, seed=cfg.seed,
            max_stages=cfg.max_stages,
            max_steps_per_phase=cfg.max_steps_per_phase,
            relax_fn=backend,
            mode=cfg.mode, deterministic=cfg.deterministic,
            checkpointer=checkpointer,
        )
    if dec.metrics is not None:
        pm.decompose_syncs = dec.metrics.host_syncs
        pm.finalize_syncs = dec.metrics.finalize_syncs
        pm.checkpoint_syncs = dec.metrics.checkpoint_syncs
        pm.halo_bytes = dec.metrics.halo_bytes
        pm.fullplane_bytes = dec.metrics.fullplane_bytes
    return dec


def _package_estimate(method: str, dec: Decomposition, phi_q: int,
                      connected: bool, pm: PipelineMetrics, ecc,
                      seconds: float, extra_steps: int = 0) -> DiameterEstimate:
    phi = phi_q + 2 * dec.radius
    log.info(
        "phi_approx=%d (quotient=%d radius=%d clusters=%d steps=%d "
        "host_syncs=%d) in %.2fs",
        phi, phi_q, dec.radius, dec.n_clusters,
        dec.growing_steps + extra_steps, pm.total_host_syncs, seconds,
    )
    return DiameterEstimate(
        phi_approx=phi,
        phi_quotient=phi_q,
        radius=dec.radius,
        n_clusters=dec.n_clusters,
        growing_steps=dec.growing_steps + extra_steps,
        n_stages=dec.n_stages,
        delta_end=dec.delta_end,
        seconds=seconds,
        connected=connected,
        pipeline=pm,
        quotient_ecc=ecc,
        method=method,
        upper=phi,
    )


@dataclass
class ClusterQuotientEstimator:
    """Paper pipeline: Phi_approx(G) = Phi(G_C) + 2 R (conservative upper).

    ``tau``/``variant``/``seed``/``delta_init``/``use_cluster2``/``mode``
    override the session defaults per query — the resident graph is reused,
    so e.g. a stop-vs-complete, CLUSTER-vs-CLUSTER2 or stages-vs-oneshot
    comparison costs two queries on one session, not two uploads.
    ``solver="device"`` (default) runs the quotient + solve on device;
    ``solver="scipy"`` keeps the host oracle path (tests / debugging).
    """

    name: ClassVar[str] = "cluster-quotient"

    tau: Optional[int] = None
    solver: str = "device"
    variant: Optional[str] = None
    seed: Optional[int] = None
    delta_init: Optional[str] = None
    use_cluster2: Optional[bool] = None
    mode: Optional[str] = None       # stages | oneshot | auto (engine mode)

    def estimate(self, session: GraphSession) -> DiameterEstimate:
        cfg, tau = _resolve_query_cfg(session, self)
        edges, backend = session.edges, session.backend
        pm = PipelineMetrics()
        ecc = None
        with session.track_query(), Timer() as t:
            dec = _run_decomposition(
                edges, backend, cfg, tau, pm,
                checkpointer=getattr(session, "checkpointer", None))
            if self.solver == "scipy":
                q = build_quotient_numpy(edges, dec)
                phi_q, connected = quotient_diameter(q)
            else:
                phi_q, ecc, connected = _device_quotient_solve(
                    edges, dec, backend, pm)
            if not connected:
                log.warning(
                    "graph is disconnected: phi_approx=%d only bounds "
                    "finite-distance pairs", phi_q + 2 * dec.radius)
        return _package_estimate(self.name, dec, phi_q, connected, pm, ecc,
                                 t.seconds)


@dataclass
class CascadeEstimator:
    """Multi-level quotient cascade: the paper pipeline applied RECURSIVELY
    (companion paper arXiv:1407.3144) until the residual quotient fits the
    batched-BF solve budget.

    Level 0 decomposes the session graph on its resident backend exactly
    like ``ClusterQuotientEstimator``; while the quotient still has more
    than ``tau_solve`` clusters and ``levels`` allows, the engine re-enters
    ON THE QUOTIENT (``quotient_as_edgelist`` -> device-resident
    ``SingleDeviceBackend`` -> decompose -> quotient), accumulating each
    level's ``2 * radius`` (times the cumulative int64->int32 weight
    rescale) into the conservative upper bound. ``levels=0`` is
    field-identical to the flat pipeline.

    Deeper levels always run single-device — the quotient is small by
    construction, mirroring the paper's "solve locally in one reducer".
    ``n_clusters``/``radius``/``n_stages``/``delta_end`` on the returned
    estimate describe LEVEL 0 (per-level breakdowns live in
    ``pipeline.level_*``); ``quotient_ecc`` covers the final solved level.
    """

    name: ClassVar[str] = "cascade"

    levels: int = 2
    tau_solve: Optional[int] = None
    tau: Optional[int] = None
    variant: Optional[str] = None
    seed: Optional[int] = None
    delta_init: Optional[str] = None
    use_cluster2: Optional[bool] = None
    mode: Optional[str] = None        # level-0 engine mode override
    level_mode: Optional[str] = None  # mode for re-entrant quotient levels;
                                      # None = follow the level-0 mode

    def estimate(self, session: GraphSession) -> DiameterEstimate:
        if self.levels < 0:
            raise ValueError(f"levels must be >= 0, got {self.levels}")
        tau_solve = (self.tau_solve if self.tau_solve is not None
                     else session.tau_solve)
        if tau_solve < 2:
            raise ValueError(f"tau_solve must be >= 2, got {tau_solve}")
        cfg, tau = _resolve_query_cfg(session, self)
        level_mode = resolve_engine_mode(
            self.level_mode if self.level_mode is not None else cfg.mode,
            session.tuning)
        edges, backend = session.edges, session.backend
        pm = PipelineMetrics()
        with session.track_query(), Timer() as t:
            dec = _run_decomposition(
                edges, backend, cfg, tau, pm,
                checkpointer=getattr(session, "checkpointer", None))
            phi_q, ecc, connected, extra = _cascade_quotient_solve(
                edges, dec, backend, pm, cfg, tau_solve, self.levels,
                level_mode=level_mode)
            if not connected:
                log.warning(
                    "graph is disconnected: phi_approx=%d only bounds "
                    "finite-distance pairs", phi_q + 2 * dec.radius)
        return _package_estimate(self.name, dec, phi_q, connected, pm, ecc,
                                 t.seconds, extra_steps=extra)


@dataclass
class DynamicQuotientEstimator:
    """Query side of the dynamic-graph subsystem (``core/dynamic.py``).

    Serves the conservative upper bound ``Phi(G_C) + 2 R`` from the
    decomposition the session MAINTAINS under ``apply_updates`` instead of
    re-decomposing per query: the quotient is refreshed incrementally (only
    (cluster, cluster) keys touching clusters dirtied since the last solve
    are recomputed) and the solve result is cached until the next update —
    so a query against an unchanged session costs ZERO device work beyond
    the cached scalars, and a post-update query costs one dirty-slice
    quotient pass plus the batched solve.

    On a session that has never seen an update this initializes dynamic
    mode (one full decomposition — the same work the flat pipeline's first
    query does); the bound contract is identical to
    ``ClusterQuotientEstimator``'s: certified upper when connected, largest
    finite-distance pair otherwise (flagged via ``connected``).
    """

    name: ClassVar[str] = "dynamic-quotient"

    def estimate(self, session: GraphSession) -> DiameterEstimate:
        from repro.core import dynamic as dyn_mod

        pm = PipelineMetrics()
        with session.track_query(), Timer() as t:
            st = dyn_mod.ensure_dynamic(session)
            phi_q, ecc, connected = dyn_mod.solve_session_quotient(
                session, pm)
            if not connected:
                log.warning(
                    "graph is disconnected: phi_approx=%d only bounds "
                    "finite-distance pairs", phi_q + 2 * st.dec.radius)
        return _package_estimate(self.name, st.dec, phi_q, connected, pm,
                                 ecc, t.seconds)


# ---------------------------------------------------------------------------
# SSSP estimators (the competitors), on the session's resident edge arrays
# ---------------------------------------------------------------------------


def _trivial_estimate(method: str, n_nodes: int) -> DiameterEstimate:
    """Empty / single-node graphs: diameter 0, connected iff <= 1 node."""
    return DiameterEstimate(
        phi_approx=0, phi_quotient=0, radius=0, n_clusters=n_nodes,
        growing_steps=0, n_stages=0, delta_end=0, seconds=0.0,
        connected=n_nodes <= 1, pipeline=PipelineMetrics(),
        method=method, lower=0, upper=0 if n_nodes <= 1 else None)


def _sssp_from(session: GraphSession, source: int, delta: Optional[int]):
    """One SSSP on the resident edge arrays; ONE packed host fetch of
    (dist, supersteps, widened). ``delta=None`` -> Bellman-Ford, narrow
    first (``sssp.bf_from``: int32 with a saturating add, rerun in int64
    on the device only if it saturated). Returns (dist, supersteps, inf) —
    ``inf`` follows the same provable bound as ``sssp.bellman_ford``
    (INF64 when ``n * max_weight`` would overflow int32, so heavy-weight
    graphs never wrap negative). The ``sssp.solve`` span carries the dtype
    the distances were computed in and ``widened`` (0/1), which
    ``SessionMetrics.sssp_widened`` counts."""
    import jax
    import jax.numpy as jnp

    from repro.core.sssp import _delta_stepping_loop, bf_from, sssp_dtype_for

    n = session.n_nodes
    src, dst, w = session.flat_device_edges()
    with jax.enable_x64(True), telemetry.span("sssp.solve", source=source) as sp:
        if delta is None:
            d, k, widened, inf = bf_from(src, dst, w, source, n,
                                         session.max_weight)
            dtype = jnp.int32
        else:
            dtype, inf = sssp_dtype_for(n, session.max_weight, delta)
            infj = jnp.asarray(inf, dtype)
            d0 = jnp.full(n, infj, dtype=dtype).at[source].set(0)
            d, k = _delta_stepping_loop(src, dst, w.astype(dtype), d0,
                                        jnp.asarray(delta, dtype), infj, n)
            widened = False
        out = guard.fetch(jnp.concatenate(
            [d.astype(jnp.int64), jnp.asarray(k, jnp.int64)[None],
             jnp.asarray(widened, jnp.int64)[None]]),
            reason="sssp estimator: packed (dist plane, supersteps, widened)")
        widened = int(out[n + 1])
        sp.set(supersteps=int(out[n]), widened=widened,
               dtype="int64" if widened else np.dtype(dtype).name)
    session.metrics.sssp_widened += widened
    return out[:n], int(out[n]), inf


@dataclass
class DeltaSteppingEstimator:
    """2-approximation from one SSSP: ecc(source) <= Phi <= 2 ecc(source).

    ``delta=None`` (default) runs Bellman-Ford — the paper notes the best
    Delta-stepping setting on a round-driven platform degenerates to
    Delta = inf — and reproduces the legacy ``diameter_2approx_sssp``
    numbers exactly (same source draw, same relaxation order).
    """

    name: ClassVar[str] = "delta-stepping"

    seed: int = 0
    delta: Optional[int] = None

    def estimate(self, session: GraphSession) -> DiameterEstimate:
        if self.delta is not None and self.delta < 1:
            raise ValueError(f"delta must be >= 1, got {self.delta} "
                             "(use delta=None for Bellman-Ford)")
        n = session.n_nodes
        if n <= 1:
            with session.track_query():
                return _trivial_estimate(self.name, n)
        with session.track_query(), Timer() as t:
            rng = np.random.default_rng(self.seed)
            s = int(rng.integers(n))
            dist, supersteps, inf = _sssp_from(session, s, self.delta)
        reached = dist < inf
        ecc = int(dist[reached].max())
        connected = bool(reached.all())
        pm = PipelineMetrics(solve_syncs=1, solve_supersteps=supersteps)
        # on a disconnected input 2*ecc only covers the SOURCE's component —
        # unlike the cluster-quotient upper it does NOT bound the largest
        # finite-distance pair, so it is no certified upper bound at all
        # (the realized ecc stays a valid lower bound either way).
        return DiameterEstimate(
            phi_approx=2 * ecc, phi_quotient=0, radius=ecc, n_clusters=0,
            growing_steps=supersteps, n_stages=1,
            # dtype: delta=None (unbucketed BF) reports delta_end=0
            delta_end=self.delta or 0,
            seconds=t.seconds, connected=connected, pipeline=pm,
            method=self.name, lower=ecc, upper=2 * ecc if connected else None)


@dataclass
class LowerBoundEstimator:
    """Farthest-point SSSP hopping (paper Table 1's Phi column): a certified
    LOWER bound — every hop realizes an actual shortest-path distance.

    The FIRST hop is exactly the 2-approx SSSP (random source, same draw as
    ``DeltaSteppingEstimator`` for the same seed), so on connected inputs
    the result also carries its free ``upper = 2 * ecc(first source)`` —
    which is why the default ``IntervalEstimator`` panel does not need a
    separate ``DeltaSteppingEstimator`` run.
    """

    name: ClassVar[str] = "farthest-point"

    rounds: int = 4
    seed: int = 0

    def estimate(self, session: GraphSession) -> DiameterEstimate:
        n = session.n_nodes
        if n <= 1:
            with session.track_query():
                return _trivial_estimate(self.name, n)
        with session.track_query(), Timer() as t:
            rng = np.random.default_rng(self.seed)
            s = int(rng.integers(n))
            best, total_steps, hops = 0, 0, 0
            first_ecc = 0
            connected = True
            pm = PipelineMetrics()
            for _ in range(self.rounds):
                dist, supersteps, inf = _sssp_from(session, s, None)
                pm.solve_syncs += 1
                pm.solve_supersteps += supersteps
                total_steps += supersteps
                hops += 1
                connected = connected and bool((dist < inf).all())
                fin = np.where(dist < inf, dist, -1)
                far = int(fin.argmax())
                best = max(best, int(fin.max()))
                if hops == 1:
                    first_ecc = int(fin.max())
                if far == s:
                    break
                s = far
        return DiameterEstimate(
            phi_approx=best, phi_quotient=0, radius=0, n_clusters=0,
            growing_steps=total_steps, n_stages=hops, delta_end=0,
            seconds=t.seconds, connected=connected, pipeline=pm,
            method=self.name, lower=best,
            upper=2 * first_ecc if connected else None)


# ---------------------------------------------------------------------------
# composite: certified [lower, upper] bracket
# ---------------------------------------------------------------------------


@dataclass
class IntervalEstimator:
    """Run a panel of estimators on ONE resident session and combine their
    bounds: lower = max of lower bounds, upper = min of upper bounds. The
    bracket is certified even on disconnected inputs (both sides then bound
    the largest finite-distance pair; ``connected=False`` flags it). The
    default panel is farthest-point (whose first hop doubles as the SSSP
    2-approx upper — running ``DeltaSteppingEstimator`` too would repeat
    that exact Bellman-Ford) plus the cluster-quotient pipeline — or, on a
    session in dynamic mode (``apply_updates``), the maintained
    ``DynamicQuotientEstimator`` so the upper side rides the repaired
    decomposition instead of re-decomposing."""

    name: ClassVar[str] = "interval"

    estimators: Tuple = ()

    def estimate(self, session: GraphSession) -> DiameterInterval:
        upper_est = (DynamicQuotientEstimator()
                     if getattr(session, "_dynamic", None) is not None
                     else ClusterQuotientEstimator())
        panel = self.estimators or (LowerBoundEstimator(), upper_est)
        with Timer() as t:
            results: Dict[str, DiameterEstimate] = {}
            for e in panel:
                key, dup = e.name, 2
                while key in results:  # multi-instance panels (e.g. seeds)
                    key, dup = f"{e.name}#{dup}", dup + 1
                results[key] = e.estimate(session)
        lowers = [r.lower for r in results.values() if r.lower is not None]
        uppers = [r.upper for r in results.values() if r.upper is not None]
        if not uppers:
            raise ValueError("interval panel produced no upper bound "
                             "(include a cluster-quotient or SSSP estimator)")
        flags = {r.connected for r in results.values()}
        if len(flags) > 1:
            log.warning("estimators disagree on connectivity: %s",
                        {k: r.connected for k, r in results.items()})
        lower, upper = max(lowers, default=0), min(uppers)
        if lower > upper:
            raise AssertionError(
                f"certified bracket violated: lower {lower} > upper {upper}")
        return DiameterInterval(
            lower=lower, upper=upper,
            connected=all(flags),
            estimates=results,
            pipeline=PipelineMetrics.merge(
                r.pipeline for r in results.values()),
            seconds=t.seconds,
        )

"""Graph-analytics launcher: the paper's diameter-approximation pipeline on
a resident ``GraphSession`` (open once, query with any estimator).

  PYTHONPATH=src python -m repro.launch.diameter --graph road --n 20000 \
      [--variant stop] [--delta-init avg] [--tau 16] \
      [--levels 2] [--tau-solve 64] \
      [--backend single|sharded|pallas] [--comm halo] [--partition cluster] \
      [--compare-sssp] [--interval]

``--levels N`` runs the multi-level quotient cascade (``CascadeEstimator``):
whenever the quotient still exceeds ``--tau-solve`` clusters, the engine
re-enters on the quotient itself (up to N extra levels) before the batched
BF solve. ``--compare-sssp`` and ``--interval`` run the competitor
estimators against the SAME session — no re-upload between methods.
``--distributed`` is kept as an alias for ``--backend sharded``.
"""
from __future__ import annotations

import argparse
import contextlib

import jax

from repro.common import enable_compile_cache, get_logger
from repro.config.base import GraphEngineConfig
from repro.core import (
    CascadeEstimator,
    ClusterQuotientEstimator,
    DeltaSteppingEstimator,
    IntervalEstimator,
    check_engine_mode,
    cluster,
    open_session,
)
from repro.graph import GraphStore, grid_mesh, random_geometric, social_like
from repro.runtime import telemetry
from repro.runtime.fault import EXIT_PREEMPTED, Preempted, PreemptionGuard

log = get_logger("repro.diameter")


def add_tau_argument(ap: argparse.ArgumentParser) -> None:
    """The shared --tau CLI contract (also used by launch/serve.py)."""
    ap.add_argument("--tau", type=int, default=None,
                    help="decomposition tau (>= 1); default: the paper's "
                         "n/1000 rule via tau_for()")


def add_cascade_arguments(ap: argparse.ArgumentParser) -> None:
    """The shared --levels/--tau-solve CLI contract (also launch/serve.py)."""
    ap.add_argument("--levels", type=int, default=0,
                    help="extra quotient-cascade decomposition levels "
                         "(0 = flat single-level pipeline)")
    ap.add_argument("--tau-solve", type=int, default=None,
                    help="quotient solve budget (>= 2): cascade whenever the "
                         "quotient exceeds this many clusters; default "
                         "DEFAULT_TAU_SOLVE")


def add_autotune_argument(ap: argparse.ArgumentParser) -> None:
    """The shared --autotune CLI contract (also used by launch/serve.py)."""
    ap.add_argument("--autotune", default="off",
                    choices=["off", "auto", "record"],
                    help="graph-statistics autotuner (core/autotune.py): "
                         "derive tau/tau-solve/delta-init/kernel tiling from "
                         "one device stats pass; explicit flags stay pinned. "
                         "'record' persists the tuning cache to JSON")


def add_engine_mode_argument(ap: argparse.ArgumentParser) -> None:
    """The shared --engine-mode CLI contract (also used by launch/serve.py).

    Deliberately NOT an argparse ``choices`` list: unknown names flow into
    ``check_engine_mode`` so the CLI and the library raise the same
    ValueError listing the valid modes (regression-tested, mirroring the
    serve.py estimator-name contract).
    """
    ap.add_argument("--engine-mode", default="stages",
                    help="decomposition mode (core/engine.py): 'stages' "
                         "(paper stage loop, default), 'oneshot' "
                         "(exponential-shift single fixpoint), or 'auto' "
                         "(defer to the autotuning record)")
    ap.add_argument("--deterministic", action="store_true",
                    help="oneshot mode: hash-derived shifts — the "
                         "decomposition is a seed-independent function of "
                         "the graph")


def add_telemetry_argument(ap: argparse.ArgumentParser) -> None:
    """The shared --telemetry-out CLI contract (also used by serve.py)."""
    ap.add_argument("--telemetry-out", default=None, metavar="DIR",
                    help="write a span trace (trace.json, loads in "
                         "ui.perfetto.dev), spans.jsonl and metrics.prom "
                         "under DIR. Tracing adds zero host syncs: the "
                         "transfer-equality contracts hold bit-identically "
                         "with it on (see docs/engine.md, Telemetry)")


def validate_tau(ap: argparse.ArgumentParser, tau) -> None:
    if tau is not None and tau < 1:
        ap.error(f"--tau must be >= 1 (got {tau}); omit it to use the "
                 "paper's n/1000 default")


def validate_cascade(ap: argparse.ArgumentParser, args) -> None:
    if args.levels < 0:
        ap.error(f"--levels must be >= 0 (got {args.levels})")
    if args.tau_solve is not None and args.tau_solve < 2:
        ap.error(f"--tau-solve must be >= 2 (got {args.tau_solve})")


def build_graph(kind: str, n: int, seed: int):
    if kind == "road":
        return random_geometric(n, avg_degree=3.0, seed=seed)
    if kind == "social":
        import math
        return social_like(max(int(math.log2(max(n, 2))), 4), 8, seed=seed,
                           weight_dist="uniform", high=2**26)
    if kind == "mesh":
        side = max(int(n ** 0.5), 4)
        return grid_mesh(side, "bimodal", heavy_w=10**6, heavy_p=0.1, seed=seed)
    raise ValueError(kind)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="road", choices=["road", "social", "mesh"])
    ap.add_argument("--n", type=int, default=10_000)
    add_tau_argument(ap)
    add_cascade_arguments(ap)
    add_autotune_argument(ap)
    add_engine_mode_argument(ap)
    add_telemetry_argument(ap)
    ap.add_argument("--variant", default="stop", choices=["stop", "complete"])
    ap.add_argument("--delta-init", default="avg")
    ap.add_argument("--cluster2", action="store_true")
    ap.add_argument("--backend", default="single",
                    choices=["single", "sharded", "pallas"])
    ap.add_argument("--distributed", action="store_true",
                    help="alias for --backend sharded")
    ap.add_argument("--comm", default="halo", choices=["halo", "allgather"],
                    help="sharded collective: halo (static boundary-row "
                         "exchange, default) or allgather (full-plane "
                         "baseline); results are byte-identical")
    ap.add_argument("--partition", default="range", choices=["range", "cluster"],
                    help="sharded backend node relabeling (cluster = "
                         "locality-aware, from a pilot decomposition)")
    ap.add_argument("--shards", type=int, default=0,
                    help="GraphStore shard count (0 = device count for the "
                         "sharded backend, unsharded otherwise); >1 also "
                         "works with --backend single for storage-level "
                         "slab/halo introspection")
    ap.add_argument("--compress", action="store_true",
                    help="hold resident GraphStore slabs compressed "
                         "(lossless delta codec, decompressed on demand)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="arm stage-boundary checkpointing of the "
                         "decomposition state (preemption-safe; see "
                         "checkpoint/checkpoint.py)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest stage checkpoint in "
                         "--checkpoint-dir (byte-identical finish)")
    ap.add_argument("--compare-sssp", action="store_true")
    ap.add_argument("--interval", action="store_true",
                    help="run the full estimator panel and report the "
                         "certified [lower, upper] bracket")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    validate_tau(ap, args.tau)
    validate_cascade(ap, args)
    check_engine_mode(args.engine_mode)  # before any graph/device work
    enable_compile_cache()
    backend_kind = "sharded" if args.distributed else args.backend

    g = build_graph(args.graph, args.n, args.seed)
    log.info("graph: %d nodes, %d directed edges", g.n_nodes, g.n_edges)
    cfg = GraphEngineConfig(variant=args.variant, delta_init=args.delta_init,
                            use_cluster2=args.cluster2, seed=args.seed,
                            backend=backend_kind, comm=args.comm,
                            mode=args.engine_mode,
                            deterministic=args.deterministic)

    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    shards = args.shards
    if shards == 0 and backend_kind == "sharded":
        shards = int(jax.device_count())
    store = None
    if shards > 1 or args.compress:
        centers = None
        if backend_kind == "sharded" and args.partition == "cluster":
            # pilot decomposition -> locality-aware relabeling inside the
            # store -> smaller halo for the sharded grow path
            pilot = cluster(g, max(16 if args.tau is None else args.tau, 4),
                            seed=args.seed)
            centers = pilot.final_c
        store = GraphStore(g, n_shards=max(shards, 1), centers=centers,
                           compress=args.compress)
        log.info("GraphStore: %d shards, halo_k=%d, halo %d B/superstep vs "
                 "full-plane %d B/superstep, resident %d B (raw %d B)",
                 store.n_shards, store.halo_k(),
                 store.halo_bytes_per_superstep(),
                 store.fullplane_bytes_per_superstep(),
                 store.resident_bytes(), store.raw_bytes())
    # the session builds the backend from cfg.backend (make_backend hands a
    # GraphStore's prebuilt slab/halo layout to the DistributedEngine)

    guard = PreemptionGuard() if args.checkpoint_dir else None
    # --telemetry-out arms the span tracer for the whole session lifetime
    # (open/pack, decomposition stages, quotient, solve); the estimators'
    # spans no-op when it is absent
    tracer = telemetry.Tracer() if args.telemetry_out else None
    tele_cm = (telemetry.tracing(tracer) if tracer is not None
               else contextlib.nullcontext())
    with tele_cm:
        sess = open_session(g if store is None else None, cfg,
                            tau=args.tau, tau_solve=args.tau_solve,
                            autotune=args.autotune, store=store,
                            checkpoint_dir=args.checkpoint_dir,
                            resume=args.resume, guard=guard)
        if sess.tuning is not None:
            t = sess.tuning
            log.info("autotuned: tau=%d tau_solve=%d levels=%d delta0=%d "
                     "tiling=(%d,%d) fuse=%d", t.tau, t.tau_solve, t.levels,
                     t.delta_init, t.node_tile, t.edge_block, t.fuse)
        if args.levels > 0:
            estimator = CascadeEstimator(levels=args.levels)
        elif sess.tuning is not None:
            estimator = None  # session default: tuned cascade depth
        else:
            estimator = ClusterQuotientEstimator()
        try:
            with (guard if guard is not None else contextlib.nullcontext()):
                est = sess.estimate(estimator)
        except Preempted as p:
            log.warning("preempted at stage %d; checkpoint durable at %s — "
                        "rerun with --resume to finish byte-identically",
                        p.stage, p.path)
            return EXIT_PREEMPTED
        log.info("Phi_approx = %d  (quotient %d + 2 x radius %d)  "
                 "clusters=%d stages=%d growing_steps=%d connected=%s  %.2fs",
                 est.phi_approx, est.phi_quotient, est.radius, est.n_clusters,
                 est.n_stages, est.growing_steps, est.connected, est.seconds)
        if est.pipeline is not None:
            pm = est.pipeline
            log.info("pipeline host syncs: %d total (decompose %d + finalize "
                     "%d + quotient %d + solve %d); solve supersteps=%d "
                     "q_edges=%d",
                     pm.total_host_syncs, pm.decompose_syncs,
                     pm.finalize_syncs, pm.quotient_syncs, pm.solve_syncs,
                     pm.solve_supersteps, pm.n_quotient_edges)
            if pm.cascade_levels:
                log.info("cascade: %d extra levels, clusters per level %s, "
                         "supersteps per level %s, syncs per level %s",
                         pm.cascade_levels, pm.level_clusters,
                         pm.level_supersteps, pm.level_syncs)

        if args.compare_sssp:
            # same resident session: the competitor re-uses the device
            # buffers
            sssp = sess.estimate(DeltaSteppingEstimator(seed=args.seed))
            # phi_approx (= 2 ecc) stays an int even when upper is dropped
            # on disconnected inputs
            log.info("SSSP-BF: lower=%d 2xecc=%d supersteps=%d connected=%s  "
                     "(CLUSTER rounds: %d -> %.1fx fewer)",
                     sssp.lower, sssp.phi_approx, sssp.growing_steps,
                     sssp.connected, est.growing_steps,
                     sssp.growing_steps / max(est.growing_steps, 1))
        if args.interval:
            iv = sess.estimate(IntervalEstimator())
            log.info("certified bracket: diameter in [%d, %d] connected=%s "
                     "(merged host syncs=%d) %.2fs", iv.lower, iv.upper,
                     iv.connected, iv.pipeline.total_host_syncs, iv.seconds)
        log.info("session metrics: %s", sess.metrics)
        if args.telemetry_out:
            registry = telemetry.MetricsRegistry()
            if est.pipeline is not None:
                registry.ingest(est.pipeline, "pipeline")
            registry.ingest(sess.metrics, "session")
            written = telemetry.write_telemetry(args.telemetry_out, tracer,
                                                registry)
            log.info("telemetry: %d spans, %d measured transfers attributed "
                     "-> %s", len(tracer.spans), tracer.total_transfers(),
                     sorted(written.values()))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

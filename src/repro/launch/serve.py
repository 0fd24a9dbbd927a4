"""Batched serving driver: prefill + steady-state decode with a KV cache,
plus a graph-analytics mode serving diameter queries through resident
``GraphSession``s — open each graph once, query many times with zero backend
rebuilds and zero edge re-uploads (asserted via ``SessionMetrics``). With
``--update-trace`` the mode becomes a DYNAMIC replay: seeded
``temporal_trace`` mutation batches are interleaved with the queries, every
post-update bracket is checked, and the amortized update cost is reported
against a full re-decomposition.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --mode graph-diameter \
      --batch 8 --graph-n 2000 --queries 3 [--graph road] [--tau 12] \
      [--estimator cluster|sssp|lower|interval|cascade|dynamic] \
      [--levels 2] [--tau-solve 64] \
      [--update-trace 4] [--update-events 64] [--update-mix mixed] \
      [--check-amortization 2.0] [--sync-budget bench]
"""
from __future__ import annotations

import argparse
import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import bench_engine_path, enable_compile_cache, get_logger
from repro.config.registry import get_arch
from repro.models import transformer as tf_mod
from repro.runtime import telemetry
from repro.runtime.fault import EXIT_PREEMPTED, Preempted, PreemptionGuard

log = get_logger("repro.serve")

ESTIMATORS = ("cluster", "sssp", "lower", "interval", "cascade", "dynamic")

# update-trace event mixes: (p_insert, p_reweight, p_delete)
UPDATE_MIXES = {"insert": (1.0, 0.0, 0.0),
                "mixed": (0.4, 0.4, 0.2),
                "delete": (0.1, 0.1, 0.8)}


def _check_estimator_name(name: str) -> None:
    if name not in ESTIMATORS:
        raise ValueError(
            f"unknown estimator {name!r} (expected one of {ESTIMATORS})")


def _make_estimator(name: str, levels: int = 0):
    from repro.core import (CascadeEstimator, ClusterQuotientEstimator,
                            DeltaSteppingEstimator, DynamicQuotientEstimator,
                            IntervalEstimator, LowerBoundEstimator)

    _check_estimator_name(name)
    if name == "cascade":
        # --levels 0 with an explicit --estimator cascade keeps the
        # estimator's own default depth
        return CascadeEstimator(levels=levels) if levels else CascadeEstimator()
    return {"cluster": ClusterQuotientEstimator,
            "sssp": DeltaSteppingEstimator,
            "lower": LowerBoundEstimator,
            "interval": IntervalEstimator,
            "dynamic": DynamicQuotientEstimator}[name]()


def _resolve_sync_budget(spec: str, estimator: str = "cluster"):
    """"off" -> None (disabled), "bench" -> the recorded BENCH_engine.json
    budget (the "cascade" block's when serving the cascade — its extra
    levels legitimately cost more syncs than the flat pipeline — else the
    "pipeline" block's, which also covers "dynamic": a maintained query
    syncs strictly less than the flat pipeline), anything else -> an
    explicit integer ceiling (0 is a real ceiling — every host sync fails
    it — not "off"). Unknown estimator names are rejected outright instead
    of silently falling through to the cluster default."""
    _check_estimator_name(estimator)
    if spec == "off":
        return None
    if spec == "bench":
        with open(bench_engine_path()) as f:
            bench = json.load(f)
        if estimator == "cascade" and "cascade" in bench:
            return int(bench["cascade"]["host_syncs_total"])
        return int(bench["pipeline"]["host_syncs_total"])
    return int(spec)


def _query_syncs(result) -> int:
    """Host syncs to judge against the per-pipeline budget. For a composite
    (DiameterInterval) the merged panel total would trivially exceed a
    single-pipeline budget, so judge its WORST member instead — every
    estimator in the panel must individually stay within budget."""
    estimates = getattr(result, "estimates", None)
    if estimates:
        return max(_query_syncs(r) for r in estimates.values())
    pm = getattr(result, "pipeline", None)
    return pm.total_host_syncs if pm is not None else 0


def serve_graph_diameter(args) -> int:
    """Steady-state diameter serving on resident sessions.

    Every graph is opened ONCE into a ``SessionPool`` (all sessions share
    one edge-pad bucket, hence one compiled pipeline); each session then
    serves ``--queries`` queries. The first query of the first session pays
    compilation; everything after streams warm. Exit status is non-zero
    when ``--check-amortization`` / ``--sync-budget`` contracts are
    violated, or when any warm query rebuilt a backend or re-uploaded edge
    arrays (the ``SessionMetrics`` contract)."""
    from repro.common import next_multiple
    from repro.config.base import GraphEngineConfig
    from repro.core import DiameterInterval, SessionPool
    from repro.launch.diameter import build_graph

    from repro.graph import temporal_trace

    graphs = [build_graph(args.graph, args.graph_n, seed=s)
              for s in range(args.batch)]
    cfg = GraphEngineConfig(backend=args.backend, autotune=args.autotune,
                            mode=args.engine_mode,
                            deterministic=args.deterministic)
    # --levels alone activates the cascade (same contract as
    # launch/diameter.py); other estimators don't take levels
    est_name = args.estimator
    if args.levels and est_name == "cluster":
        est_name = "cascade"
    elif args.levels and est_name not in ("cascade",):
        log.warning("--levels %d is ignored by --estimator %s",
                    args.levels, est_name)
    if args.update_trace and est_name == "cluster":
        # replaying mutations against per-query full re-decompositions
        # would defeat the dynamic subsystem being exercised
        log.info("--update-trace: serving through the maintained "
                 "dynamic-quotient estimator")
        est_name = "dynamic"
    estimator = _make_estimator(est_name, levels=args.levels)
    sync_budget = _resolve_sync_budget(args.sync_budget, est_name)
    traces = []
    if args.update_trace:
        p_ins, p_rw, p_del = UPDATE_MIXES[args.update_mix]
        events = args.update_events or max(g.n_edges // 200 for g in graphs)
        traces = [temporal_trace(g, args.update_trace,
                                 events_per_batch=events, p_insert=p_ins,
                                 p_reweight=p_rw, p_delete=p_del, seed=s)
                  for s, g in enumerate(graphs)]

    # preemption-safe serving: a checkpoint-dir arms per-session stage
    # checkpointers (subdirs g0, g1, ...) under one process-level guard;
    # a SIGTERM mid-decomposition checkpoints, exits EXIT_PREEMPTED (75),
    # and a --resume rerun finishes the bracket byte-identically
    pguard = PreemptionGuard() if args.checkpoint_dir else None
    pool = SessionPool(cfg, tau_solve=args.tau_solve,
                       checkpoint_dir=args.checkpoint_dir,
                       shards=args.shards, resume=args.resume, guard=pguard)
    # one shared edge-pad bucket across the whole batch (per-graph buckets
    # would pad to different sizes and recompile)
    e_pad = next_multiple(max(g.n_edges for g in graphs) or 1,
                          pool.edge_bucket)
    # --telemetry-out arms the span tracer (zero host syncs: span
    # attribution is meter-stack bookkeeping, never a jax transfer — the
    # --sync-budget contract below holds bit-identically with it on) and
    # a registry fed per-estimator latency histograms by the query loop
    tracer = telemetry.Tracer() if args.telemetry_out else None
    registry = telemetry.MetricsRegistry() if args.telemetry_out else None
    tele_cm = (telemetry.tracing(tracer) if tracer is not None
               else contextlib.nullcontext())
    with tele_cm, pool:
        sessions = [pool.open(g, tau=args.tau, e_pad=e_pad) for g in graphs]
        if args.preempt_after:
            # TEST HOOK (kill-and-resume smoke): real SIGTERM at this stage
            # boundary of the FIRST session's first decomposition
            ck = sessions[0].checkpointer
            if ck is None:
                raise SystemExit("--preempt-after requires --checkpoint-dir")
            ck.preempt_after_stage = args.preempt_after

        worst_syncs, failures = 0, []
        # per-query results are COLLECTED here and logged in one pass after
        # the loop: the timed serving loop does no formatting/IO, and every
        # scalar it touches rides the batched guard.fetch sites inside the
        # estimators (sync-lint contract — see repro.analysis)
        records: list[tuple] = []  # (graph, round, result, syncs, dt)
        update_lines: list[tuple] = []
        from repro.analysis import guard

        t0 = telemetry.clock()
        cold: list[float] = []  # first query per session (session 0 compiles)
        warm: list[float] = []
        try:
            with (pguard if pguard is not None
                  else contextlib.nullcontext()), \
                    guard.measured_transfers() as meter, \
                    telemetry.span("serve.replay", batch=args.batch,
                                   queries=args.queries, estimator=est_name):
                for round_idx in range(args.queries):
                    if round_idx == 1:
                        # the SessionMetrics contract: from here on, NOTHING
                        # may build a backend or upload an edge array
                        builds0 = pool.metrics.backend_builds
                        uploads0 = pool.metrics.edge_uploads
                    if round_idx and traces:
                        # replay: one mutation batch per session between
                        # rounds (update work counts in DynamicMetrics, not
                        # the warm-query residency counters — the buffers
                        # are mutated IN PLACE)
                        for i, sess in enumerate(sessions):
                            if round_idx - 1 < len(traces[i]):
                                with telemetry.span("serve.update", graph=i,
                                                    batch=round_idx - 1):
                                    rep = sess.apply_updates(
                                        traces[i][round_idx - 1])
                                update_lines.append((i, round_idx - 1, rep))
                    for i, sess in enumerate(sessions):
                        tq = telemetry.clock()
                        with telemetry.span("serve.query", graph=i,
                                            round=round_idx) as qs:
                            res = sess.estimate(estimator)
                            syncs = _query_syncs(res)
                            qs.set(host_syncs=syncs)
                        dt = telemetry.clock() - tq
                        (cold if round_idx == 0 else warm).append(dt)
                        if registry is not None:
                            kind = "cold" if round_idx == 0 else "warm"
                            registry.observe(
                                f"serve.latency.{est_name}", dt)
                            registry.observe(
                                f"serve.latency.{est_name}.{kind}", dt)
                        worst_syncs = max(worst_syncs, syncs)
                        records.append((i, round_idx, res, syncs, dt))
        except Preempted as p:
            log.warning("preempted at stage %d; checkpoint durable at %s — "
                        "rerun with --resume to finish byte-identically",
                        p.stage, p.path)
            return EXIT_PREEMPTED
        total = telemetry.clock() - t0

        for i, u_idx, rep in update_lines:
            log.info("graph[%d] u%d: %s sweeps=%d dead=%d", i, u_idx,
                     rep.action, rep.supersteps, rep.dead_nodes)
        for i, round_idx, res, syncs, dt in records:
            if isinstance(res, DiameterInterval):
                log.info("graph[%d] q%d: diameter in [%d, %d] connected=%s "
                         "host_syncs=%d %.3fs", i, round_idx, res.lower,
                         res.upper, res.connected, syncs, dt)
            else:
                log.info("graph[%d] q%d: phi=%d clusters=%d connected=%s "
                         "host_syncs=%d %.3fs", i, round_idx, res.phi_approx,
                         res.n_clusters, res.connected, syncs, dt)
        log.info("measured device->host transfers: %d over %d queries "
                 "(all via guard.fetch)", meter.transfers, len(records))

        m = pool.metrics
        if args.queries > 1:
            rebuilds = m.backend_builds - builds0
            reuploads = m.edge_uploads - uploads0
            log.info("warm path: %d backend rebuilds, %d edge re-uploads "
                     "over %d warm queries", rebuilds, reuploads, len(warm))
            if rebuilds or reuploads:
                failures.append(
                    f"warm queries must be resident: {rebuilds} rebuilds, "
                    f"{reuploads} re-uploads")
        if traces:
            from repro.core import IntervalEstimator

            # drain any batches beyond the query rounds, then certify the
            # final bracket of every mutated session
            for i, sess in enumerate(sessions):
                for b in traces[i][max(args.queries - 1, 0):]:
                    sess.apply_updates(b)
                iv = sess.estimate(IntervalEstimator())  # raises if inverted
                log.info("graph[%d] final bracket [%d, %d] connected=%s",
                         i, iv.lower, iv.upper, iv.connected)
            dm = [s.dynamic.metrics for s in sessions]
            upd_steps = sum(m.update_supersteps + m.rebuild_supersteps
                            for m in dm)
            upd_batches = sum(m.batches for m in dm)
            baseline = max(m.baseline_supersteps for m in dm)
            amort_upd = upd_steps / max(upd_batches, 1)
            log.info("update replay: %d batches, %.1f supersteps/batch "
                     "amortized vs %d for a full re-decomposition (%d "
                     "rebuilds)", upd_batches, amort_upd, baseline,
                     sum(m.full_rebuilds for m in dm))
            if args.check_update_cost and baseline and \
                    amort_upd * args.check_update_cost > baseline:
                failures.append(
                    f"amortized update cost {amort_upd:.1f} supersteps/batch "
                    f"exceeds 1/{args.check_update_cost:g} of a full "
                    f"re-decomposition ({baseline})")
        t_cold = cold[0]
        steady = (cold[1:] + warm) or [t_cold]
        per_warm = sum(steady) / len(steady)
        amort = t_cold / max(per_warm, 1e-9)
        log.info("opened %d sessions; first query %.2fs (compile), steady "
                 "state %.3fs/query (%.1f queries/s, %.1fx amortization), "
                 "%.2fs total", len(sessions), t_cold, per_warm,
                 1.0 / max(per_warm, 1e-9), amort, total)
        log.info("session metrics: %s", m)
        if args.check_amortization and amort < args.check_amortization:
            failures.append(f"amortization {amort:.1f}x below required "
                            f"{args.check_amortization:.1f}x")
        if sync_budget is not None and worst_syncs > sync_budget:
            failures.append(f"host syncs {worst_syncs} exceed the recorded "
                            f"bench budget {sync_budget}")
        if args.telemetry_out:
            registry.ingest(m, "session")
            registry.ingest(meter, "serve.transfers")
            for i, sess in enumerate(sessions):
                dyn = getattr(sess, "dynamic", None)
                if dyn is not None:
                    registry.ingest(dyn.metrics, f"dynamic.g{i}")
            written = telemetry.write_telemetry(
                args.telemetry_out, tracer, registry)
            log.info("telemetry: %d spans, %d measured transfers attributed "
                     "-> %s", len(tracer.spans), tracer.total_transfers(),
                     sorted(written.values()))
    for f in failures:
        log.error("FAIL: %s", f)
    return 1 if failures else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="lm", choices=["lm", "graph-diameter"])
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    # graph-diameter mode
    ap.add_argument("--graph", default="road",
                    choices=["road", "social", "mesh"])
    from repro.launch.diameter import (add_autotune_argument,
                                       add_cascade_arguments,
                                       add_engine_mode_argument,
                                       add_tau_argument,
                                       add_telemetry_argument,
                                       validate_cascade, validate_tau)

    ap.add_argument("--graph-n", type=int, default=2000)
    add_tau_argument(ap)
    add_cascade_arguments(ap)
    add_autotune_argument(ap)
    add_engine_mode_argument(ap)
    add_telemetry_argument(ap)
    ap.add_argument("--backend", default="single",
                    choices=["single", "sharded", "pallas"])
    ap.add_argument("--queries", type=int, default=2,
                    help="diameter queries per resident session")
    ap.add_argument("--estimator", default="cluster", choices=ESTIMATORS)
    ap.add_argument("--shards", type=int, default=0,
                    help="back each session with a partition-sharded "
                         "GraphStore of this many shards (0 = flat storage)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="arm per-session stage-boundary checkpointing "
                         "(preemption-safe serving; subdirs g0, g1, ...)")
    ap.add_argument("--resume", action="store_true",
                    help="continue decompositions from the latest stage "
                         "checkpoints in --checkpoint-dir")
    ap.add_argument("--preempt-after", type=int, default=0,
                    help="TEST HOOK: deliver a real SIGTERM at this stage "
                         "boundary of the first session's decomposition "
                         "(kill-and-resume smoke; requires --checkpoint-dir)")
    ap.add_argument("--update-trace", type=int, default=0,
                    help="replay this many temporal_trace mutation batches "
                         "per session, interleaved with the query rounds "
                         "(0 = static serving)")
    ap.add_argument("--update-events", type=int, default=0,
                    help="events per mutation batch (0 = ~0.5%% of edges)")
    ap.add_argument("--update-mix", default="mixed",
                    choices=sorted(UPDATE_MIXES))
    ap.add_argument("--check-update-cost", type=float, default=0.0,
                    help="fail unless amortized update supersteps stay "
                         "below baseline/THIS (e.g. 5 = the 1/5 contract; "
                         "0 = off)")
    ap.add_argument("--check-amortization", type=float, default=0.0,
                    help="fail unless cold/warm query amortization reaches "
                         "this ratio (0 = off)")
    ap.add_argument("--sync-budget", default="off",
                    help="per-query host-sync ceiling: off | bench "
                         "(use the recorded BENCH_engine.json value) | <int>")
    args = ap.parse_args()
    validate_tau(ap, args.tau)
    validate_cascade(ap, args)
    from repro.core import check_engine_mode
    check_engine_mode(args.engine_mode)  # before any graph/device work
    if args.queries < 1:
        ap.error("--queries must be >= 1")
    if args.batch < 1:
        ap.error("--batch must be >= 1")
    if args.update_trace < 0:
        ap.error("--update-trace must be >= 0")
    if args.update_events < 0:
        ap.error("--update-events must be >= 0")
    if args.sync_budget not in ("off", "bench"):
        try:
            int(args.sync_budget)
        except ValueError:
            ap.error(f"--sync-budget must be off | bench | <int> "
                     f"(got {args.sync_budget!r})")

    enable_compile_cache()
    if args.mode == "graph-diameter":
        return serve_graph_diameter(args)

    cfg = get_arch(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(0)
    params = tf_mod.init_params(cfg, key)
    max_len = args.prompt_len + args.gen

    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)
    cache = tf_mod.init_cache(cfg, args.batch, max_len)

    decode = jax.jit(lambda p, c, t: tf_mod.decode_step(p, c, t, cfg))

    # prefill by streaming the prompt through decode (keeps ONE compiled
    # step; a production server would batch-prefill via forward())
    t0 = telemetry.clock()
    logits = None
    for i in range(args.prompt_len):
        logits, cache = decode(params, cache, prompts[:, i:i+1])
    jax.block_until_ready(logits)
    t_prefill = telemetry.clock() - t0

    toks = []
    t0 = telemetry.clock()
    cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for i in range(args.gen):
        toks.append(cur)
        logits, cache = decode(params, cache, cur)
        if args.temperature > 0:
            key, sub = jax.random.split(key)
            cur = jax.random.categorical(
                sub, logits / args.temperature)[:, None].astype(jnp.int32)
        else:
            cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = telemetry.clock() - t0

    out = np.asarray(jnp.concatenate(toks, axis=1))  # sync: one post-loop fetch of all decoded ids
    log.info("prefill %.2fs (%.1f tok/s)  decode %.2fs (%.1f tok/s/seq)",
             t_prefill, args.batch * args.prompt_len / t_prefill,
             t_decode, args.gen / t_decode)
    log.info("generated ids[0,:8] = %s", out[0, :8].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

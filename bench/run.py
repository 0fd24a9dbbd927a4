#!/usr/bin/env python3
"""Benchmark of the certified-bracket path on the chip.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process is one run of one cell of ``BENCHMARK.json``:

1. Fail, before any work and printing no result, unless JAX's first device
   is a TPU and there are as many as the cell asks for.
2. Turn on the persistent compilation cache (``<checkout>/.jax_cache``, or
   ``JAX_COMPILATION_CACHE_DIR``) for every program.
3. Generate the cell's graph on the host from ``--seed`` (``families/``).
4. ``open_session`` on it and wait until its edges are resident.
5. Warm up: query 0 in full, then, for query 1, 2, ..., the panel
   entries whose programs' shapes follow the query's seed
   (``SEEDED_SHAPES``), until the queries' time without builds covers
   ``WARM_COVER`` windows. The window repeats these queries, so it finds
   every program built; what the warm-up compiled is set-up.
6. The window: one client sends query 0, 1, 2, ... back to back while
   fewer than ``--seconds`` have passed. The timed interval ends with the
   last query started inside it; each query ends in a host fetch.
7. ``--trace 1``: the program's spans are recorded through the window, and
   query 0 runs once more under the profiler.
8. Close the session, check a sample of the window's answers drawn from
   the seed against the plain reference (``reference/``), print the
   numbers compared beside their limits, and print the result line.

With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics; each is read by ``metrics/<name>.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import spec, traffic as traffic_gen  # noqa: E402

# The warm-up's queries, without their build time, cover this share of
# the window: a query's first run also spends time that no build event
# reports (executables loaded onto the chip, first allocations), so its
# estimate errs long ...
WARM_COVER = 1.5
# ... within this many queries.
WARM_MAX = 200
# Estimator classes whose programs' shapes follow the query's seed: the
# quotient solve pads its cluster count to a bucket, and the count follows
# the decomposition's random centers. Every other entry's shapes are fixed
# by the graph, so query 0 builds them all.
SEEDED_SHAPES = ("ClusterQuotientEstimator", "CascadeEstimator")
# Program spans whose time the layer metrics read.
SPAN_NAMES = ("engine.stage", "engine.oneshot", "engine.finalize",
              "quotient.build", "quotient.solve", "cascade.level",
              "sssp.solve")
# Kernels found in the device trace: metric prefix -> operation-name part.
KERNELS = {"edge_relax": "%_edge_relax_pallas_jit"}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Events whose seconds a program's build spends: tracing, lowering, and
# compiling or loading from the cache.
BUILD_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration",
                COMPILE_EVENT)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# Engine settings a test puts in place of the mix's (the CPU has no
# compiled kernels); empty in every run.
ENGINE_OVERRIDES: Dict[str, Any] = {}


class NoChip(RuntimeError):
    pass


def require_chip(chips: int):
    """JAX's devices, where the first is a TPU and there are ``chips``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{len(devices)}")
    return devices


@dataclass
class QueryRecord:
    index: int
    seeds: List[int]
    start: float
    end: float
    result: Any = None
    error: Optional[str] = None
    transfers: int = 0
    captures: List = field(default_factory=list)
    programs: int = 0          # programs built (compiled or loaded)
    compile_s: float = 0.0

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class RunRecord:
    """What a metric reader reads."""
    cell: str
    config: dict
    traffic: dict
    setup_s: float
    window_s: float
    queries: List[QueryRecord]
    peak_hbm_bytes: Optional[int]
    device_kind: str
    compiles_in_window: int
    kernel_shapes: Dict[str, Dict[str, int]]
    spans: List = field(default_factory=list)
    trace: Optional[dict] = None


class CompileCounter:
    """Counts the programs JAX builds (compiles, or loads from the
    persistent cache) and the seconds it spends on them, and keeps when
    each step of a build (trace, lowering, compile) ended."""

    def __init__(self):
        self.programs = 0
        self.hits = 0
        self.seconds = 0.0
        self.ends: List[Tuple[float, float]] = []   # (clock at end, seconds)

    def on_duration(self, event, seconds, **kw):
        if event == COMPILE_EVENT:
            self.programs += 1
            self.seconds += seconds
        if event in BUILD_EVENTS:
            self.ends.append((time.perf_counter(), seconds))

    def seconds_between(self, a: float, b: float) -> float:
        """Seconds of the build steps that ended between clock readings a
        and b."""
        return sum(s for t, s in self.ends if a < t <= b)

    def on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def install(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)


_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    """The process's one counter (JAX's listeners cannot be removed one
    by one)."""
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
        _COUNTER.install()
    return _COUNTER


_CAPTURES: List = []   # the current run's decompositions, in order


def _capture_decompositions() -> None:
    """Keep each decomposition the cluster-quotient path makes (its host
    ``final_c``/``final_pathw`` planes) in ``_CAPTURES``, for the
    reference's certificate check. The computation is untouched."""
    import numpy as np
    import repro.core.estimators as est

    def wrap(fn):
        def captured(*a, **k):
            dec = fn(*a, **k)
            _CAPTURES.append((np.asarray(dec.final_c),
                              np.asarray(dec.final_pathw)))
            return dec
        captured.bench_capture = True
        return captured

    for name in ("cluster", "cluster2"):
        fn = getattr(est, name)
        if not getattr(fn, "bench_capture", False):
            setattr(est, name, wrap(fn))


def _kernel_shapes(backend) -> Dict[str, Dict[str, int]]:
    """The shapes of the edge-relax kernel's calls, where the session's
    backend runs it (the blocked layout of ``PallasBackend``)."""
    try:
        return {"edge_relax": {
            "n_blocks": int(backend.graph_args()[0].shape[0]),
            "edge_block": int(backend.edge_block),
            "n_tiles": int(backend.n_tiles),
            "node_tile": int(backend.node_tile)}}
    except AttributeError:
        return {}


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CellRun:
    """One cell's graph, resident session and query loop, after the chip
    check: what a run and the control's runs share."""

    def __init__(self, cell: spec.Cell, seed: int):
        if os.path.join(ROOT, "src") not in sys.path:
            sys.path.insert(0, os.path.join(ROOT, "src"))
        import jax

        from repro.common import enable_compile_cache

        self.cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.counter = compile_counter()

        import repro.core as core
        from repro.config.base import GraphEngineConfig
        from repro.graph.structures import EdgeList

        self.cell, self.seed, self.core = cell, seed, core
        t = time.perf_counter()
        fam = spec.family(cell.config["family"])
        self.graph = fam.generate(cell.config,
                                  traffic_gen.graph_seed(seed))
        n, src = self.graph[0], self.graph[1]
        _log(f"graph {cell.config['name']}: n={n} arcs={len(src)} "
             f"generate_s={time.perf_counter() - t:.3f}")
        cfg = GraphEngineConfig(**{**cell.traffic["engine"],
                                   **ENGINE_OVERRIDES})
        self.sess = core.open_session(EdgeList(*self.graph), cfg)
        jax.block_until_ready(self.sess.backend.graph_args())
        self.kernel_shapes = _kernel_shapes(self.sess.backend)
        _CAPTURES.clear()
        self.captures = _CAPTURES
        _capture_decompositions()

    def query(self, i: int, entries=None) -> QueryRecord:
        """Run query ``i`` of the mix, or, with ``entries``, those panel
        entries of it alone, one after another; a query that raises is
        recorded as failed."""
        from repro.analysis import guard

        q = traffic_gen.query(self.cell.traffic, self.seed, i)
        if entries is None:
            estimator = q.estimator(self.cell.traffic, self.core)
        else:
            parts = q.parts(self.cell.traffic, self.core)
            estimator = _Sequence([parts[j] for j in entries])
        c0, p0, s0 = (len(self.captures), self.counter.programs,
                      self.counter.seconds)
        rec = QueryRecord(i, q.seeds, time.perf_counter(), 0.0)
        try:
            with guard.measured_transfers() as meter:
                rec.result = self.sess.estimate(estimator)
            rec.transfers = meter.transfers
        except Exception as e:  # noqa: BLE001 - a failed query is counted
            rec.error = f"{type(e).__name__}: {e}"
            _log(f"query {i} failed:\n{traceback.format_exc()}")
        rec.end = time.perf_counter()
        rec.captures = self.captures[c0:]
        rec.programs = self.counter.programs - p0
        rec.compile_s = self.counter.seconds - s0
        return rec

    def close(self) -> None:
        self.sess.close()
        self.sess = None


@dataclass
class _Sequence:
    """Estimators run one after another on the session, as a panel runs
    them, without combining their answers."""
    parts: List

    def estimate(self, session):
        return [e.estimate(session) for e in self.parts]


def check_answers(cell: spec.Cell, graph, seed: int,
                  records: List[QueryRecord], control: Optional[str] = None
                  ) -> Dict[str, int]:
    """The numbers compared, over a sample of the answered queries drawn
    from ``seed`` with the longest among them. With ``control``, the
    control's answers stand in for the program's."""
    import numpy as np

    from bench.reference import check
    from bench.reference.graph import RefGraph

    failed = [r for r in records if r.error]
    done = [r for r in records if not r.error]
    numbers: Dict[str, int] = {"failed_queries": len(failed)}
    numbers["host_sync_gap"] = max(
        (abs(r.result.pipeline.total_host_syncs - r.transfers) for r in done),
        default=0)
    rg = RefGraph(*graph)
    rng = np.random.default_rng([int(seed) % 2**64, 7])
    k = min(int(cell.traffic.get("checked_per_run", 3)), len(done))
    order = sorted(range(len(done)), key=lambda j: -done[j].seconds)
    picked = order[:1] + [int(j) for j in rng.choice(
        order[1:], size=max(k - 1, 0), replace=False)] if done else []
    rows = []
    for j in sorted(picked):
        r = done[j]
        got = (r.result if control is None else check.expected(
            rg, cell.traffic, r.seeds, r.captures, control=control))
        rows.append(check.compare(rg, cell.traffic, r.seeds, r.captures, got))
    numbers.update(check.merge(rows))
    numbers["checked_queries"] = len(rows)
    return numbers


def is_correct(numbers: Dict[str, int]) -> bool:
    from bench.reference.check import LIMIT

    return numbers.get("checked_queries", 0) > 0 and all(
        v <= LIMIT for k, v in numbers.items() if k != "checked_queries")


def _metric_values(names, run: RunRecord) -> Dict[str, dict]:
    out = {}
    for m in names:
        v = spec.metric_reader(m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _warm_up(cr: CellRun, seconds: float) -> None:
    """Query 0 in full, then the seed-shaped panel entries of each later
    query, until the window could not reach past the queries warmed."""
    panel = cr.cell.traffic["panel"]
    seeded = [j for j, e in enumerate(panel) if e["class"] in SEEDED_SHAPES]
    r = cr.query(0)
    _log(f"warm-up query 0: {r.seconds:.3f}s programs={r.programs} "
         f"compile_s={r.compile_s:.3f}"
         + (f" error={r.error}" if r.error else ""))
    if r.error or not seeded:
        return
    # the fixed-shape entries' time in query 0 without the builds inside
    # them; a panel runs its entries back to back from the query's start
    parts = list(getattr(r.result, "estimates", {}).values()) or [r.result]
    fixed, t = 0.0, r.start
    for j, part in enumerate(parts):
        if j not in seeded:
            fixed += part.seconds - cr.counter.seconds_between(
                t, t + part.seconds)
        t += part.seconds
    fixed = max(fixed, 0.0)
    est: List[float] = []
    while len(est) < WARM_MAX:
        r = cr.query(len(est) + 1, entries=seeded)
        _log(f"warm-up query {r.index}, seed-shaped entries: {r.seconds:.3f}s"
             f" programs={r.programs} compile_s={r.compile_s:.3f}"
             + (f" error={r.error}" if r.error else ""))
        if r.error:
            return
        est.append(fixed + max(r.seconds - cr.counter.seconds_between(
            r.start, r.end), 0.0))
        # query 0 without its compiles counts as the others' median
        if statistics.median(est) + sum(est) >= WARM_COVER * seconds:
            return


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    try:
        devices = require_chip(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    dev = devices[0]

    cr = CellRun(cell, args.seed)
    _warm_up(cr, args.seconds)

    from repro.runtime import telemetry

    tracer = None
    if args.trace:
        from bench.spans import profiled_tracer
        tracer = profiled_tracer()
    window: List[QueryRecord] = []
    p_window = cr.counter.programs
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    with (telemetry.tracing(tracer) if tracer is not None
          else contextlib.nullcontext()):
        while time.perf_counter() - t_window < args.seconds:
            window.append(cr.query(len(window)))
    window_s = window[-1].end - t_window
    compiles_in_window = cr.counter.programs - p_window
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    _log(f"window: {len(window)} queries in {window_s:.3f}s "
         f"compiles_in_window={compiles_in_window} setup_s={setup_s:.3f} "
         f"peak_hbm_bytes={peak}")

    trace = None
    if args.trace:
        from bench import devtrace

        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            with telemetry.tracing(profiled_tracer()):
                traced, path = devtrace.record(lambda: cr.query(0), log_dir)
            trace = devtrace.reduce_file(path, KERNELS, SPAN_NAMES)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        _log(f"traced query 0: {traced.seconds:.3f}s "
             f"window_s={trace['window_s']:.6f} busy_s={trace['busy_s']:.6f}"
             + (f" error={traced.error}" if traced.error else ""))
    cr.close()

    run = RunRecord(
        cell=cell.name, config=cell.config, traffic=cell.traffic,
        setup_s=setup_s, window_s=window_s, queries=window,
        peak_hbm_bytes=peak, device_kind=dev.device_kind,
        compiles_in_window=compiles_in_window,
        kernel_shapes=cr.kernel_shapes,
        spans=list(tracer.spans) if tracer is not None else [],
        trace=trace)
    t = time.perf_counter()
    numbers = check_answers(cell, cr.graph, args.seed, window)
    correct = is_correct(numbers)
    _log(f"reference: checked {numbers['checked_queries']} queries in "
         f"{time.perf_counter() - t:.3f}s")

    metrics = _metric_values(cell.per_layer if args.trace
                             else cell.end_to_end, run)
    _log(f"metrics: {json.dumps(metrics)}")
    _log(f"compile cache {cr.cache_dir}: programs_built="
         f"{cr.counter.programs} cache_hits={cr.counter.hits} "
         f"compiles_in_window={compiles_in_window}")
    from bench.reference.check import LIMIT

    checks = {k: {"value": v, "limit": None if k == "checked_queries"
                  else LIMIT} for k, v in numbers.items()}
    for k, c in checks.items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out: Dict[str, Any] = {"correct": correct, "attempted": len(window),
                           "failed": numbers["failed_queries"],
                           "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

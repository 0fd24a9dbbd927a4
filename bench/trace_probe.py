#!/usr/bin/env python3
"""The program's tracing, measured in one cell on the chip.

  python3 bench/trace_probe.py --workload <cell> --seed <n> [--seconds 51] \
      [--pairs 3] [--profile 1] [--scale <s>] [--keep-trace <path.gz>]

One process, after the chip check:

1. Set-up under a ``telemetry.Tracer``, as a run makes it: generate the
   graph, ``open_session``, and the warm-up of a ``--seconds`` window
   (``run._warm_up``). Prints the ``session.open`` span, the builds the
   tracer attributed (its spans' plus those outside every span) beside the
   programs the harness's ``CompileCounter`` counted over the same
   interval, and their seconds.
2. ``--pairs`` pairs of the same query, one untraced and one under a
   tracer, in alternating order: the cost of tracing when it is on.
3. ``--profile 1``: query 0 under a tracer and the profiler, reduced by
   ``devtrace.reduce`` and by ``spantrace``: device seconds per span and
   per layer, and the query's time outside every layer span.

``--scale`` replaces the configuration's scale (a small graph, for a trace
to keep); ``--keep-trace`` writes the profiler's trace there, gzipped. The
last line of output is one JSON object with every reading.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import devtrace, run, spantrace, spec  # noqa: E402


def _log(msg: str) -> None:
    print(f"[probe] {msg}", file=sys.stderr, flush=True)


def setup(cell, seed: int, seconds: float):
    """Set-up under a tracer: (the cell's run, the readings)."""
    from repro.runtime import telemetry

    counter = run.compile_counter()
    tracer = telemetry.Tracer()
    with telemetry.tracing(tracer):
        p0, c0 = counter.programs, counter.seconds
        cr = run.CellRun(cell, seed)
        run._warm_up(cr, seconds)
        programs, compile_s = counter.programs - p0, counter.seconds - c0
    opened = [s.duration for s in tracer.spans if s.name == "session.open"]
    by_span = {}
    for s in tracer.spans:
        if s.builds or s.build_s:
            b = by_span.setdefault(s.name, [0, 0.0])
            b[0] += s.builds
            b[1] += s.build_s
    out = {"open_s": opened[0] if opened else None,
           "setup_builds": tracer.total_builds(),
           "setup_programs_counted": programs,
           "setup_build_s": tracer.total_build_s(),
           "setup_compile_s_counted": compile_s,
           "builds_outside": tracer.builds_outside,
           "builds_by_span": by_span,
           "cache_dir": cr.cache_dir}
    _log(f"set-up: {json.dumps(out)}")
    return cr, out


def pairs(cr, n: int):
    """Query i untraced and under a tracer, alternating which goes first."""
    from repro.runtime import telemetry

    off, on = [], []
    for i in range(n):
        for traced in ((False, True) if i % 2 == 0 else (True, False)):
            if traced:
                with telemetry.tracing(telemetry.Tracer()):
                    r = cr.query(i)
                on.append(r.seconds)
            else:
                r = cr.query(i)
                off.append(r.seconds)
            if r.error:
                raise RuntimeError(f"query {i} failed: {r.error}")
        _log(f"pair {i}: untraced {off[-1]:.6f}s traced {on[-1]:.6f}s")
    return {"untraced_s": off, "traced_s": on,
            "traced_over_untraced": sum(on) / sum(off) if off else None}


def profile(cr, keep: str = ""):
    """Query 0 under a tracer and the profiler."""
    from repro.runtime import telemetry

    log_dir = tempfile.mkdtemp(prefix="probe_trace_")
    try:
        tracer = telemetry.Tracer()
        with telemetry.tracing(tracer):
            rec, path = devtrace.record(lambda: cr.query(0), log_dir)
        if keep:
            os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
            with open(path, "rb") as f, gzip.open(keep, "wb") as g:
                shutil.copyfileobj(f, g)
        pd = devtrace.load(path)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    red = devtrace.reduce(pd, run.KERNELS, spantrace.SPAN_NAMES)
    per_span = spantrace.span_device_s(pd)
    layers = spantrace.layer_device_s(per_span)
    return {"query_s": rec.seconds, "error": rec.error,
            "window_s": red["window_s"], "busy_s": red["busy_s"],
            "span_device_s": per_span,
            "layer_device_s": layers,
            "layers_over_busy": sum(layers.values()) / red["busy_s"],
            "unspanned_s": spantrace.unspanned_s(tracer.spans),
            "idle_gaps": red["idle_gaps"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--pairs", type=int, default=0)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--keep-trace", default="")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    try:
        devices = run.require_chip(cell.chips)
    except run.NoChip as e:
        print(f"probe: {e}", file=sys.stderr)
        return 1
    if args.scale is not None:
        cell.config["scale"] = args.scale
    t0 = time.perf_counter()
    cr, out = setup(cell, args.seed, args.seconds)
    out["setup_wall_s"] = time.perf_counter() - t0
    if args.pairs:
        out["pairs"] = pairs(cr, args.pairs)
    if args.profile:
        out["profile"] = profile(cr, args.keep_trace)
    cr.close()
    out.update(cell=cell.name, seed=args.seed, scale=cell.config["scale"],
               device={"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)})
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

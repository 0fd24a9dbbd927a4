#!/usr/bin/env python3
"""Readings that set the correctness limits, at a cell's own size.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --queries 2 \
      [--controls float32,half_steps]

For each seed: generate the cell's graph, open it, run the mix's first
``--queries`` queries through the timed path, and print the numbers
compared by a run (``run.check_answers``) for the program, and for each
control in the program's place: the reference with its SSSP bounds'
Bellman-Ford accumulated in float32, or stopped at half the supersteps it
needs (``reference.graph.control_sssp``). The program's readings are the
lower ones, the controls' the upper ones. Runs of the benchmark never call
this; it needs the chip like they do.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--queries", type=int, default=1)
    ap.add_argument("--controls", default="",
                    help="comma-separated: float32, half_steps")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        run.require_chip(cell.chips)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    controls = [c for c in args.controls.split(",") if c]
    worst = {"program": {}, **{c: None for c in controls}}
    for seed in (int(s) for s in args.seeds.split(",")):
        cr = run.CellRun(cell, seed)
        recs = [cr.query(i) for i in range(args.queries)]
        cr.close()
        row = {"seed": seed, "query_s": [round(r.seconds, 3) for r in recs],
               "program": run.check_answers(cell, cr.graph, seed, recs)}
        for k, v in row["program"].items():
            worst["program"][k] = max(worst["program"].get(k, 0), v)
        for c in controls:
            row[c] = run.check_answers(cell, cr.graph, seed, recs, control=c)
            bad = {k: v for k, v in row[c].items() if k != "checked_queries"}
            worst[c] = (bad if worst[c] is None else
                        {k: min(worst[c].get(k, v), v) for k, v in bad.items()})
        print(json.dumps(row), flush=True)
    # lower reading: the largest the program gave; upper: the smallest each
    # control gave
    print(json.dumps({"lower_readings": worst["program"],
                      "upper_readings": {c: worst[c] for c in controls}}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

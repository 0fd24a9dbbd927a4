"""The one traffic generator: a mix's file of parameters and the run's
``--seed`` give the queries, in order.

A mix names a ``panel`` of estimator classes of ``repro.core`` with their
arguments. Query ``i`` gives panel entry ``j`` the seed
``seed_of(run_seed, i, j)``; with ``"query": "IntervalEstimator"`` the
panel runs as one certified bracket, with ``"query": null`` the single
entry is the query. One client sends them back to back (a closed loop).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


def _seed(*words: int) -> int:
    ss = np.random.SeedSequence([int(w) % 2**64 for w in words])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def seed_of(run_seed: int, i: int, j: int) -> int:
    """A seed below 2^31 for panel entry ``j`` of query ``i``."""
    return _seed(run_seed, 0, i, j)


def graph_seed(run_seed: int) -> int:
    """The seed the run's graph is generated from."""
    return _seed(run_seed, 1)


@dataclass
class Query:
    index: int
    seeds: List[int]      # one per panel entry

    def parts(self, traffic: dict, core) -> List:
        """The ``repro.core`` estimator object of each panel entry."""
        return [getattr(core, e["class"])(seed=s, **e.get("args", {}))
                for e, s in zip(traffic["panel"], self.seeds)]

    def estimator(self, traffic: dict, core):
        """The ``repro.core`` estimator object that runs this query."""
        parts = self.parts(traffic, core)
        if traffic.get("query") is None:
            if len(parts) != 1:
                raise ValueError("a mix without a composite query has one "
                                 "panel entry")
            return parts[0]
        return getattr(core, traffic["query"])(estimators=tuple(parts))


def query(traffic: dict, run_seed: int, i: int) -> Query:
    return Query(i, [seed_of(run_seed, i, j)
                     for j in range(len(traffic["panel"]))])

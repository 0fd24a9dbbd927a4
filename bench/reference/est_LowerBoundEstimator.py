"""Reference of the farthest-point walk (``LowerBoundEstimator``): the
lower bound is the largest eccentricity the walk meets, and the free upper
bound is twice the first one. Both are exact integers."""
from __future__ import annotations

from types import SimpleNamespace

from bench.reference.graph import control_sssp, gap, farthest_walk, source_of


def expected(g, args: dict, seed: int, capture=None, control=None):
    sssp = (control_sssp(g, control) if control
            else (lambda s: g.sssp([s])[0]))
    lower, upper, hops = farthest_walk(
        source_of(seed, g.n), int(args.get("rounds", 4)), sssp)
    return SimpleNamespace(lower=lower, upper=upper, n_stages=hops)


def compare(got, want) -> dict:
    return {
        "walk_lower_gap": gap(got.lower, want.lower),
        "walk_upper_gap": gap(got.upper, want.upper),
        "walk_hops_gap": gap(got.n_stages, want.n_stages),
    }

"""Reference of the SSSP 2-approximation (``DeltaSteppingEstimator``): the
eccentricity of the query's random source bounds the diameter below, and
twice it above. Whatever ``delta`` is, the distances are exact."""
from __future__ import annotations

from types import SimpleNamespace

from bench.reference.graph import UNREACHED, control_sssp, gap, source_of


def expected(g, args: dict, seed: int, capture=None, control=None):
    s = source_of(seed, g.n)
    d = control_sssp(g, control)(s) if control else g.sssp([s])[0]
    ecc = int(d.max())
    connected = bool((d != UNREACHED).all())
    return SimpleNamespace(lower=ecc, upper=2 * ecc if connected else None)


def compare(got, want) -> dict:
    return {
        "sssp_lower_gap": gap(got.lower, want.lower),
        "sssp_upper_gap": gap(got.upper, want.upper),
    }

"""The comparison that decides ``correct``: each checked query's answer
against the plain reference, as named numbers that are 0 when they agree.
Every limit is 0: the answers are integers, exact by the configuration's
guarantees."""
from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, List, Optional

from bench import spec
from bench.reference.graph import gap

# number -> limit; a run is correct only where every number is at most it
LIMIT = 0


def _panel(traffic: dict):
    return [(e["class"], e.get("args", {}),
             spec.estimator_reference(e["class"])) for e in traffic["panel"]]


def _parts(result, traffic: dict) -> List:
    """The panel's estimates of one query, in panel order."""
    if traffic.get("query") is None:
        return [result]
    return list(result.estimates.values())


def expected(g, traffic: dict, seeds: List[int], captures: List,
             control: Optional[str] = None):
    """The reference's answer to one query, shaped like the program's
    (or, with ``control``, the control's)."""
    wants = []
    caps = iter(captures)
    for (cls, args, mod), seed in zip(_panel(traffic), seeds):
        cap = next(caps) if getattr(mod, "CAPTURE", False) else None
        wants.append(mod.expected(g, args, seed, capture=cap,
                                  control=control))
    if traffic.get("query") is None:
        return wants[0]
    lowers = [w.lower for w in wants if getattr(w, "lower", None) is not None]
    uppers = [w.upper for w in wants if getattr(w, "upper", None) is not None]
    return SimpleNamespace(
        lower=max(lowers, default=0), upper=min(uppers, default=None),
        estimates={f"{i}": w for i, w in enumerate(wants)})


def compare(g, traffic: dict, seeds: List[int], captures: List,
            result) -> Dict[str, int]:
    """Numbers of one query: each is 0 where the answer agrees."""
    want = expected(g, traffic, seeds, captures)
    out: Dict[str, int] = {}
    for (cls, _, mod), got, w in zip(_panel(traffic), _parts(result, traffic),
                                     _parts(want, traffic)):
        for k, v in mod.compare(got, w).items():
            out[k] = max(out.get(k, 0), int(v))
    if traffic.get("query") is not None:
        out["lower_gap"] = gap(result.lower, want.lower)
        out["upper_gap"] = gap(result.upper, want.upper)
    return out


def merge(rows: List[Dict[str, int]]) -> Dict[str, int]:
    """The widest reading of each number over the checked queries."""
    out: Dict[str, int] = {}
    for row in rows:
        for k, v in row.items():
            out[k] = max(out.get(k, 0), v)
    return out

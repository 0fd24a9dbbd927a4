"""Roofline term extraction from a compiled dry-run artifact.

Three terms per (arch x shape x mesh), in seconds, priced with the v5e
entry of ``PEAKS`` (the dry-run models a v5e pod):

  compute    = HLO_FLOPs / (chips x peak bf16 FLOP/s)
  memory     = HLO_bytes / (chips x peak HBM B/s)
  collective = collective_wire_bytes / (chips x ICI B/s per link)

FLOPs/bytes come from compiled.cost_analysis(). Collective bytes are NOT in
cost_analysis — we parse the optimized HLO text and sum operand sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
ops. Sizes are whole-array; per-chip wire bytes depend on the algorithm
(ring all-gather moves (n-1)/n of the output through each link), so we apply
the standard per-collective ring factors.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""
    flops: float     # bf16 FLOP/s
    hbm_bw: float    # HBM bytes/s
    ici_bw: float    # chip-to-chip bytes/s per link


# Keyed by ``jax.Device.device_kind``. Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect (four links of 50 GB/s).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
V5E = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> ChipPeaks:
    """The table entry for ``device_kind``; a kind not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# instruction form: %name = <result shape(s)> op(...). Result tuples may
# embed /*index=NNN*/ comments, so the shape region must be matched with `.`
# (anchored at the instruction's "=") rather than [^=].
_COLLECTIVE_RE = re.compile(
    r"^\s*%?[\w.\-]+\s*=\s*(?P<outshape>.*?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"(?P<dt>(?:pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128))\[(?P<dims>[0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    bytes_by_op: Dict[str, int] = field(default_factory=dict)

    @property
    def wire_bytes(self) -> float:
        """Ring-algorithm wire bytes per chip (factors applied at parse)."""
        return float(sum(self.bytes_by_op.values()))


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum RING-algorithm wire bytes per chip for every collective.

    Result-shape conventions in SPMD HLO:
      all-gather      result = post-gather (big)  -> wire ~ (g-1)/g * result
      all-reduce      result = local shard        -> wire ~ 2 (g-1)/g * result
      reduce-scatter  result = post-scatter (small)-> wire ~ (g-1) * result
      all-to-all      result = local size         -> wire ~ (g-1)/g * result
      collective-permute                          -> wire ~ 1 * result
    g = replica group size (parsed from replica_groups=[n,g]<=[...]).
    -start/-done async pairs counted once (at -start).
    """
    st = CollectiveStats()
    for line in hlo_text.splitlines():
        if "-done(" in line or "-done." in line:
            continue  # counted at -start
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        nbytes = _shape_bytes(m.group("outshape"))
        g = 0
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        g = max(g, 2)
        if op == "all-reduce":
            wire = 2.0 * nbytes * (g - 1) / g
        elif op == "reduce-scatter":
            wire = float(nbytes) * (g - 1)
        elif op == "collective-permute":
            wire = float(nbytes)
        else:  # all-gather / all-to-all
            wire = float(nbytes) * (g - 1) / g
        st.counts[op] = st.counts.get(op, 0) + 1
        st.bytes_by_op[op] = st.bytes_by_op.get(op, 0) + wire
    return st


@dataclass
class RooflineReport:
    name: str
    n_chips: int
    hlo_flops: float
    hlo_bytes: float
    collective: CollectiveStats
    model_flops: float = 0.0          # 6*N*D analytic (0 if n/a)
    bytes_per_device: float = 0.0     # from memory_analysis

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.n_chips * V5E.flops)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.n_chips * V5E.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.collective.wire_bytes / V5E.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of peak the dominant-resource time implies for the
        useful (model) FLOPs: model_time_at_peak / bound_time."""
        bound = max(self.t_compute, self.t_memory, self.t_collective)
        ideal = (self.model_flops or self.hlo_flops) / (self.n_chips * V5E.flops)
        return ideal / bound if bound else 0.0

    def row(self) -> Dict[str, object]:
        return {
            "name": self.name,
            "chips": self.n_chips,
            "hlo_gflops": round(self.hlo_flops / 1e9, 2),
            "hlo_gbytes": round(self.hlo_bytes / 1e9, 3),
            "coll_gbytes": round(self.collective.wire_bytes / 1e9, 4),
            "t_compute_ms": round(self.t_compute * 1e3, 4),
            "t_memory_ms": round(self.t_memory * 1e3, 4),
            "t_collective_ms": round(self.t_collective * 1e3, 4),
            "bottleneck": self.bottleneck,
            "useful_ratio": round(self.useful_flops_ratio, 3),
            "roofline_frac": round(self.roofline_fraction, 3),
            "bytes_per_dev_mb": round(self.bytes_per_device / 1e6, 1),
            "collectives": dict(self.collective.counts),
        }


def analyze(name: str, lowered, compiled, n_chips: int,
            model_flops: float = 0.0) -> RooflineReport:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    # XLA reports the PER-PARTITION program's flops/bytes under SPMD
    # (verified against an analytic matmul); scale to global so the
    # assignment's  HLO_FLOPs / (chips x peak)  formula applies directly.
    flops = float(cost.get("flops", 0.0)) * n_chips
    nbytes = float(cost.get("bytes accessed", 0.0)) * n_chips
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = parse_collectives(hlo)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem_bytes = float(getattr(ma, "argument_size_in_bytes", 0)
                          + getattr(ma, "output_size_in_bytes", 0)
                          + getattr(ma, "temp_size_in_bytes", 0))
    except Exception:
        mem_bytes = 0.0
    return RooflineReport(
        name=name, n_chips=n_chips, hlo_flops=flops, hlo_bytes=nbytes,
        collective=coll, model_flops=model_flops, bytes_per_device=mem_bytes,
    )

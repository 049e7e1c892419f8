"""Roofline-term extraction from compiled XLA artifacts (TPU v5e model).

Per (arch × shape × mesh) cell:

    compute term    = HLO_FLOPs   / (chips × 197e12 FLOP/s bf16)
    memory term     = HLO_bytes   / (chips × 819e9 B/s HBM)
    collective term = coll_bytes  / (chips × 3 links × 50e9 B/s ICI)

``cost_analysis()`` supplies FLOPs and bytes accessed.  Collective bytes are
*not* in cost_analysis — we parse the optimized HLO text and sum operand
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops (cross-pod DCI collectives are counted separately by
matching the replica-group span when possible).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

# TPU v5e hardware model
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
ICI_LINKS = 3                # usable links per chip (2D torus + wrap)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u4": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """'f32[128,256]' → byte count; tuple shapes handled by caller."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output-shape bytes of every collective op in optimized HLO."""
    out: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    out["total"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        # instruction lines look like:  "%name = f32[..] all-reduce(...)"
        m = re.search(r"=\s+((?:\(|\w).*?)\s+(" + "|".join(_COLLECTIVES)
                      + r")[\.\( ]", s)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        nbytes = _shape_bytes(shape_str)
        out[op] += nbytes
        out["total"] += nbytes
    return out


@dataclasses.dataclass
class RooflineReport:
    name: str
    mesh: str
    chips: int
    hlo_gflops: float            # total across chips
    hlo_gbytes: float
    coll_gbytes: float
    per_collective: dict
    model_gflops: Optional[float]
    peak_memory_bytes: Optional[int]

    @property
    def t_compute(self) -> float:
        return self.hlo_gflops * 1e9 / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_gbytes * 1e9 / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return (self.coll_gbytes * 1e9
                / (self.chips * ICI_LINKS * ICI_BW))

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Roofline-model step time: max of the three terms (perfect
        overlap assumption — the optimistic bound)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """useful-FLOPs throughput vs peak, at roofline step time."""
        if not self.model_gflops or self.step_time <= 0:
            return 0.0
        achieved = self.model_gflops * 1e9 / self.step_time
        return achieved / (self.chips * PEAK_FLOPS)

    @property
    def flops_efficiency(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — compiled-compute usefulness."""
        if not self.model_gflops or not self.hlo_gflops:
            return 0.0
        return self.model_gflops / self.hlo_gflops

    def to_dict(self) -> dict:
        return {
            "name": self.name, "mesh": self.mesh, "chips": self.chips,
            "hlo_gflops": self.hlo_gflops, "hlo_gbytes": self.hlo_gbytes,
            "coll_gbytes": self.coll_gbytes,
            "per_collective": self.per_collective,
            "model_gflops": self.model_gflops,
            "peak_memory_bytes": self.peak_memory_bytes,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time,
            "roofline_fraction": self.roofline_fraction,
            "flops_efficiency": self.flops_efficiency,
        }


def analyze(name: str, mesh_desc: str, chips: int, compiled,
            model_flops: Optional[float] = None) -> RooflineReport:
    """Build a report from a jax compiled executable."""
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    peak_mem = None
    try:
        ma = compiled.memory_analysis()
        peak_mem = int(getattr(ma, "temp_size_in_bytes", 0)
                       + getattr(ma, "argument_size_in_bytes", 0)
                       + getattr(ma, "output_size_in_bytes", 0)
                       - getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        pass

    return RooflineReport(
        name=name, mesh=mesh_desc, chips=chips,
        hlo_gflops=flops / 1e9, hlo_gbytes=nbytes / 1e9,
        coll_gbytes=coll["total"] / 1e9,
        per_collective={k: v for k, v in coll.items() if k != "total"},
        model_gflops=(model_flops / 1e9 if model_flops else None),
        peak_memory_bytes=peak_mem)

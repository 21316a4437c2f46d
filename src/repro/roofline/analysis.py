"""Roofline analysis from compiled XLA artifacts (no hardware required).

Extracts, per compiled step:
  - HLO FLOPs and bytes from ``compiled.cost_analysis()``
  - collective traffic by parsing the post-SPMD HLO text for
    all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute ops

and derives the three roofline terms for TPU v5e:
    compute    = HLO_FLOPs / (chips x 197e12)
    memory     = HLO_bytes / (chips x 819e9)
    collective = collective_bytes / (chips x 50e9)

Byte conventions (documented; consistent across all rows so ratios are meaningful):
  all-reduce         2 x result bytes   (ring reduce-scatter + all-gather)
  all-gather         1 x result bytes
  reduce-scatter     1 x operand bytes  (== result x shards)
  all-to-all         1 x result bytes
  collective-permute 1 x result bytes

``cost_analysis()`` on an SPMD-partitioned module reports the PER-DEVICE program, so
``flops``/``bytes`` are per chip; the fleet totals multiply by ``chips``. The roofline
terms below therefore divide per-device quantities by per-chip peaks directly.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

# TPU v5e per-chip constants (assignment-specified)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(pred|[sufbc]\w*?\d+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)

_MULTIPLIER = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(type_str: str) -> int:
    """Sum byte sizes of all array shapes in an HLO type string (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, float] = field(default_factory=dict)
    count_by_kind: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum weighted operand/result bytes of every collective in the HLO module.

    Skips -done ops (the -start carries the shape) to avoid double counting async
    pairs; plain (synchronous) ops are counted once.
    """
    stats = CollectiveStats()
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if f"{m.group(2)}-done(" in line:
            continue
        type_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(type_str) * _MULTIPLIER[kind]
        if kind == "reduce-scatter":
            # convention: operand bytes; result bytes x shard count ~= operand.
            # parse the operand shapes from inside the parens instead
            inner = line[m.end():]
            ob = _shape_bytes(inner.split(")")[0])
            b = float(ob) if ob else b
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0.0) + b
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


@dataclass
class RooflineReport:
    name: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collective_detail: Dict[str, float]
    collective_counts: Dict[str, int]
    model_flops: Optional[float] = None  # 6*N*D fleet-wide
    peak_memory_per_device: Optional[float] = None
    extra: Dict = field(default_factory=dict)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / PEAK_FLOPS_BF16

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> Optional[float]:
        if self.model_flops is None:
            return None
        fleet = self.flops_per_device * self.chips
        return self.model_flops / fleet if fleet else None

    def to_dict(self) -> Dict:
        return {
            "name": self.name,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "collective_detail": self.collective_detail,
            "collective_counts": self.collective_counts,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "peak_memory_per_device": self.peak_memory_per_device,
            **self.extra,
        }


def analyze_compiled(name: str, compiled, chips: int, model_flops: Optional[float] = None,
                     extra: Optional[Dict] = None) -> RooflineReport:
    from repro.roofline.hlo_analyzer import analyze as hlo_analyze

    cost = compiled.cost_analysis()
    text = compiled.as_text()
    hlo = hlo_analyze(text)  # trip-count-aware (XLA counts while bodies once)
    flops = hlo.flops
    byts = hlo.bytes
    stats = CollectiveStats(
        bytes_by_kind=dict(hlo.coll_by_kind),
        count_by_kind={k: int(v) for k, v in hlo.coll_counts.items()},
    )
    extra = dict(extra or {})
    extra["xla_cost_analysis"] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
    mem = compiled.memory_analysis()
    peak = None
    if mem is not None:
        peak = float(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "generated_code_size_in_bytes", 0)
        )
        # avoid double counting aliased (donated) buffers
        peak -= float(getattr(mem, "alias_size_in_bytes", 0))
    return RooflineReport(
        name=name,
        chips=chips,
        flops_per_device=flops,
        bytes_per_device=byts,
        collective_bytes_per_device=stats.total_bytes,
        collective_detail=stats.bytes_by_kind,
        collective_counts=stats.count_by_kind,
        model_flops=model_flops,
        peak_memory_per_device=peak,
        extra=extra,
    )


def model_flops_6nd(n_params_active: int, n_tokens: int, train: bool = True) -> float:
    """6·N·D for a train step (fwd+bwd); 2·N·D for inference."""
    return (6.0 if train else 2.0) * n_params_active * n_tokens

"""Cost extraction from compiled XLA artifacts (dry-run roofline inputs).

Two jobs:

1. ``parse_collectives`` — sum per-device *wire bytes* of every collective in
   a post-optimization HLO module, using standard ring-algorithm factors:
       all-reduce       2(g-1)/g * N      (N = per-device operand bytes)
       all-gather       (g-1)/g * N_out
       reduce-scatter   (g-1) * N_out
       all-to-all       (g-1)/g * N
       collective-permute  N
   (g = replica-group size; groups of size 1 contribute nothing.)

2. ``CostSummary`` accounting with the scan correction: XLA cost_analysis
   counts a ``while`` body once, so the dry-run lowers every scan-segment
   body separately and reports  total = full + Σ_i body_i × (n_i − 1)
   (exact for scanned stacks — verified in DESIGN.md §6).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> Dict:
    """Returns {"wire_bytes", "raw_bytes", "count", "by_kind": {...}}."""
    wire = 0.0
    raw = 0
    by_kind: Dict[str, float] = {}
    count = 0
    for m in _COLL_RE.finditer(hlo_text):
        tuple_body, dtype, dims, kind = m.groups()
        if tuple_body is not None:
            # tuple-shaped output (e.g. fused start ops): sum elements
            out_bytes = sum(_shape_bytes(d, s)
                            for d, s in _SHAPE_RE.findall(tuple_body))
        else:
            out_bytes = _shape_bytes(dtype, dims)
        # group size
        line_end = hlo_text.find("\n", m.end())
        line = hlo_text[m.start(): line_end if line_end > 0 else None]
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        else:
            gb = _GROUPS_BRACE_RE.search(line)
            if gb:
                g = len(gb.group(1).split(","))
        if kind == "collective-permute":
            # permutes carry source_target_pairs, not replica_groups
            count += 1
            raw += out_bytes
            wire += float(out_bytes)
            by_kind[kind] = by_kind.get(kind, 0.0) + float(out_bytes)
            continue
        if g <= 1:
            continue
        count += 1
        raw += out_bytes
        if kind == "all-reduce":
            w = 2.0 * (g - 1) / g * out_bytes
        elif kind == "all-gather":
            w = (g - 1) / g * out_bytes
        elif kind == "reduce-scatter":
            w = float(g - 1) * out_bytes
        elif kind == "all-to-all":
            w = (g - 1) / g * out_bytes
        else:  # collective-permute
            w = float(out_bytes)
        wire += w
        by_kind[kind] = by_kind.get(kind, 0.0) + w
    return {"wire_bytes": wire, "raw_bytes": raw, "count": count,
            "by_kind": by_kind}


@dataclass
class CostSummary:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    coll_wire_bytes: float = 0.0
    coll_count: int = 0
    coll_by_kind: Dict[str, float] = field(default_factory=dict)

    def scaled_add(self, other: "CostSummary", k: float):
        self.flops += k * other.flops
        self.bytes_accessed += k * other.bytes_accessed
        self.coll_wire_bytes += k * other.coll_wire_bytes
        self.coll_count += int(k * other.coll_count)
        for kk, v in other.coll_by_kind.items():
            self.coll_by_kind[kk] = self.coll_by_kind.get(kk, 0.0) + k * v

    def to_dict(self):
        return {"flops": self.flops, "bytes_accessed": self.bytes_accessed,
                "coll_wire_bytes": self.coll_wire_bytes,
                "coll_count": self.coll_count,
                "coll_by_kind": dict(self.coll_by_kind)}


def summarize_compiled(compiled) -> CostSummary:
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    return CostSummary(
        flops=float(ca.get("flops", 0.0) or 0.0),
        bytes_accessed=float(ca.get("bytes accessed", 0.0) or 0.0),
        coll_wire_bytes=colls["wire_bytes"],
        coll_count=colls["count"],
        coll_by_kind=colls["by_kind"],
    )


def memory_summary(compiled) -> Dict:
    ma = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        out[k] = int(getattr(ma, k, 0) or 0)
    out["peak_hbm_bytes"] = (out["argument_size_in_bytes"]
                             + out["output_size_in_bytes"]
                             + out["temp_size_in_bytes"]
                             - out["alias_size_in_bytes"])
    return out


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float  # FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    ici_bw: float  # bytes/s per inter-chip link


# Published per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e:
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of ICI over 4 links (50 GB/s each).  JAX reports a
# v5e as "TPU v5 lite".
_V5E = ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9)
PEAKS: Dict[str, ChipPeaks] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; a kind without published peaks is an
    error, never a default (a CPU caller names the TPU kind it models)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} — pass the target chip's kind") from None


def tau_from_step_cost(cost: CostSummary, n_chips: int, m_blocks: int,
                       n_rows: int, *, device_kind: str) -> float:
    """Per-block per-token decode τ (s) from one pooled decode step's cost.

    The step advances every pool row one token through all ``m_blocks``
    hosted blocks, so the roofline bound of ONE dispatch amortises over
    ``m_blocks x n_rows`` (block, token) pairs — exactly the τ the paper's
    eq. (1) multiplies back up.  With a sharded step the cost analysis is
    per-device after SPMD partitioning, so a TP/EP device group's speedup
    (and its collective bytes) land in τ automatically."""
    terms = roofline_terms(cost, n_chips, device_kind=device_kind)
    return terms["bound_s"] / max(1, int(m_blocks) * int(n_rows))


def roofline_terms(cost: CostSummary, n_chips: int,
                   mem_floor_bytes: float = 0.0, *, device_kind: str) -> Dict:
    """cost_analysis numbers are PER-DEVICE after SPMD partitioning, so the
    per-chip terms divide by the per-chip rates of ``device_kind``
    (:data:`PEAKS`) directly.

    CPU-backend caveat (DESIGN.md §6): XLA:CPU has no native bf16 GEMMs, so it
    upcasts bf16 dots/gathers to f32 — ``bytes_accessed`` (and temp memory)
    overstate a real bf16 TPU program by up to ~2x.  We therefore report
    three memory numbers: the spec-mandated HLO figure, a /2 "tpu_est"
    adjustment for bf16 programs, and an analytic floor (params+caches+
    outputs actually touched, from per-device argument/output sizes).
    """
    peaks = chip_peaks(device_kind)
    compute_s = cost.flops / peaks.flops_bf16
    memory_s = cost.bytes_accessed / peaks.hbm_bw
    memory_s_tpu_est = (max(cost.bytes_accessed / 2.0, mem_floor_bytes)
                        / peaks.hbm_bw)
    memory_s_floor = mem_floor_bytes / peaks.hbm_bw
    collective_s = cost.coll_wire_bytes / peaks.ici_bw
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)), key=lambda t: t[1])[0]
    total = max(compute_s, memory_s, collective_s)
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "memory_s_tpu_est": memory_s_tpu_est,
        "memory_s_floor": memory_s_floor,
        "collective_s": collective_s,
        "dominant": dominant,
        "bound_s": total,
        "compute_fraction_of_bound": (compute_s / total) if total > 0 else 0.0,
    }

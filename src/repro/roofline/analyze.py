"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch x shape x mesh), in seconds:
    compute    = HLO_FLOPs / (chips * peak_FLOPs)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes; collective bytes are
NOT in cost_analysis — we parse the (post-SPMD) HLO text and sum operand
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops.

Hardware constants: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI (assignment brief).
"""
from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  "bf16[16,4096,128]{2,1,0} all-gather(...)"  or tuple-typed ops
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum byte sizes of every tensor literal in an HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo: str) -> Dict[str, float]:
    """Per-kind and total collective bytes (result-shape convention).

    MUST be fed *post-SPMD* HLO (``compiled.as_text()``) — collectives only
    exist after partitioning; the pre-compile StableHLO has none. Counts each
    collective once with its result size: sync ops directly; async
    start/done pairs via the ``-done`` op (the start op returns a tuple
    holding both buffers and would double-count)."""
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo.splitlines():
        s = line.strip()
        # result-type prefix form: "<name> = <type> <op>(...)"
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
                     r"([a-z\-]+(?:-start|-done)?)", s)
        if not m:
            continue
        op = m.group(2)
        if op.endswith("-start"):
            continue
        base = op[:-5] if op.endswith("-done") else op
        for kind in _COLLECTIVES:
            if base == kind:
                out[kind] += _shape_bytes(m.group(1))
                counts[kind] += 1
                break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts  # type: ignore[assignment]
    return out


@dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops: float
    useful_ratio: float

    def as_dict(self) -> Dict[str, float]:
        return {"compute_s": self.compute_s, "memory_s": self.memory_s,
                "collective_s": self.collective_s, "dominant": self.dominant,
                "model_flops": self.model_flops, "hlo_flops": self.hlo_flops,
                "useful_ratio": self.useful_ratio}


def model_flops(params: int, active_params: int, tokens: int,
                kind: str) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); decode: D = batch tokens (1 step).
    Training includes backward (the 6x already counts fwd+bwd); inference
    steps use 2*N*D."""
    n = active_params
    if kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


def roofline_terms(*, flops: float, bytes_accessed: float,
                   collective: Dict[str, float], chips: int,
                   params: int, active_params: int, tokens: int,
                   kind: str) -> Roofline:
    """All inputs are whole-program (all-chip) quantities from the dry-run.

    cost_analysis flops/bytes are per-partition after SPMD; we treat them as
    per-chip. Collective bytes from HLO are per-chip program bytes; ring
    all-reduce costs ~2x on the wire, others ~1x."""
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_accessed / HBM_BW
    wire = (2.0 * collective.get("all-reduce", 0.0)
            + collective.get("all-gather", 0.0)
            + collective.get("reduce-scatter", 0.0)
            + collective.get("all-to-all", 0.0)
            + collective.get("collective-permute", 0.0))
    collective_s = wire / LINK_BW
    mf = model_flops(params, active_params, tokens, kind)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    hlo_total = flops * chips
    return Roofline(compute_s, memory_s, collective_s, dominant, mf,
                    hlo_total, mf / hlo_total if hlo_total > 0 else 0.0)


def load_dryrun(results_dir: str = "results/dryrun") -> List[Dict]:
    recs = []
    if not os.path.isdir(results_dir):
        return recs
    for name in sorted(os.listdir(results_dir)):
        if name.endswith(".json"):
            with open(os.path.join(results_dir, name)) as f:
                recs.append(json.load(f))
    return recs


def analyze_record(rec: Dict, tokens: int, kind: str) -> Optional[Roofline]:
    if rec.get("status") != "ok":
        return None
    chips = 1
    for s in rec["mesh"]:
        chips *= s
    return roofline_terms(
        flops=rec["flops"], bytes_accessed=rec["bytes_accessed"],
        collective=rec["collective_bytes"], chips=chips,
        params=rec["params"], active_params=rec["active_params"],
        tokens=tokens, kind=kind)

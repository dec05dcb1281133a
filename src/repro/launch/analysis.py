"""Compiled-artifact analysis: collective-byte parsing + roofline terms.

``cost_analysis()`` gives HLO FLOPs and bytes accessed; collective traffic
is NOT in cost_analysis, so we parse the compiled (SPMD-partitioned,
per-device) HLO text and sum the output-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
with wire factors (all-reduce counts 2x: ring reduce+broadcast). Collectives
whose replica groups span both pods are priced at the inter-pod (DCN)
bandwidth.

Roofline (per chip):
    compute    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = ici_bytes/link_bw + dcn_bytes/dci_bw    (per-chip HLO bytes)

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (repro.core.hardware.TPU_V5E).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

from repro.core.hardware import TPU_V5E, HardwareProfile

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_RE = re.compile(
    r"=\s*(?P<shape>[^=]*?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(", re.I)

SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9,\s]*)\}")

WIRE_FACTOR = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
               "all-to-all": 1.0, "collective-permute": 1.0}


def shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(text):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: Dict[str, float]
    count_by_op: Dict[str, int]
    ici_bytes: float           # per-chip wire bytes within a pod
    dcn_bytes: float           # per-chip wire bytes crossing pods

    @property
    def total_bytes(self) -> float:
        return self.ici_bytes + self.dcn_bytes


def parse_collectives(hlo_text: str, pod_size: int = 0) -> CollectiveStats:
    bytes_by_op: Dict[str, float] = {}
    count_by_op: Dict[str, int] = {}
    ici = dcn = 0.0
    seen_done = set()
    for m in COLLECTIVE_RE.finditer(hlo_text):
        op = m.group("op").lower()
        line_start = hlo_text.rfind("\n", 0, m.start()) + 1
        line_end = hlo_text.find("\n", m.end())
        line = hlo_text[line_start:line_end if line_end > 0 else None]
        if "-done(" in line or " done" in line.split("(")[0]:
            continue                      # avoid double-count of async pairs
        b = shape_bytes(m.group("shape")) * WIRE_FACTOR[op]
        if b == 0:
            continue
        bytes_by_op[op] = bytes_by_op.get(op, 0.0) + b
        count_by_op[op] = count_by_op.get(op, 0) + 1
        crosses_pod = False
        if pod_size:
            g = GROUPS_RE.search(line)
            if g and g.group(1).strip():
                ids = [int(x) for x in g.group(1).split(",") if x.strip()]
                pods = {i // pod_size for i in ids}
                crosses_pod = len(pods) > 1
        if crosses_pod:
            dcn += b
        else:
            ici += b
    return CollectiveStats(bytes_by_op=bytes_by_op, count_by_op=count_by_op,
                           ici_bytes=ici, dcn_bytes=dcn)


def extract_cost(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def extract_memory(compiled) -> Dict[str, float]:
    out: Dict[str, float] = {}
    try:
        ma = compiled.memory_analysis()
        for f in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "temp_size_in_bytes"):
            v = getattr(ma, f, None)
            if v is not None:
                out[f] = float(v)
        out["per_device_peak_bytes"] = (
            out.get("argument_size_in_bytes", 0.0)
            + out.get("output_size_in_bytes", 0.0)
            + out.get("temp_size_in_bytes", 0.0)
            - out.get("alias_size_in_bytes", 0.0))
    except Exception as e:                                 # pragma: no cover
        out["error"] = str(e)
    return out


@dataclasses.dataclass
class Roofline:
    t_compute: float
    t_memory: float
    t_collective: float
    flops: float
    hbm_bytes: float
    coll: CollectiveStats
    chips: int
    model_flops: float = 0.0            # 6*N_active*D analytic

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_frac(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    def row(self) -> Dict[str, object]:
        return {
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "dominant": self.dominant,
            "hlo_flops": self.flops, "hlo_bytes": self.hbm_bytes,
            "coll_ici_bytes": self.coll.ici_bytes,
            "coll_dcn_bytes": self.coll.dcn_bytes,
            "coll_counts": dict(self.coll.count_by_op),
            "model_flops": self.model_flops,
            "useful_flops_frac": self.useful_flops_frac,
        }


def roofline(compiled, chips: int, pod_size: int = 0,
             profile: HardwareProfile = TPU_V5E,
             model_flops: float = 0.0,
             hlo_text: Optional[str] = None) -> Roofline:
    """Three-term roofline from a compiled artifact.

    The SPMD module is the per-device program, so all terms are per-chip.
    XLA's cost_analysis() counts while (scan) bodies once, so FLOPs/bytes/
    collectives come from repro.launch.hlo_cost — a trip-count-aware HLO
    analysis (validated against cost_analysis on scan-free modules). The
    raw cost_analysis numbers are kept in the record for reference.
    """
    from repro.launch import hlo_cost
    text = hlo_text if hlo_text is not None else compiled.as_text()
    hc = hlo_cost.analyze(text, pod_size=pod_size)
    coll = CollectiveStats(bytes_by_op=dict(hc.collective_wire),
                           count_by_op=dict(hc.collective_counts),
                           ici_bytes=hc.ici_bytes, dcn_bytes=hc.dcn_bytes)
    t_c = hc.flops / profile.peak_flops
    t_m = hc.bytes / profile.hbm_bw
    t_x = coll.ici_bytes / profile.ici_bw
    if coll.dcn_bytes:
        t_x += coll.dcn_bytes / max(profile.dci_bw, 1.0)
    return Roofline(t_compute=t_c, t_memory=t_m, t_collective=t_x,
                    flops=hc.flops, hbm_bytes=hc.bytes, coll=coll,
                    chips=chips, model_flops=model_flops)

"""Roofline extraction from compiled XLA artifacts.

``compiled.cost_analysis()`` reports **per-device** FLOPs / bytes accessed
(verified empirically: a 4-way-sharded 1024³ matmul reports 2·1024³/4 FLOPs).
Collective traffic is NOT in cost_analysis, so we parse the optimized HLO of
``compiled.as_text()`` and sum wire bytes of every collective op using the
standard ring-algorithm costs:

  all-gather        out_bytes · (g-1)/g         (out = gathered result)
  all-reduce        2 · bytes · (g-1)/g         (reduce-scatter + all-gather)
  reduce-scatter    out_bytes · (g-1)            (out = scattered shard)
  all-to-all        bytes · (g-1)/g
  collective-permute bytes                       (single hop)

where g is the replica-group size.  These are per-device wire bytes; the
roofline collective term is wire_bytes_per_device / ici_bw, which equals the
assignment's ``collective_bytes / (chips × link_bw)`` with global bytes.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^=]*?\)|[a-z0-9]+\[[0-9,]*\]\S*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)"
    r"(-start)?\(",
)
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUP_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUP_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return default


def collective_wire_bytes(hlo_text: str, n_devices: int) -> Dict[str, float]:
    """Per-device wire bytes by collective kind, from optimized HLO text."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        type_str, kind, _ = m.groups()
        size = _shape_bytes(type_str)
        g = _group_size(line, n_devices)
        if g <= 1:
            continue
        if kind == "all-gather":
            wire = size * (g - 1) / g
        elif kind in ("all-reduce", "collective-broadcast"):
            wire = 2 * size * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = size * (g - 1)
        elif kind in ("all-to-all", "ragged-all-to-all"):
            wire = size * (g - 1) / g
        else:  # collective-permute
            wire = size
        out[kind] = out.get(kind, 0.0) + wire
    out["total"] = sum(out.values())
    return out


@dataclasses.dataclass
class Artifact:
    """Everything JMeasure needs, extracted once per compile."""
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    collectives: Dict[str, float]
    arg_bytes: int
    temp_bytes: int
    output_bytes: int
    n_devices: int
    hlo_ops: Optional[Dict[str, int]] = None
    # analytic fusion-aware HBM traffic (roofline/traffic.py); the raw
    # 'bytes accessed' above overstates TPU HBM traffic (no fusion modeling)
    hbm_est_per_device: Optional[float] = None

    @property
    def global_flops(self) -> float:
        return self.flops_per_device * self.n_devices

    @property
    def effective_bytes_per_device(self) -> float:
        return (self.hbm_est_per_device if self.hbm_est_per_device is not None
                else self.bytes_per_device)

    @property
    def peak_memory_per_device(self) -> int:
        return self.arg_bytes + self.temp_bytes + self.output_bytes


def summarize(compiled, n_devices: int, with_ops: bool = False) -> Artifact:
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    coll = collective_wire_bytes(txt, n_devices)
    ops = None
    if with_ops:
        ops = {}
        for m in re.finditer(r"=\s*\S+\s+([a-z][a-z0-9-]*)\(", txt):
            ops[m.group(1)] = ops.get(m.group(1), 0) + 1
    return Artifact(
        flops_per_device=float(ca.get("flops", 0.0)),
        bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        wire_bytes_per_device=coll.get("total", 0.0),
        collectives={k: v for k, v in coll.items() if k != "total"},
        arg_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
        temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
        output_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
        n_devices=n_devices,
        hlo_ops=ops,
    )


def roofline_report(art: Artifact, hw) -> dict:
    """Three-term roofline + dominant bottleneck for one artifact."""
    terms = hw.roofline_terms(art.global_flops,
                              art.bytes_per_device * art.n_devices,
                              art.wire_bytes_per_device * art.n_devices)
    terms.update(
        flops_per_device=art.flops_per_device,
        bytes_per_device=art.bytes_per_device,
        wire_bytes_per_device=art.wire_bytes_per_device,
        peak_mem_per_device=art.peak_memory_per_device,
    )
    return terms

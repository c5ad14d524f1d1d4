"""Roofline extraction from compiled XLA artifacts.

``compiled.cost_analysis()`` reports **per-device** bytes accessed (a
4-way-sharded 1024³ matmul reports a quarter of the work), but it visits a
``while`` body once, so a scanned layer stack counts as one layer, and
collective traffic is not in it at all.  So the optimized HLO of
``compiled.as_text()`` is parsed once into computations (``HloCounts``):

* each computation runs as often as the product of the trip counts of the
  ``while`` loops around it, summed over its call sites.  A trip count is
  the loop's ``known_trip_count`` where the compiler states it (XLA:CPU),
  else it is read from the loop's counter (the TPU compiler): a constant
  start in the loop's operand, an ``LT`` against a constant in its
  condition, a constant step in its body.  A loop whose count cannot be
  read counts once, as cost analysis does, and is counted in
  ``unknown_trip_loops``;
* every collective's wire bytes count once a run, with the standard ring
  costs (g = replica-group size; per-device wire bytes):

    all-gather        out_bytes · (g-1)/g         (out = gathered result)
    all-reduce        2 · bytes · (g-1)/g         (reduce-scatter + all-gather)
    reduce-scatter    out_bytes · (g-1)            (out = scattered shard)
    all-to-all        bytes · (g-1)/g
    collective-permute bytes                       (single hop)

  The TPU's fused forms count once too: of an async ``-start``/``-done``
  pair the ``-start``'s result; of a collective that an async collective
  fusion clones into its start, step and done computations, one per
  ``channel_id``; and the all-reduce plus dynamic-slice that the TPU folds
  into a ``kind=kCustom`` fusion calling ``%all-reduce-scatter…``, as the
  reduce-scatter it is;
* ``flops_per_device`` is the matmul FLOPs once a run: each ``dot`` and
  ``convolution`` as XLA's cost analysis counts it (2 × output elements ×
  contracted size; a convolution's window positions on padding or dilation
  holes do not count), and the multiply-and-reduce that XLA lowers a
  matrix-vector product to (2 per product).  Elementwise work is left out:
  it does not run against the matmul peak the compute term divides by.

The roofline collective term is wire_bytes_per_device / ici_bw, which equals
the assignment's ``collective_bytes / (chips × link_bw)`` with global bytes.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# one instruction: name, result type, opcode, the rest of the line
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s([a-z][a-z0-9\-]*)\((.*)$")
_PARAM_RE = re.compile(r"([\w.\-]+):\s*([a-z][a-z0-9]*\[[0-9,]*\])")
_CALLEE_RE = re.compile(
    r"\b(calls|to_apply|body|condition|branch_computations|"
    r"called_computations|true_computation|false_computation)="
    r"(\{[^}]*\}|%?[\w.\-]+)")
_GROUP_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_INDEX_RE = re.compile(r"\bindex=(\d+)")
_CONST_RE = re.compile(r"^(-?\d+)\)")


def _dims(type_str: str) -> Optional[List[int]]:
    """The dimensions of the first array in a type string."""
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


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


def _wire_bytes(kind: str, size: float, g: int) -> float:
    if kind == "all-gather":
        return size * (g - 1) / g
    if kind in ("all-reduce", "collective-broadcast"):
        return 2 * size * (g - 1) / g
    if kind == "reduce-scatter":
        return size * (g - 1)
    if kind in ("all-to-all", "ragged-all-to-all"):
        return size * (g - 1) / g
    return size                                       # collective-permute


@dataclasses.dataclass
class _Instr:
    name: str
    type: str
    op: str
    rest: str

    def operands(self) -> List[str]:
        return re.findall(r"%([\w.\-]+)", self.rest.split(")", 1)[0])

    def attr(self, key: str) -> Optional[str]:
        m = re.search(r"\b" + key + r"=(\{[^}]*\}|[^,\s]+)", self.rest)
        return m.group(1) if m else None


def _int_list(attr: Optional[str]) -> List[int]:
    return [int(x) for x in re.findall(r"-?\d+", attr or "")]


def _window(attr: str) -> Dict[str, List[List[int]]]:
    """``window={size=4x8 stride=3x1 pad=0_0x7_7 lhs_dilate=4x1}``."""
    out = {}
    for part in attr.strip("{}").split():
        key, _, val = part.partition("=")
        out[key] = [[int(v) for v in d.split("_")] for d in val.split("x")]
    return out


def _valid_positions(out_n, kernel_n, in_n, stride, lo, base_dil, win_dil):
    """(output, kernel) position pairs of one spatial dimension that read an
    input element, neither padding nor a dilation hole."""
    u = (np.arange(out_n)[None, :] * stride - lo
         + np.arange(kernel_n)[:, None] * win_dil)
    ok = (u >= 0) & (u % base_dil == 0) & (u // base_dil < in_n)
    return int(ok.sum())


class HloCounts:
    """One parse of an optimized HLO module's text: the multiplicity of each
    computation, the collectives' wire bytes and the matmul FLOPs, each per
    trip of the loops around it."""

    def __init__(self, hlo_text: str, n_devices: int):
        self.n_devices = n_devices
        self.comps: Dict[str, Dict[str, _Instr]] = {}   # in text order
        self.params: Dict[str, Dict[str, str]] = {}
        self.entry: Optional[str] = None
        self.loop_trips: List[int] = []     # each while's trips, as applied
        self.unknown_trip_loops = 0
        self._parse(hlo_text)
        self.mult = self._multiplicities()

    def _parse(self, text: str) -> None:
        cur = None
        for line in text.splitlines():
            if line[:1].isspace():
                m = _INSTR_RE.match(line) if cur is not None else None
                if m:
                    ins = _Instr(*m.groups())
                    cur[ins.name] = ins
            elif line.rstrip().endswith("{"):
                head = line[5:].lstrip() if line.startswith("ENTRY") else line
                name = head.split()[0].lstrip("%")
                cur = self.comps[name] = {}
                self.params[name] = dict(_PARAM_RE.findall(head))
                if line.startswith("ENTRY"):
                    self.entry = name
            else:
                cur = None
        if self.entry is None and self.comps:
            self.entry = next(iter(self.comps))

    def _type(self, comp: str, name: str) -> Optional[str]:
        ins = self.comps[comp].get(name)
        return ins.type if ins is not None else self.params[comp].get(name)

    # -- loops -------------------------------------------------------------
    def _multiplicities(self) -> Dict[str, int]:
        """How often each reachable computation runs: the sum over its call
        paths of the trips of the loops on the path."""
        edges: Dict[str, List[Tuple[str, int]]] = {}

        def visit(c):                   # post-order over the call DAG
            edges[c] = [e for ins in self.comps[c].values()
                        for e in self._callees(c, ins)]
            for callee, _ in edges[c]:
                if callee not in edges:
                    visit(callee)
            order.append(c)

        order: List[str] = []
        if self.entry is not None:
            visit(self.entry)
        mult = dict.fromkeys(order, 0)
        if self.entry is not None:
            mult[self.entry] = 1
        for c in reversed(order):
            for callee, trips in edges[c]:
                mult[callee] += mult[c] * trips
        return mult

    def _callees(self, comp: str, ins: _Instr) -> List[Tuple[str, int]]:
        if "=%" not in ins.rest and "s={" not in ins.rest:
            return []
        out = []
        for key, val in _CALLEE_RE.findall(ins.rest):
            trips = self._trips(comp, ins) if key == "body" else 1
            out += [(n, trips) for n in re.findall(r"%?([\w.\-]+)", val)
                    if n in self.comps]
        return out

    def _trips(self, comp: str, loop: _Instr) -> int:
        m = _TRIP_RE.search(loop.rest)
        n = int(m.group(1)) if m else self._counted_trips(comp, loop)
        if n is None:
            self.unknown_trip_loops += 1
            n = 1
        self.loop_trips.append(n)
        return n

    def _counted_trips(self, comp: str, loop: _Instr) -> Optional[int]:
        """``for (i = start; i < limit; i += step)``, read from the loop's
        operand tuple, condition and body; None where it is not that form."""
        cname = (loop.attr("condition") or "").lstrip("%")
        bname = (loop.attr("body") or "").lstrip("%")
        if cname not in self.comps or bname not in self.comps:
            return None
        cond, body = self.comps[cname], self.comps[bname]
        root = list(cond.values())[-1]
        if root.op != "compare" or "direction=LT" not in root.rest:
            return None
        counter, limit = (cond.get(o) for o in (root.operands() + ["", ""])[:2])
        i = _tuple_index(counter)
        if i is None:
            return None
        start = _constant(_tuple_operand(self.comps[comp].get(
            next(iter(loop.operands()), "")), i), self.comps[comp])
        step = _step(body, list(body.values())[-1], i)
        stop = _constant(limit, cond)
        if start is None or stop is None or not step or step < 0:
            return None
        return max(0, -(-(stop - start) // step))

    # -- counts ------------------------------------------------------------
    def collective_wire_bytes(self) -> Dict[str, float]:
        """Per-device wire bytes by collective kind, every collective once
        per run of its computation."""
        out: Dict[str, float] = {}
        seen: Dict[str, Tuple[str, float]] = {}
        for comp, instrs in self.comps.items():
            fused_rs = comp.startswith("all-reduce-scatter")
            for ins in instrs.values():
                kind = ins.op[:-6] if ins.op.endswith("-start") else ins.op
                if kind not in _COLLECTIVES:
                    continue
                g = _group_size(ins.rest, self.n_devices)
                if g <= 1:
                    continue
                size = _shape_bytes(ins.type)
                if kind == "all-reduce" and fused_rs:
                    kind, size = "reduce-scatter", size / g
                elif ins.type.startswith("("):
                    # an async start's (operand, result, context) tuple
                    size = max(_shape_bytes(f"{d}[{n}]")
                               for d, n in _SHAPE_RE.findall(ins.type))
                ch = _CHANNEL_RE.search(ins.rest)
                key = ch.group(1) if ch else f"{comp}/{ins.name}"
                wire = _wire_bytes(kind, size, g) * self.mult.get(comp, 0)
                if key not in seen or wire > seen[key][1]:
                    seen[key] = (kind, wire)
        for kind, wire in seen.values():
            out[kind] = out.get(kind, 0.0) + wire
        out["total"] = sum(out.values())
        return out

    def matmul_flops(self, comp: str) -> float:
        """FLOPs of one run of a computation's dots and convolutions, and of
        the multiply-and-reduce a matrix-vector dot is lowered to (2 per
        product)."""
        total = 0.0
        instrs = self.comps[comp]
        for ins in instrs.values():
            if ins.op not in ("reduce", "dot", "convolution"):
                continue
            ops = ins.operands()
            if ins.op == "reduce" and ops:
                src = instrs.get(ops[0])
                while src is not None and src.op in ("convert", "bitcast",
                                                     "copy"):
                    src = instrs.get(next(iter(src.operands()), ""))
                if src is not None and src.op == "multiply":
                    total += 2.0 * float(np.prod(_dims(src.type) or [0]))
                continue
            shapes = [_dims(self._type(comp, o) or "") for o in ops[:2]]
            out = _dims(ins.type)
            if out is None or None in shapes or len(shapes) < 2:
                continue
            fn = _dot_flops if ins.op == "dot" else _conv_flops
            total += fn(ins, shapes[0], shapes[1], out)
        return total

    def flops(self) -> float:
        """Matmul FLOPs per run of the module, each computation's once per
        trip of the loops around it."""
        return sum(m * self.matmul_flops(c) for c, m in self.mult.items() if m)


def _tuple_index(ins: Optional[_Instr]) -> Optional[int]:
    if ins is None or ins.op != "get-tuple-element":
        return None
    m = _INDEX_RE.search(ins.rest)
    return int(m.group(1)) if m else None


def _tuple_operand(tup: Optional[_Instr], i: int) -> Optional[str]:
    if tup is None or tup.op != "tuple" or i >= len(tup.operands()):
        return None
    return tup.operands()[i]


def _constant(ins, defs: Dict[str, _Instr]) -> Optional[int]:
    """The integer an instruction (or its name) holds, through copies."""
    if isinstance(ins, str):
        ins = defs.get(ins)
    while ins is not None and ins.op in ("copy", "bitcast", "convert"):
        ins = defs.get(next(iter(ins.operands()), ""))
    if ins is None or ins.op != "constant":
        return None
    m = _CONST_RE.match(ins.rest)
    return int(m.group(1)) if m else None


def _step(body: Dict[str, _Instr], root: _Instr, i: int) -> Optional[int]:
    """The constant a loop body adds to tuple element ``i``."""
    inc = body.get(_tuple_operand(root, i) or "")
    if inc is None or inc.op != "add":
        return None
    a, b = (body.get(o) for o in (inc.operands() + ["", ""])[:2])
    if _tuple_index(a) == i:
        return _constant(b, body)
    if _tuple_index(b) == i:
        return _constant(a, body)
    return None


def _dot_flops(ins: _Instr, lhs, rhs, out) -> float:
    contracted = np.prod([lhs[d] for d in
                          _int_list(ins.attr("lhs_contracting_dims"))])
    return 2.0 * float(np.prod(out)) * float(contracted)


def _conv_flops(ins: _Instr, lhs, rhs, out) -> float:
    """XLA's ``GetConvolutionFlops``: batch × input features per group ×
    output features × the valid (output, kernel) position pairs of every
    spatial dimension."""
    labels = ins.attr("dim_labels") or ""
    lhs_l, _, rest = labels.partition("_")
    rhs_l, _, out_l = rest.partition("->")
    if not (lhs_l and rhs_l and out_l):
        return 0.0
    win = _window(ins.attr("window") or "{}")
    fgc = int(ins.attr("feature_group_count") or 1)
    bgc = int(ins.attr("batch_group_count") or 1)
    valid = 1
    for k in range(sum(c.isdigit() for c in lhs_l)):
        d = str(k)
        n_out, n_ker, n_in = (out[out_l.index(d)], rhs[rhs_l.index(d)],
                              lhs[lhs_l.index(d)])

        def w(key, default):
            vals = win.get(key)
            return vals[k] if vals else default

        valid *= _valid_positions(n_out, n_ker, n_in, w("stride", [1])[0],
                                  w("pad", [0, 0])[0],
                                  w("lhs_dilate", [1])[0],
                                  w("rhs_dilate", [1])[0])
    fma = ((lhs[lhs_l.index("f")] // fgc) * out[out_l.index("f")]
           * (lhs[lhs_l.index("b")] // bgc) * valid)
    return 2.0 * fma


def collective_wire_bytes(hlo_text: str, n_devices: int) -> Dict[str, float]:
    """Per-device wire bytes by collective kind, from optimized HLO text,
    each collective once per trip of the loops around it."""
    return HloCounts(hlo_text, n_devices).collective_wire_bytes()


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
    # and counts a loop body once
    hbm_est_per_device: Optional[float] = None
    # each while loop's trips as applied to the counts above, and how many
    # loops had no count the parser could read (counted once)
    loop_trips: Tuple[int, ...] = ()
    unknown_trip_loops: int = 0

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

    def count_stats(self, prefix: str) -> Dict[str, int]:
        """What the per-trip counts applied, as span stats: the loops' trips
        summed, the loops counted once for want of a trip count, and the
        wire bytes of each collective kind."""
        out = {f"{prefix}trips": sum(self.loop_trips),
               f"{prefix}unknown_trip_loops": self.unknown_trip_loops}
        for kind, wire in self.collectives.items():
            out[f"{prefix}wire_{kind.replace('-', '_')}"] = int(wire)
        return out


def summarize(compiled, n_devices: int, with_ops: bool = False) -> Artifact:
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    hlo = HloCounts(txt, n_devices)
    coll = hlo.collective_wire_bytes()
    ops = None
    if with_ops:
        ops = {}
        for m in re.finditer(r"=\s*\S+\s+([a-z][a-z0-9-]*)\(", txt):
            ops[m.group(1)] = ops.get(m.group(1), 0) + 1
    return Artifact(
        flops_per_device=hlo.flops(),
        bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        wire_bytes_per_device=coll.get("total", 0.0),
        collectives={k: v for k, v in coll.items() if k != "total"},
        arg_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
        temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
        output_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
        n_devices=n_devices,
        hlo_ops=ops,
        loop_trips=tuple(hlo.loop_trips),
        unknown_trip_loops=hlo.unknown_trip_loops,
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

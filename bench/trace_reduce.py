"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is reduced to a plain ``Trace``: per chip, the device operations and
the compiled programs (XLA modules) that ran, as ``(name, start_ns, end_ns)``;
the host spans the harness opened (``bench.*`` trace annotations); and the
traced window, the ``bench.window`` span.  Everything after ``read_xplane``
works on that structure alone, so it is checked on hand-built traces.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, end_ns

WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]           # chip -> device operations
    modules: Dict[str, List[Event]]       # chip -> compiled programs
    spans: List[Event]                    # host spans of the harness
    window: Tuple[float, float]           # traced window, ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def merged(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Union of the events' intervals as sorted disjoint (start, end)."""
    out: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(events: Iterable[Event], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which at least one event ran."""
    return sum(e - s for s, e in merged(clip(events, lo, hi))) * 1e-9


def idle_shares(trace: Trace) -> Dict[str, float]:
    """Per chip: 1 - busy / window."""
    lo, hi = trace.window
    return {chip: 1.0 - busy_s(evs, lo, hi) / (trace.window_s or 1.0)
            for chip, evs in trace.ops.items()}


def mean_busy_s(trace: Trace) -> float:
    lo, hi = trace.window
    if not trace.ops:
        return 0.0
    return (sum(busy_s(evs, lo, hi) for evs in trace.ops.values())
            / len(trace.ops))


def time_by_name(events: Iterable[Event], parts: Sequence[str],
                 lo: float, hi: float) -> float:
    """Seconds of the events inside [lo, hi] whose name holds any of
    ``parts``."""
    return sum(e - s for n, s, e in clip(events, lo, hi)
               if any(p in n for p in parts)) * 1e-9


def top_ops(trace: Trace, k: int = 10) -> List[list]:
    """The k device operations that took most time in the window, summed
    over chips and over calls of the same name."""
    lo, hi = trace.window
    acc: Dict[str, float] = {}
    for evs in trace.ops.values():
        for n, s, e in clip(evs, lo, hi):
            acc[n] = acc.get(n, 0.0) + (e - s) * 1e-9
    return [[n, t] for n, t in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def label_at(spans: Sequence[Event], s: float, e: float) -> str:
    """The host span that overlaps [s, e] most, or ``none``."""
    best, cover = "none", 0.0
    for n, a, b in spans:
        if n == WINDOW_SPAN:
            continue
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = n, c
    return best


def idle_gaps(trace: Trace, k: int = 10) -> List[list]:
    """The k longest stretches in which no chip ran an operation, each named
    by the host span that covers most of it."""
    lo, hi = trace.window
    busy = merged(ev for evs in trace.ops.values() for ev in clip(evs, lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label_at(trace.spans, s, e), (e - s) * 1e-9]
            for s, e in gaps[:k]]


def op_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction; keep the
    instruction's name (``%fusion.12 = f32[...] ...`` -> ``fusion.12``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_xplane(trace_dir: str) -> Optional[Trace]:
    """The newest ``*.xplane.pb`` under ``trace_dir`` as a ``Trace``: device
    planes (``/device:...``) give ops from their ``XLA Ops`` line and
    programs from their ``XLA Modules`` line; host lines give the harness's
    ``bench.*`` spans."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(op_name(ev.name), ev.start_ns,
                                        ev.end_ns) for ev in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [(ev.name, ev.start_ns, ev.end_ns)
                                           for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.end_ns)
                          for ev in line.events
                          if ev.name.startswith("bench.")]
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        return None
    return Trace(ops=ops, modules=modules, spans=spans, window=win[-1])

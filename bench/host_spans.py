"""The program's own host spans (``jx.*``) in a profiler trace.

The program opens a ``jax.profiler.TraceAnnotation`` at each layer boundary
(``repro.core.tracing``): ``jx.host.*`` on the thread that runs
``JHost``'s loop, ``jx.search.*`` and ``jx.gp.*`` inside its asks,
``jx.client.*`` and ``jx.build.*`` on each board's thread.  They land on the
profiler's host plane, on the clock of the device's ``XLA Ops`` and
``XLA Modules`` lines, so a span can be set against what the chip did
meanwhile.

``read_spans`` collects them from an xplane file; the readers in
``bench/metrics/`` find them as ``trace.program_spans`` on the ``Trace``
they are given, and return ``None`` where it is missing or empty.
``label_gap`` names an idle stretch of the device by the innermost span
covering it on each thread.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace_reduce


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float           # ns, the trace's clock
    end: float
    line: int              # the host line (thread) it was recorded on
    stats: Tuple[Tuple[str, object], ...] = ()


def read_spans(trace_dir: str) -> List[Span]:
    """Every ``jx.*`` event of the host planes of the newest
    ``*.xplane.pb`` under ``trace_dir``, with its line and arguments."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    out: List[Span] = []
    line_no = 0
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [Span(ev.name, ev.start_ns, ev.end_ns, line_no,
                         tuple(ev.stats))
                    for ev in line.events if ev.name.startswith("jx.")]
            line_no += 1
    return out


def program_spans(trace) -> List[Span]:
    """The spans a ``Trace`` carries, or [] where it carries none."""
    return list(getattr(trace, "program_spans", None) or [])


def named(spans: Iterable[Span], *prefixes: str) -> List[Span]:
    return [s for s in spans if s.name.startswith(prefixes)]


def started_in(spans: Iterable[Span], lo: float, hi: float) -> List[Span]:
    return [s for s in spans if lo <= s.start < hi]


def events(spans: Iterable[Span]) -> List[trace_reduce.Event]:
    return [(s.name, s.start, s.end) for s in spans]


def uncovered_s(spans: Iterable[Span], busy: Iterable[trace_reduce.Event],
                lo: float, hi: float) -> float:
    """Seconds of [lo, hi] inside the union of ``spans`` in which none of
    the ``busy`` events ran; one pass over both, sorted."""
    cover = trace_reduce.merged(trace_reduce.clip(events(spans), lo, hi))
    busy = trace_reduce.merged(trace_reduce.clip(busy, lo, hi))
    total, j = 0.0, 0
    for s, e in cover:
        total += e - s
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < e:
            total -= min(busy[k][1], e) - max(busy[k][0], s)
            k += 1
    return total * 1e-9


def device_ops(trace) -> List[trace_reduce.Event]:
    """Every chip's device operations, as one list."""
    return [ev for evs in trace.ops.values() for ev in evs]


def per_ask_ms(run, seconds: float) -> Optional[float]:
    """``seconds`` spread over the asks that started in the traced window."""
    lo, hi = run.trace.window
    asks = started_in(named(program_spans(run.trace), "jx.host.ask"), lo, hi)
    return 1e3 * seconds / len(asks) if asks else None


def inside_s(spans: Sequence[Span], outer: Span, *prefixes: str) -> float:
    """Seconds of ``spans`` named with one of ``prefixes`` that lie inside
    ``outer`` on its thread."""
    return sum(s.end - s.start for s in named(spans, *prefixes)
               if s.line == outer.line and outer.start <= s.start
               and s.end <= outer.end) * 1e-9


def per_build_s(run, prefix: str) -> Optional[float]:
    """Mean seconds in ``prefix`` spans per ``jx.client.build`` that started
    in the traced window."""
    if run.trace is None:
        return None
    spans = program_spans(run.trace)
    lo, hi = run.trace.window
    builds = started_in(named(spans, "jx.client.build"), lo, hi)
    if not builds:
        return None
    return sum(inside_s(spans, b, prefix) for b in builds) / len(builds)


def own_seconds(spans: Iterable[Span]) -> Dict[str, float]:
    """Seconds per span name, each span's time less that of the spans
    directly inside it on its thread."""
    out: Dict[str, float] = {}
    by_line: Dict[int, List[Span]] = {}
    for sp in spans:
        by_line.setdefault(sp.line, []).append(sp)
    for line in by_line.values():
        stack: List[Span] = []
        for sp in sorted(line, key=lambda x: (x.start, -x.end)):
            while stack and stack[-1].end <= sp.start:
                stack.pop()
            dur = (sp.end - sp.start) * 1e-9
            if stack:
                out[stack[-1].name] = out.get(stack[-1].name, 0.0) - dur
            out[sp.name] = out.get(sp.name, 0.0) + dur
            stack.append(sp)
    return out


def label_gap(spans: Sequence[Span], s: float, e: float) -> Optional[str]:
    """Names [s, e] by the program's spans: on each thread, the innermost
    span that covers at least half of it; the host loop's thread first,
    joined with ``|``.  Where no span covers half, the span that covers
    most; ``None`` where no span of the program overlaps it."""
    half = 0.5 * (e - s)
    inner: Dict[int, Tuple[float, str]] = {}
    most: Optional[Tuple[float, float, str]] = None
    for sp in spans:
        cover = min(sp.end, e) - max(sp.start, s)
        if cover <= 0:
            continue
        dur = sp.end - sp.start
        if most is None or (cover, -dur) > most[:2]:
            most = (cover, -dur, sp.name)
        if cover >= half and (sp.line not in inner
                              or dur < inner[sp.line][0]):
            inner[sp.line] = (dur, sp.name)
    if not inner:
        return None if most is None else most[2]
    loop = {sp.line for sp in spans if sp.name.startswith("jx.host.")}
    return "|".join(inner[ln][1] for ln in
                    sorted(inner, key=lambda ln: (ln not in loop, ln)))


def gap_parts(spans: Sequence[Span], s: float, e: float) -> Dict[str, float]:
    """Seconds of [s, e] under each span name, summed over the name's
    spans: what the threads were inside while the chip idled."""
    out: Dict[str, float] = {}
    for sp in spans:
        cover = min(sp.end, e) - max(sp.start, s)
        if cover > 0:
            out[sp.name] = out.get(sp.name, 0.0) + cover * 1e-9
    return out


def gap_bounds(trace, k: int = 10) -> List[Tuple[float, float]]:
    """The k longest stretches of the window in which no chip ran an
    operation, longest first, as ``trace_reduce.idle_gaps`` finds them."""
    lo, hi = trace.window
    gaps, t = [], lo
    for s, e in trace_reduce.merged(
            trace_reduce.clip(device_ops(trace), lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps[:k]


def idle_gaps(trace, k: int = 10) -> List[list]:
    """``trace_reduce.idle_gaps`` with each stretch named by the program's
    spans where they overlap it, by the harness's span elsewhere."""
    spans = program_spans(trace)
    return [[label_gap(spans, s, e)
             or trace_reduce.label_at(trace.spans, s, e), (e - s) * 1e-9]
            for s, e in gap_bounds(trace, k)]

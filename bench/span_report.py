#!/usr/bin/env python3
"""One traced run of one cell, read with the program's own spans.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does (set-up, a window of sweeps
whose first ``TRACE_SECONDS`` are profiled, the checks) and reads the trace
twice: as the harness does, for the per-layer metrics ``BENCHMARK.json``
gives the cell, and with the program's ``jx.*`` spans
(``bench/host_spans.py``) for the span metrics, whose readers sit beside the
others in ``bench/metrics/``.  The last line of standard output is one JSON
object: ``correct``, both sets of metrics, the top device operations and
when the last one ended, the idle gaps named both ways with the seconds of
each under every span name, the seconds per ask and per build in each span
name (its own time, without the spans inside it), how much of each ask the
searcher's and the GP's spans cover, and the evaluations told in the traced
part of the window.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPAN_METRICS = ("ask_idle_ms", "gp_host_ms", "pool_ms", "acquire_ms",
                "pull_wait_share", "build_lower_s", "build_compile_s",
                "build_analyze_s")
# the spans that take an ask apart, and the harness's readers of the build
ASK_PARTS = ("jx.search.", "jx.gp.")
BUILD_READERS = ("build_s", "builds_per_sweep")


def report(root: str, workload: str, seed: int, seconds: float,
           require_chip: bool = True, peaks_kind=None) -> dict:
    from bench import check, harness, host_spans, reference, trace_reduce
    from bench.metrics_io import RunData
    from bench.spec import Spec

    spec = Spec(root)
    chips = int(spec.config(spec.cell(workload)["config"])["chips_per_board"])
    dev = harness.devices(chips, require_chip)
    peaks = reference.load_peaks(peaks_kind or dev["kind"])
    cache_root = tempfile.mkdtemp(prefix="bench-")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        cell, _, _ = harness.set_up(spec, workload, seed, cache_root)
        harness.log("set-up done")
        rec = harness.run_window(cell, seed, seconds, trace_dir)
        verdict = check.run_checks(cell, rec, peaks)
        harness.log("window and checks done")
        trace = trace_reduce.read_xplane(trace_dir)
        spans = host_spans.read_spans(trace_dir)
        harness.log(f"trace read: {len(spans)} program spans")
    finally:
        shutil.rmtree(cache_root, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = RunData(rec=rec, trace=trace, lo=rec.trace.t0, hi=rec.trace.t1,
                  peaks=peaks, traffic=cell.traffic, config=cell.cfg)
    readers = spec.readers(workload)
    readers.update((n, spec.reader(n)) for n in BUILD_READERS)
    harness_metrics = {n: read(run) for n, read in readers.items()}
    out = {"correct": verdict["correct"], "metrics": harness_metrics,
           "told_traced": sum(1 for s in rec.sweeps for t, _ in s.tells
                              if run.lo <= t < run.hi)}
    if trace is None:
        return out
    trace.program_spans = spans
    out["span_metrics"] = {n: spec.reader(n)(run) for n in SPAN_METRICS}
    out.update(span_breakdown(trace, spans))
    out["device_ops"] = trace_reduce.top_ops(trace)
    out["idle_gaps"] = trace_reduce.idle_gaps(trace)
    out["idle_gaps_by_span"] = host_spans.idle_gaps(trace)
    out["idle_gap_parts"] = [host_spans.gap_parts(spans, s, e)
                             for s, e in host_spans.gap_bounds(trace)]
    out["device_busy_s"] = trace_reduce.mean_busy_s(trace)
    out["window_s"] = trace.window_s
    lo, hi = trace.window
    ends = [e for _, _, e in host_spans.device_ops(trace) if lo < e <= hi]
    out["last_op_s"] = (max(ends) - lo) * 1e-9 if ends else None
    return out


def span_breakdown(trace, spans) -> dict:
    """Counts, own seconds per name, and the asks' coverage, over the spans
    that started in the traced window."""
    from bench import host_spans as hs

    lo, hi = trace.window
    inside = hs.started_in(spans, lo, hi)
    asks = hs.named(inside, "jx.host.ask")
    builds = hs.named(inside, "jx.client.build")
    counts: dict = {}
    for sp in inside:
        counts[sp.name] = counts.get(sp.name, 0) + 1
    own = hs.own_seconds(inside)
    parts = hs.events(hs.named(inside, *ASK_PARTS))
    asked = hs.uncovered_s(asks, [], lo, hi)
    covered = asked - hs.uncovered_s(asks, parts, lo, hi)
    return {"span_counts": counts, "asks": len(asks), "builds": len(builds),
            "own_ms_per_ask": {n: 1e3 * t / len(asks) for n, t in own.items()
                               if asks and not n.startswith(
                                   ("jx.client.", "jx.build."))},
            "own_s_per_build": {n: t / len(builds) for n, t in own.items()
                                if builds and n.startswith(
                                    ("jx.client.build", "jx.build."))},
            "ask_covered_share": covered / asked if asked else None}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    # bench/run.py's compile cache, unless one is set
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    t0 = time.monotonic()
    try:
        out = report(ROOT, args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    out["run_s"] = time.monotonic() - t0
    print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The result line's ``metrics``: end-to-end numbers by name and unit, and
the per-layer numbers their readers find in a traced run."""
from __future__ import annotations

import dataclasses
import shutil
from typing import Optional

from bench import trace_reduce
from bench.trace_reduce import Trace


@dataclasses.dataclass
class RunData:
    """What a per-layer reader (``bench/metrics/<name>.py``) is given."""
    rec: object                    # harness.Recorder of the window
    trace: Optional[Trace]         # the device trace of the traced part
    lo: float                      # the traced part of the window, on the
    hi: float                      # host's monotonic clock
    peaks: dict                    # peaks.json entry of the chip
    traffic: dict
    config: dict

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def spans(self, *names):
        """Host spans of the traced part with one of ``names``, clipped."""
        return [(n, max(s, self.lo), min(e, self.hi))
                for n, s, e in self.rec.spans
                if n in names and e > self.lo and s < self.hi]

    def window_sweeps(self):
        return [s for s in self.rec.sweeps if self.lo <= s.t_start < self.hi]


def end_to_end(spec, workload: str, values: dict) -> dict:
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in spec.end_to_end(workload) if m["name"] in values}


def per_layer(spec, cell, rec, peaks: dict, trace_dir: str) -> dict:
    try:
        trace = trace_reduce.read_xplane(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = RunData(rec=rec, trace=trace, lo=rec.trace.t0, hi=rec.trace.t1,
                  peaks=peaks, traffic=cell.traffic, config=cell.cfg)
    units = {m["name"]: m["unit"] for m in spec.per_layer(cell.name)}
    metrics = {}
    for name, read in spec.readers(cell.name).items():
        value = read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    out = {"metrics": metrics}
    if trace is not None:
        out["device_trace"] = {"busy_s": trace_reduce.mean_busy_s(trace),
                               "window_s": trace.window_s}
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                            "idle_gaps": trace_reduce.idle_gaps(trace)}
    return out

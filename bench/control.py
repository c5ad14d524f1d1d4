#!/usr/bin/env python3
"""Readings that set the limits of ``bench/check.py``, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

One process sets the cell up once, then for each seed runs a window of
``--seconds`` and reads every compared number twice: for the program (the
lower reading is the largest over the seeds) and for the control, the
reference in the precision below the stated one put in the program's place
(the upper reading is the smallest).  Each seed's line gives both verdicts
of ``bench/check.py``: ``correct`` for the program, ``control_correct``
with the control in the program's place, which has to be false.  One JSON
line per seed and a summary line; the benchmark's own runs never run this.
"""
import argparse
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, reference
    from bench.spec import Spec

    spec = Spec(ROOT)
    cfg = spec.config(spec.cell(args.workload)["config"])
    try:
        dev = harness.devices(int(cfg["chips_per_board"]), True)
    except harness.NoChip as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    peaks = reference.load_peaks(dev["kind"])
    seeds = [int(s) for s in args.seeds.split(",")]
    lower, upper, verdicts = {}, {}, {"program": [], "control": []}
    with tempfile.TemporaryDirectory(prefix="bench-") as cache:
        cell, front, _ = harness.set_up(spec, args.workload, seeds[0], cache)
        for seed in seeds:
            prog, ctrl = read_seed(cell, front, seed, args.seconds, peaks,
                                   lower, upper)
            verdicts["program"].append(prog)
            verdicts["control"].append(ctrl)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper,
                      "program_correct_seeds": sum(verdicts["program"]),
                      "control_correct_seeds": sum(verdicts["control"]),
                      "seeds": len(seeds), "device": dev}), flush=True)
    return 0


def read_seed(cell, front, seed, seconds, peaks, lower, upper):
    """One window: the program's readings and the control's, on one line;
    ``lower`` and ``upper`` are updated, the two verdicts returned."""
    from bench import check, harness

    t0 = time.monotonic()
    rec = harness.run_window(cell, seed, seconds)
    verdict = check.run_checks(cell, rec, peaks)
    prog = {k: c["value"] for k, c in verdict["checks"].items()
            if k != "failed"}
    control = check.run_checks(cell, rec, peaks, control=True)
    ctrl = {k: c["value"] for k, c in control["checks"].items()
            if k != "failed"}
    for k, v in prog.items():
        lower[k] = max(lower.get(k, -math.inf),
                       math.inf if v is None else v)
    for k, v in ctrl.items():
        upper[k] = min(upper.get(k, math.inf), math.inf if v is None else v)
    print(json.dumps({
        "seed": seed, "correct": verdict["correct"],
        "control_correct": control["correct"],
        "program": prog, "control": ctrl,
        "sweeps": len(rec.sweeps), "gp_calls": len(rec.gp_calls),
        "evaluations": sum(len(r.records) for r in rec.sweeps),
        "e2e": harness.end_to_end(rec, front, seconds),
        "wall_s": time.monotonic() - t0}), flush=True)
    return verdict["correct"], control["correct"]


if __name__ == "__main__":
    sys.exit(main())

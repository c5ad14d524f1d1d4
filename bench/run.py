#!/usr/bin/env python3
"""Benchmark of the exploration loop on the chip: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a model configuration and a traffic mix.
The run loads them, sets up (builds every software fingerprint of the
space, measures the whole space for the reference front, runs one sweep to
warm every program the searcher uses), then runs sweeps back to back for
``--seconds`` and checks what they produced against the plain references in
``bench/reference.py``.  ``--trace 1`` profiles the window and prints the
per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit);
the last lines of standard error repeat the checks.  A run that finds no
accelerator, or fewer chips than the cell asks for, exits 2 and prints no
result.
"""
import time

T_MONO = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def process_start() -> float:
    """The process's start on the monotonic clock (Linux: both count from
    boot), so set-up includes the interpreter's own start."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return T_MONO - max(0.0, min(age - (time.monotonic() - T_MONO), 60.0))
    except (OSError, ValueError, IndexError):
        return T_MONO


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def jsonable(x):
    if isinstance(x, dict):
        return {k: jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [jsonable(v) for v in x]
    if hasattr(x, "item"):
        return x.item()
    return x


def main(argv=None) -> int:
    t_process = process_start()
    args = parse_args(argv)
    # JAX's compile cache lives in the checkout at a fixed path, and every
    # program goes into it, so only a checkout's first run compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    try:
        out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    out = jsonable(out)
    for name, c in out["checks"].items():
        print(f"[bench] check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Fault tolerance end-to-end: kill the training driver mid-run, restart it,
and verify the final state is bit-identical to an uninterrupted run."""
import json
import os
import subprocess
import sys

import numpy as np

from tests.conftest import SRC

TRAIN = [sys.executable, "-m", "repro.launch.train", "--arch", "tinyllama-1.1b",
         "--reduced", "--batch", "4", "--seq", "32", "--save-every", "5",
         "--log-every", "100"]


def _run(args, expect_rc=0):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(TRAIN + args, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == expect_rc, out.stdout + out.stderr
    return out.stdout


def _load_params(ckdir, step):
    d = os.path.join(ckdir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    return {k: np.load(os.path.join(d, m["file"]))
            for k, m in manifest["leaves"].items() if k.startswith("params/")}


def test_crash_restart_identical(tmp_path):
    straight = str(tmp_path / "straight")
    faulty = str(tmp_path / "faulty")

    # uninterrupted 15-step run
    _run(["--steps", "15", "--checkpoint-dir", straight])

    # crash at step 8 (rc 42), then restart to completion
    _run(["--steps", "15", "--checkpoint-dir", faulty, "--fault-at", "8"],
         expect_rc=42)
    out = _run(["--steps", "15", "--checkpoint-dir", faulty])
    assert "resumed from step 5" in out

    a = _load_params(straight, 15)
    b = _load_params(faulty, 15)
    assert a.keys() == b.keys() and len(a) > 0
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# ---------------------------------------------------------------------------
# exploration sweeps: kill -9 the host mid-sweep, restart with --resume
# ---------------------------------------------------------------------------

EXPLORE = [sys.executable, "-m", "repro.launch.explore", "--workload",
           "llama2-7b", "--reduced", "--samples", "12", "--algorithm",
           "bayesopt", "--clients", "1", "--chips", "1", "--prompt-len", "8",
           "--gen-tokens", "4", "--seed", "5", "--batch-size", "4",
           "--checkpoint-every", "6"]


def _run_explore(args, expect_rc=0):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(EXPLORE + args, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == expect_rc, out.stdout + out.stderr
    return out.stdout


def _csv_rows(path):
    import csv

    with open(path, newline="") as f:
        return list(csv.DictReader(f))


def test_explore_crash_resume_zero_dups_bit_identical(tmp_path):
    """The durability acceptance run: ChaosTransport hard-kills the host
    (os._exit 42 — no flush, no close) mid-sweep; a --resume restart must
    finish with zero duplicate evaluations and a final CSV bit-identical
    to the uninterrupted sweep's."""
    cache = str(tmp_path / "cache")          # shared: compile once
    u_csv = str(tmp_path / "u.csv")
    c_csv = str(tmp_path / "c.csv")

    _run_explore(["--out", u_csv, "--cache-dir", cache,
                  "--checkpoint-dir", str(tmp_path / "u_ckpt")])
    _run_explore(["--out", c_csv, "--cache-dir", cache,
                  "--checkpoint-dir", str(tmp_path / "c_ckpt"),
                  "--chaos-crash-at", "7"], expect_rc=42)
    out = _run_explore(["--out", c_csv, "--cache-dir", cache,
                        "--checkpoint-dir", str(tmp_path / "c_ckpt"),
                        "--resume"])
    assert "# resume:" in out

    def key(rows):
        return sorted(tuple(sorted((k, v) for k, v in r.items()
                                   if k != "cached" and k != "wall_s"))
                      for r in rows)

    u, c = _csv_rows(u_csv), _csv_rows(c_csv)
    ids = [r["config_id"] for r in c]
    assert len(ids) == len(set(ids)) == 12   # zero duplicate evaluations
    assert key(u) == key(c)                  # bit-identical sweep


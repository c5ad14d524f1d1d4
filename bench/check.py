"""The comparison that decides ``correct``.

Once the window has closed, what the timed path produced is compared with
the plain references of ``bench/reference.py``:

* ``gp_gap``          search: for the GP calls the window recorded (drawn
  from the seed, plus each sweep's last), the widest gap between the
  device GP's posterior mean or standard deviation over the ask's pool and
  the float64 reference's, over the reference's largest |mean|.  The
  reference is given the told configurations in tell order and the targets
  the searcher fitted; the pool is the one the call scored.
* ``measure_gap``     measure: for every evaluation answered, the widest
  relative gap of its ``time_s``, ``power_w`` and ``mem_bytes`` from the
  roofline recomputed from its artifact's counts and ``peaks.json``.
* ``param_bytes_gap`` build: for every build, the relative gap between the
  prefill program's argument bytes per device and the parameter and input
  bytes one device holds, counted from the configuration file: a build
  that leaves out layers, holds other experts, shards otherwise than the
  board's chips, or stores another type than the stated bfloat16 moves it.
* ``failed``          evaluations that came back not ``ok``; exact.

Each number has its limit in the traffic or configuration file.  With
``control=True`` each number is read with the reference, in the precision
below the stated one, in the program's place: float32 for the float64 GP and
roofline, int8 for the bfloat16 parameters.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench import reference


def gp_gaps(cell, rec, control: bool = False) -> List[float]:
    gp = cell.traffic["gp_model"]
    sweeps = {s.index: s for s in rec.sweeps}
    out = []
    for call in rec.gp_calls:
        told = sweeps[call.sweep].told[:call.n_obs]
        x = np.stack([reference.encode(k, cell.space_values) for k in told])
        mu_ref, sig_ref = reference.gp_posterior(
            x, call.y, call.xq, gp["lengthscale"], gp["noise"], gp["signal"])
        mu, sig = call.mu, call.sig
        if control:
            mu, sig = reference.gp_posterior(
                x, call.y, call.xq, gp["lengthscale"], gp["noise"],
                gp["signal"], dtype=np.float32)
        gap = max(np.abs(np.asarray(mu, float) - mu_ref).max(),
                  np.abs(np.asarray(sig, float) - sig_ref).max())
        out.append(float(gap / max(np.abs(mu_ref).max(), 1e-300)))
    return out


def measure_gaps(cell, rec, peaks: dict, control: bool = False
                 ) -> List[float]:
    n_tok = int(cell.cfg["workload"]["gen_tokens"])
    arts = cell.builds.artifacts
    out = []
    for sweep in rec.sweeps:
        for r in sweep.records:
            if r.status != "ok":
                continue
            art = arts.get(cell.builds.fingerprint(r.knobs))
            if art is None or art["dec"] is None:
                out.append(float("inf"))     # no build stands behind it
                continue
            ref = reference.measure_generation(
                art["pre"], art["dec"], n_tok, cell.chips, r.knobs, peaks)
            got = r.metrics
            if control:
                got = reference.measure_generation(
                    art["pre"], art["dec"], n_tok, cell.chips, r.knobs,
                    peaks, dtype=np.float32)
            out.append(max(abs(float(got[k]) - float(ref[k]))
                           / abs(float(ref[k]))
                           for k in ("time_s", "power_w", "mem_bytes")))
    return out


def param_bytes_gaps(cell, control: bool = False) -> List[float]:
    model, w = cell.cfg["model"], cell.cfg["workload"]
    ref = (reference.param_bytes(model, cell.chips,
                                 1.0 if control else 2.0)
           + reference.prefill_input_bytes(w["batch"], w["prompt_len"]))
    return [abs(got["pre"]["arg_bytes"] - ref) / ref
            for _, got in cell.builds.all_builds]


def widest(xs: List[float]) -> Optional[float]:
    return max(xs) if xs else None


def readings(cell, rec, peaks: dict, control: bool = False
             ) -> Dict[str, Optional[float]]:
    out = {}
    if cell.traffic["algorithm"] in ("bayesopt", "pal"):
        out["gp_gap"] = widest(gp_gaps(cell, rec, control))
    out["measure_gap"] = widest(measure_gaps(cell, rec, peaks, control))
    out["param_bytes_gap"] = widest(param_bytes_gaps(cell, control))
    return out


def run_checks(cell, rec, peaks: dict, control: bool = False) -> dict:
    """The verdict on the window; with ``control=True``, the verdict with the
    control in the program's place, which has to come out not correct."""
    records = [r for s in rec.sweeps for r in s.records]
    failed = (sum(r.status != "ok" for r in records)
              + sum(s.error is not None for s in rec.sweeps))
    limits = dict(cell.traffic["limits"], **cell.cfg["limits"])
    values = dict(readings(cell, rec, peaks, control), failed=failed)
    checks, correct = {}, True
    for name, value in values.items():
        limit = limits[name]
        ok = value is not None and value <= limit
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit}
    return {"correct": correct, "attempted": len(records), "failed": failed,
            "checks": checks}

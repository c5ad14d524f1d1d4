"""The timed path broken underneath, on the CPU: each fault a cell can have
must turn ``correct`` false, and the control must fail a limit.

One set-up of the tiny warm cell (``bench/testing.py``) serves every case;
each case breaks the program, runs a window and checks it as a run does.
"""
import numpy as np
import pytest

from bench import check, harness, reference
from bench.spec import Spec
from bench.testing import make_tiny_root

SEED = 2 ** 32 + 77
PEAKS = reference.load_peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = make_tiny_root(str(tmp_path_factory.mktemp("tiny")),
                          samples=40, timeout_s=2)
    cache = str(tmp_path_factory.mktemp("cache"))
    c, _, _ = harness.set_up(Spec(root), "tiny.warm", SEED, cache)
    return c


def window(cell, seconds=3.0):
    rec = harness.run_window(cell, SEED, seconds)
    return rec, check.run_checks(cell, rec, PEAKS)


def test_the_unbroken_program_is_correct(cell):
    rec, verdict = window(cell)
    assert verdict["correct"], verdict["checks"]
    assert rec.gp_calls


def test_the_control_fails_a_limit_the_program_meets(cell):
    """The reference in float32 (GP, roofline) and int8 (parameters) in the
    program's place, at the tiny size."""
    rec, verdict = window(cell)
    ctrl = check.readings(cell, rec, PEAKS, control=True)
    limits = dict(cell.traffic["limits"], **cell.cfg["limits"])
    failed = [k for k, v in ctrl.items() if v > limits[k]]
    assert set(failed) == {"gp_gap", "measure_gap", "param_bytes_gap"}
    assert verdict["correct"]


def test_the_control_in_the_programs_place_is_not_correct(cell):
    """The verdict ``bench/control.py`` prints for the control: the same
    comparison as a run's, with the control's readings."""
    rec, verdict = window(cell)
    control = check.run_checks(cell, rec, PEAKS, control=True)
    assert verdict["correct"] and not control["correct"]
    assert control["attempted"] == verdict["attempted"]
    for name in ("gp_gap", "measure_gap", "param_bytes_gap"):
        c = control["checks"][name]
        assert c["value"] > c["limit"], name


def _state_unchanged(mp):
    """The GP keeps its first block and drops every later observation."""
    from repro.core.search.gp_jax import JaxIncrementalGP

    orig = JaxIncrementalGP._append_active

    def frozen(self, xa, idx):
        if self._n == 0:
            orig(self, xa, idx)
    mp.setattr(JaxIncrementalGP, "_append_active", frozen)
    return "gp_gap"


def _half_batch(mp):
    """A board answers half of each chunk it is sent."""
    from repro.core.jclient import JClient

    orig = JClient.evaluate_batch
    mp.setattr(JClient, "evaluate_batch",
               lambda self, tcs: orig(self, tcs[:max(1, len(tcs) // 2)]))
    return "failed"


def _measure_altered(mp):
    """The roofline time is off by one part in a million."""
    from repro.core.jmeasure import JTime

    orig = JTime.measure_batch

    def skewed(self, art, hwb, meta):
        out = orig(self, art, hwb, meta)
        out["time_s"] = out["time_s"] * (1 + 1e-6)
        return out
    mp.setattr(JTime, "measure_batch", skewed)
    return "measure_gap"


def _posterior_altered(mp):
    """The device GP's posterior mean is off by one part in a million."""
    from repro.core.search.gp_jax import JaxIncrementalGP

    orig = JaxIncrementalGP.predict

    def skewed(self, xs):
        mu, sig = orig(self, xs)
        return mu + 1e-6 * np.abs(mu).max(), sig
    mp.setattr(JaxIncrementalGP, "predict", skewed)
    return "gp_gap"


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _measure_altered, _posterior_altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_timed_path_is_not_correct(cell, monkeypatch, fault):
    name = fault(monkeypatch)
    _, verdict = window(cell)
    assert not verdict["correct"]
    c = verdict["checks"][name]
    assert c["value"] is None or c["value"] > c["limit"], verdict["checks"]

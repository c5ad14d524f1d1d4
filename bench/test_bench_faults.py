"""The timed path broken underneath, on the CPU: each fault a cell can have
must turn ``correct`` false, and the control must fail a limit.

One set-up of the tiny warm cell (``bench/testing.py``) serves every case;
each case breaks the program, runs a window and checks it as a run does.
A two-layer MoE cell, whose faults only a build can show, checks a build.
"""
import dataclasses
import json
import os

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


TINY_MOE = {"kind": "transformer", "num_hidden_layers": 2, "hidden_size": 64,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
            "tie_word_embeddings": True, "n_routed_experts": 4,
            "n_shared_experts": 1, "num_experts_per_tok": 2,
            "moe_intermediate_size": 64, "first_k_dense_replace": 0,
            "router_dtype": "float32",
            "layers": [{"mixer": "attention", "ffn": "moe"}] * 2}


@pytest.fixture(scope="module")
def moe_cell(tmp_path_factory):
    """A two-layer MoE at sizes of its own, registered from its file."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("tinymoe")))
    cfg = {"name": "tinymoe", "arch": "deepseek-moe-16b", "source": "test",
           "reduced": [], "model": TINY_MOE,
           "workload": {"prompt_len": 16, "gen_tokens": 8, "batch": 1,
                        "dtype": "bfloat16"},
           "chips_per_board": 1, "limits": {"param_bytes_gap": 0.05}}
    with open(os.path.join(root, "bench", "configs", "tinymoe.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tinymoe", "source": "test",
                         "file": "bench/configs/tinymoe.json", "reduced": [],
                         "why": "CPU test"})
    b["workloads"].append({"name": "tinymoe.random", "config": "tinymoe",
                           "traffic": "tiny-random", "chips": 1,
                           "why": "CPU test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return harness.Cell(Spec(root), "tinymoe.random",
                        str(tmp_path_factory.mktemp("moecache")))


@pytest.mark.parametrize("extra", [0, 1], ids=["as_stated", "one_expert_more"])
def test_a_program_holding_one_expert_more_is_not_correct(moe_cell,
                                                          monkeypatch, extra):
    """Once its sizes are checked, the program builds an architecture with
    one routed expert more than the file states in each layer: the build
    check reads the extra expert's bytes."""
    from repro.configs import base, get_arch
    from repro.core.jconfig import TestConfig

    name = moe_cell.args.workload
    arch = get_arch(name)
    assert arch.n_experts == 4 and harness.size_mismatches(
        TINY_MOE, arch) == []
    monkeypatch.setitem(base._REGISTRY, name, dataclasses.replace(
        arch, n_experts=arch.n_experts + extra))
    moe_cell.builds.all_builds.clear()
    moe_cell.builds(TestConfig(0, name, "generate",
                               moe_cell.all_configs()[0]))
    rec = harness.Recorder(deadline=0.0, traced=False)
    c = check.run_checks(moe_cell, rec, PEAKS)["checks"]["param_bytes_gap"]
    if extra:
        assert c["value"] > c["limit"], c
    else:
        assert c["value"] <= c["limit"], c


def test_one_expert_more_at_a_published_size_is_caught_by_its_sizes():
    """At ``deepseek-moe-16b``'s own sizes one routed expert more in each
    layer is 1.4% of the parameter bytes, under the 0.1 limit the
    configurations use, so ``check_sizes`` is what refuses it, and it can
    refuse only an architecture that states the extra expert."""
    import jax

    from bench.test_bench_reference import LAYER_MIXES
    from repro.configs import get_arch
    from repro.models import BuildFlags, Model

    model = LAYER_MIXES["deepseek-moe-16b"]
    arch = get_arch("deepseek-moe-16b")
    more = dataclasses.replace(arch, n_experts=arch.n_experts + 1)
    shapes = Model(more, BuildFlags()).init_shapes()
    got = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
              for leaf in jax.tree_util.tree_leaves(shapes))
    gap = abs(got - reference.param_bytes(model, 1)) / reference.param_bytes(
        model, 1)
    assert 0.01 < gap < 0.02
    assert harness.size_mismatches(model, arch) == []
    assert any("n_experts=65" in m
               for m in harness.size_mismatches(model, more))

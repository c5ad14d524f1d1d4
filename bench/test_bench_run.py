"""Whole runs of the harness on the CPU: the look for a chip, a sound run,
and a build that stores another type than the configuration states."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.testing import ROOT, make_tiny_root

SEED = 2 ** 31 + 12345


def _no_result(stdout: str) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in stdout.splitlines())


def test_a_run_on_the_cpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "yi-9b.24l.bayes-warm", "--seed", str(SEED),
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert _no_result(p.stdout)
    assert "no accelerator" in p.stderr


def test_a_checkout_of_only_the_benchmark_exits_nonzero(tmp_path):
    root = make_tiny_root(str(tmp_path / "bare"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "tiny.warm", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert _no_result(p.stdout)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny")))


def test_a_sound_run_is_correct_and_reports_its_metrics(tiny):
    out = harness.run(tiny, "tiny.warm", SEED, 3.0, False, time.monotonic(),
                      require_chip=False, peaks_kind="TPU v5 lite")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"evals_per_s", "setup_s"} <= set(out["metrics"])
    for name, c in out["checks"].items():
        assert c["value"] is not None and c["value"] <= c["limit"], name
    assert out["device"]["count"] == 1
    json.dumps(out, default=float)


def test_a_traced_run_reports_its_per_layer_metrics(tiny):
    out = harness.run(tiny, "tiny.warm", SEED + 1, 3.0, True,
                      time.monotonic(), require_chip=False,
                      peaks_kind="TPU v5 lite")
    assert out["correct"], out["checks"]
    # the CPU's trace has no device plane: the readers of the device trace
    # leave their metrics out instead of reporting 0
    assert set(out["metrics"]) == {"search_share"}
    assert 0 < out["metrics"]["search_share"]["value"] <= 100
    assert 2.5 < out["device"]["window_s"] < 4.0
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    assert list(out)[-1] == "checks"


def test_a_random_search_run_is_correct_and_reports_its_metrics(tiny):
    """The build-bound mix: random search, no surrogate to check."""
    out = harness.run(tiny, "tiny.random", SEED + 2, 15.0, False,
                      time.monotonic(), require_chip=False,
                      peaks_kind="TPU v5 lite")
    assert out["correct"], out["checks"]
    assert "gp_gap" not in out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"sweep_s", "setup_s"}
    assert 0 < out["metrics"]["sweep_s"]["value"] < 15.0


def test_sweep_s_counts_the_time_between_sweeps():
    """``sweep_s`` is the window up to its last finished sweep over the
    finished sweeps: a slow teardown between sweeps counts, a sweep the
    window closes does not."""
    rec = harness.Recorder(deadline=20.0, traced=False)
    rec.t0 = 0.0
    rec.sweeps = [harness.Sweep(0, 0.0, 4.0), harness.Sweep(1, 7.0, 11.0),
                  harness.Sweep(2, 11.5, 15.5), harness.Sweep(3, 16.0)]
    out = harness.end_to_end(rec, {"hv": 1.0, "ref_point": None}, 20.0)
    assert out["sweep_s"] == 15.5 / 3


def test_a_configuration_that_departs_from_the_program_is_registered():
    """A cut in depth is explored as a workload of its own, at the sizes
    the configuration file states; the program's entry is left as it is."""
    from repro.configs import get_arch

    def config(name):
        with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
            return json.load(f)

    cfg = config("yi-9b.24l")
    name = harness.workload_arch(cfg)
    assert name != "yi-9b" and get_arch("yi-9b").n_layers == 48
    assert get_arch(name).n_layers == 24
    assert harness.workload_arch(cfg) == name
    assert harness.workload_arch(config("yi-9b")) == "yi-9b"
    mamba = get_arch(harness.workload_arch(config("mamba2-780m")))
    assert mamba.vocab_size == 50288 and mamba.tie_embeddings


def test_sizes_the_program_departs_from_are_named():
    """One expert more, another layer list, or a size no reference counts
    (grouped B/C projections): each is named."""
    import dataclasses

    from repro.configs import get_arch
    from repro.configs.base import LayerSpec

    model = {"kind": "transformer", "num_hidden_layers": 28,
             "n_routed_experts": 64, "first_k_dense_replace": 1,
             "layers": [{"mixer": "attention", "ffn": "moe"}] * 28}
    arch = get_arch("deepseek-moe-16b")
    assert harness.size_mismatches(model, arch) == []
    more = dataclasses.replace(arch, n_experts=65)
    assert any("n_experts=65" in m
               for m in harness.size_mismatches(model, more))
    dense = dataclasses.replace(arch, pattern=(LayerSpec(),))
    assert any("layer 1" in m for m in harness.size_mismatches(model, dense))
    grouped = dict(model, n_groups=8)
    assert any("n_groups" in m
               for m in harness.size_mismatches(grouped, arch))


FOUR_CHIP_RUN = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
if {replicate}:
    from jax.sharding import PartitionSpec
    from repro.parallel.sharding import ShardingPolicy
    ShardingPolicy.param_spec = lambda self, path, shape: PartitionSpec()
out = harness.run({tiny!r}, "tiny4.cold", 7, 2.0, False, time.monotonic(),
                  require_chip=False, peaks_kind="TPU v5 lite")
print(json.dumps(out["checks"], default=float))
"""


@pytest.mark.parametrize("replicate", [False, True],
                         ids=["sharded", "exchange_left_out"])
def test_a_four_chip_board_unsharded_is_not_correct(tiny, replicate):
    """A tp=4 board whose parameters are not split over the chips (so no
    exchange between them is needed) holds about 4x the bytes on each
    device."""
    with open(os.path.join(tiny, "BENCHMARK.json")) as f:
        b = json.load(f)
    with open(os.path.join(tiny, "bench", "configs", "tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny4", chips_per_board=4)
    with open(os.path.join(tiny, "bench", "configs", "tiny4.json"), "w") as f:
        json.dump(cfg, f)
    if "tiny4.cold" not in {w["name"] for w in b["workloads"]}:
        b["configs"].append({"name": "tiny4", "source": "test",
                             "file": "bench/configs/tiny4.json",
                             "reduced": [], "why": "CPU test"})
        b["workloads"].append({"name": "tiny4.cold", "config": "tiny4",
                               "traffic": "tiny-cold", "chips": 4,
                               "why": "CPU test"})
        with open(os.path.join(tiny, "BENCHMARK.json"), "w") as f:
            json.dump(b, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIP_RUN.format(root=ROOT, src=os.path.join(ROOT, "src"),
                                replicate=replicate, tiny=tiny)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    gap = json.loads(p.stdout.strip().splitlines()[-1])["param_bytes_gap"]
    if replicate:
        assert gap["value"] > 2.5 and gap["value"] > gap["limit"]
    else:
        assert gap["value"] <= gap["limit"]


def test_a_build_in_another_type_is_not_correct(tiny, monkeypatch):
    """The build check: float32 parameters where bfloat16 is stated."""
    from repro.core.jconfig import JConfig
    from repro.models.model import BuildFlags

    monkeypatch.setattr(JConfig, "build_flags",
                        lambda self, knobs: BuildFlags(dtype="float32"))
    out = harness.run(tiny, "tiny.warm", SEED, 2.0, False, time.monotonic(),
                      require_chip=False, peaks_kind="TPU v5 lite")
    assert not out["correct"]
    c = out["checks"]["param_bytes_gap"]
    assert c["value"] > c["limit"]

"""The benchmark's copied arithmetic against the program's, on the CPU."""
import json
import os

import numpy as np
import pytest

from bench import counts, reference

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "traffic", "bayes-warm.json")) as _f:
    GP_LIMIT = json.load(_f)["limits"]["gp_gap"]


@pytest.mark.parametrize("seed", range(5))
def test_hypervolume_matches_the_program(seed):
    from repro.core.search.hypervolume import hypervolume_2d

    rng = np.random.default_rng(seed)
    pts = rng.random((int(rng.integers(1, 60)), 2))
    ref = np.array([1.1, 1.2])
    assert reference.hypervolume_2d(pts, ref) == pytest.approx(
        hypervolume_2d(pts, ref), rel=1e-12, abs=0)


def test_hypervolume_of_a_staircase_by_hand():
    pts = np.array([[1.0, 3.0], [2.0, 2.0], [3.0, 1.0], [3.5, 3.5]])
    # columns 1-2, 2-3, 3-4 under heights 1, 2, 3
    assert reference.hypervolume_2d(pts, [4.0, 4.0]) == pytest.approx(6.0)


def test_gp_counts_by_hand():
    cap, pool, dim = 1024, 512, 7
    kern = 2 * pool * cap * dim + 2 * (pool + cap) * dim + 6 * pool * cap
    assert counts.predict_flops(cap, pool, dim) == (
        kern + 2 * pool * cap + 2 * cap * cap * pool + 2 * cap * pool)
    # the (cap, cap) x (cap, pool) product is about 1.07 GFLOP of it
    assert 2 * cap * cap * pool == 1_073_741_824
    assert counts.fit_y_flops(cap) == 4 * cap * cap
    b = 4
    assert counts.append_flops(cap, b, dim) == (
        counts.kernel_flops(cap, b, dim) + counts.kernel_flops(b, b, dim)
        + 2 * cap * cap * b + 2 * b * b * cap + b ** 3 // 3 + b ** 3
        + 2 * b * cap * cap + 2 * b * b * cap)


def _gp_case(seed, n=48, d=6, q=64):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 4, (n, d)) / 3.0
    x = np.unique(x, axis=0)
    y = np.sin(3 * x).sum(1) + 0.1 * rng.standard_normal(len(x))
    return x, y, rng.random((q, d))


@pytest.mark.parametrize("seed", range(3))
def test_plain_gp_matches_the_program_and_float32_does_not(seed):
    from repro.core.search.bayesopt import IncrementalGP

    x, y, xq = _gp_case(seed)
    mu_p, sig_p = IncrementalGP(0.3, 1e-3, 1.0).fit(x, y).predict(xq)
    mu, sig = reference.gp_posterior(x, y, xq, 0.3, 1e-3, 1.0)
    scale = np.abs(mu).max()
    gap = max(np.abs(mu - mu_p).max(), np.abs(sig - sig_p).max()) / scale
    assert gap < 1e-12
    mu32, sig32 = reference.gp_posterior(x, y, xq, 0.3, 1e-3, 1.0,
                                         dtype=np.float32)
    gap32 = max(np.abs(mu32 - mu).max(), np.abs(sig32 - sig).max()) / scale
    assert gap32 > GP_LIMIT


def _artifact(flops, hbm, wire, n_dev, arg, temp, out):
    from repro.roofline.analysis import Artifact

    return Artifact(flops_per_device=flops, bytes_per_device=3 * hbm,
                    wire_bytes_per_device=wire, collectives={},
                    arg_bytes=arg, temp_bytes=temp, output_bytes=out,
                    n_devices=n_dev, hbm_est_per_device=hbm)


def _counts(a):
    return {"flops_per_device": a.flops_per_device,
            "hbm_bytes_per_device": a.hbm_est_per_device,
            "wire_bytes_per_device": a.wire_bytes_per_device,
            "n_devices": a.n_devices, "arg_bytes": a.arg_bytes,
            "temp_bytes": a.temp_bytes, "output_bytes": a.output_bytes}


@pytest.mark.parametrize("n_chips", [1, 4])
def test_roofline_matches_the_programs_measures(n_chips):
    from repro.core.jmeasure import DEFAULT_MEASURES
    from repro.roofline.hw import (CLOCK_LADDER, HBM_LADDER, ICI_LADDER,
                                   HwModel)

    pre = _artifact(2.6e10, 4.1e9, 3e7 * (n_chips > 1), n_chips, 3_000_000,
                    10_000, 4_000)
    dec = _artifact(3.4e8, 3.6e9, 1e6 * (n_chips > 1), n_chips, 3_300_000,
                    20_000, 9_000)
    meta = {"decode_artifact": dec, "n_decode_tokens": 150}
    peaks = reference.load_peaks("TPU v5 lite")
    worst, worst32 = 0.0, 0.0
    for c in CLOCK_LADDER:
        for h in HBM_LADDER:
            for i in ICI_LADDER:
                hw = HwModel(n_chips=n_chips, clock_scale=c, hbm_scale=h,
                             ici_scale=i)
                got = {}
                for m in DEFAULT_MEASURES:
                    got.update(m.measure(pre, hw, meta))
                knobs = {"clock_scale": c, "hbm_scale": h, "ici_scale": i}
                args = (_counts(pre), _counts(dec), 150, n_chips, knobs,
                        peaks)
                ref = reference.measure_generation(*args)
                ref32 = reference.measure_generation(*args,
                                                     dtype=np.float32)
                for k in ("time_s", "power_w", "mem_bytes"):
                    worst = max(worst, abs(got[k] - ref[k]) / ref[k])
                    worst32 = max(worst32,
                                  abs(float(ref32[k]) - ref[k]) / ref[k])
    assert worst < 1e-14
    assert worst32 > 1e-9


@pytest.mark.parametrize("config", ["mamba2-780m", "yi-9b.24l", "yi-9b"])
def test_param_bytes_count_the_programs_parameters(config):
    """At the configured sizes, on the parameter shapes the program builds
    for the workload the harness explores."""
    import jax

    from bench.harness import workload_arch
    from repro.configs import get_arch
    from repro.models import BuildFlags, Model

    with open(os.path.join(HERE, "configs", config + ".json")) as f:
        cfg = json.load(f)
    model = cfg["model"]
    shapes = Model(get_arch(workload_arch(cfg)), BuildFlags()).init_shapes()
    got = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
              for leaf in jax.tree_util.tree_leaves(shapes))
    assert reference.param_bytes(model, 1) == got
    assert reference.param_bytes(model, 1, 1.0) < 0.51 * got

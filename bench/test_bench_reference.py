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
    # a predict that reuses the last full one's pool runs the mean alone
    assert counts.predict_mean_flops(cap, pool, dim) == kern + 2 * pool * cap
    assert counts.predict_flops(cap, pool, dim) - counts.predict_mean_flops(
        cap, pool, dim) == 2 * cap * cap * pool + 2 * cap * pool
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


# the program's registered layer mixes at their sizes, as configuration
# files state them: routed and shared experts with a leading dense layer,
# Mamba-2 and attention interleaved with MoE every other layer, and
# windowed attention five layers of six
LAYER_MIXES = {
    "deepseek-moe-16b": {
        "kind": "transformer", "num_hidden_layers": 28, "hidden_size": 2048,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "head_dim": 128, "intermediate_size": 10944, "vocab_size": 102400,
        "tie_word_embeddings": False, "hidden_act": "silu",
        "n_routed_experts": 64, "n_shared_experts": 2,
        "num_experts_per_tok": 6, "moe_intermediate_size": 1408,
        "first_k_dense_replace": 1, "router_dtype": "float32",
        "layers": [{"mixer": "attention", "ffn": "moe"}] * 28},
    "jamba-v0.1-52b": {
        "kind": "hybrid", "num_hidden_layers": 32, "hidden_size": 4096,
        "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
        "intermediate_size": 14336, "vocab_size": 65536,
        "tie_word_embeddings": False, "num_experts": 16,
        "num_experts_per_tok": 2, "moe_intermediate_size": 14336,
        "router_dtype": "float32", "ssm_state_size": 16,
        "mamba_head_dim": 64, "expand": 2, "conv_kernel": 4,
        "layers": [{"mixer": "attention" if i % 8 == 4 else "mamba2",
                    "ffn": "moe" if i % 2 else "dense"} for i in range(32)]},
    "gemma3-27b": {
        "kind": "transformer", "num_hidden_layers": 62, "hidden_size": 5376,
        "num_attention_heads": 32, "num_key_value_heads": 16,
        "head_dim": 128, "intermediate_size": 21504, "vocab_size": 262144,
        "tie_word_embeddings": False, "sliding_window": 1024,
        "layers": [{"mixer": "attention" if i % 6 == 5
                    else "attention_window", "ffn": "dense"}
                   for i in range(62)]},
}


@pytest.mark.parametrize("config", ["mamba2-780m", "yi-9b.24l", "yi-9b",
                                    *LAYER_MIXES])
def test_param_bytes_count_the_programs_parameters(config):
    """At the configured sizes, on the parameter shapes the program builds
    for the workload the harness explores."""
    import jax

    from bench.harness import size_mismatches, workload_arch
    from repro.configs import get_arch
    from repro.models import BuildFlags, Model

    if config in LAYER_MIXES:
        cfg = {"name": config, "arch": config, "model": LAYER_MIXES[config]}
        assert workload_arch(cfg) == config
    else:
        with open(os.path.join(HERE, "configs", config + ".json")) as f:
            cfg = json.load(f)
    model = cfg["model"]
    arch = get_arch(workload_arch(cfg))
    assert size_mismatches(model, arch) == []
    shapes = Model(arch, BuildFlags()).init_shapes()
    got = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
              for leaf in jax.tree_util.tree_leaves(shapes))
    assert reference.param_bytes(model, 1) == got
    assert reference.param_bytes(model, 1, 1.0) < 0.51 * got


def test_param_bytes_by_hand_split_two_ways():
    """Mamba-2 and attention mixers, a leading dense layer, routed and
    shared experts with a bfloat16 router, and a layer with no FFN, split
    two ways: matrices halved, vectors and the router whole."""
    d, v = 16, 40
    model = {"kind": "hybrid", "num_hidden_layers": 3, "hidden_size": d,
             "vocab_size": v, "tie_word_embeddings": False,
             "num_attention_heads": 2, "num_key_value_heads": 1,
             "head_dim": 8, "mamba_head_dim": 4, "expand": 2,
             "ssm_state_size": 5, "conv_kernel": 4,
             "intermediate_size": 20, "n_routed_experts": 2,
             "n_shared_experts": 1, "moe_intermediate_size": 6,
             "router_dtype": "bfloat16", "first_k_dense_replace": 1,
             "layers": [{"mixer": "attention", "ffn": "moe"},
                        {"mixer": "mamba2", "ffn": "none"},
                        {"mixer": "attention", "ffn": "moe"}]}
    di, bc, h = 2 * d, 2 * 5, 2 * d // 4
    mamba_mats = d * (2 * di + bc + h) + di * d + (di + bc) * 4
    mamba_vecs = (di + bc) + di + d
    attn_mats = 2 * d * 2 * 8 + 2 * d * 1 * 8
    dense = 3 * d * 20                   # layer 0: the leading dense layer
    moe = 2 * (3 * d * 6) + 3 * d * 6    # layer 2: two experts, the shared
    mats = attn_mats + dense + mamba_mats + attn_mats + moe + 2 * v * d
    vecs = d + d + mamba_vecs + d + d + d   # attn, FFN, mixer, attn, FFN, final
    whole = 4 * 3 * h + d * 2 * 2        # A_log, D, dt bias; bf16 router
    assert reference.layer_kinds(model)[0] == ("attention", "dense")
    assert reference.param_bytes(model, 2) == (mats / 2 + vecs) * 2 + whole
    assert reference.param_bytes(model, 2, 1.0) == mats / 2 + vecs + whole


def test_a_layer_mix_without_its_layers_is_refused():
    with pytest.raises(ValueError, match="layers"):
        reference.layer_kinds({"kind": "hybrid", "num_hidden_layers": 2})
    with pytest.raises(ValueError, match="3 layers"):
        reference.layer_kinds({"kind": "hybrid", "num_hidden_layers": 2,
                               "layers": [{"mixer": "mamba2",
                                           "ffn": "none"}] * 3})
    with pytest.raises(ValueError, match="unknown"):
        reference.layer_kinds({"kind": "hybrid", "num_hidden_layers": 1,
                               "layers": [{"mixer": "rwkv", "ffn": "none"}]})

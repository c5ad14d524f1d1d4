"""Roofline layer: HLO collective parsing, hardware model, traffic model."""
import numpy as np
import pytest

from repro.configs import SHAPES, get_arch
from repro.models.model import BuildFlags
from repro.roofline.analysis import collective_wire_bytes, _shape_bytes
from repro.roofline.hw import HBM_LADDER, HwModel
from repro.roofline.traffic import analytic_hbm_bytes_per_device


def test_shape_bytes():
    assert _shape_bytes("f32[1024,1024]") == 4 * 1024 * 1024
    assert _shape_bytes("bf16[8,16]{1,0}") == 2 * 128
    assert _shape_bytes("(f32[4], bf16[2,2])") == 16 + 8
    assert _shape_bytes("pred[]") == 1


CRAFTED_HLO = """
ENTRY %main {
  %ag = f32[1024,1024]{1,0} all-gather(%x), channel_id=1, replica_groups=[1,4]<=[4], dimensions={1}
  %ar = bf16[512]{0} all-reduce(%y), replica_groups={{0,1},{2,3}}, to_apply=%add
  %rs = f32[256]{0} reduce-scatter(%z), replica_groups=[1,4]<=[4], dimensions={0}
  %cp = f32[128]{0} collective-permute(%w), source_target_pairs={{0,1},{1,2}}
  %ignored = f32[64]{0} add(%a, %b)
  %ags = (f32[64]{0}, f32[64]{0}) all-gather-start(%q), replica_groups=[1,4]<=[4]
}
"""


def test_collective_parsing_crafted():
    got = collective_wire_bytes(CRAFTED_HLO, 4)
    assert got["all-gather"] == pytest.approx(
        4 * 1024 * 1024 * 3 / 4       # main all-gather
        + (64 * 4) * 3 / 4)           # -start: its tuple's result, once
    assert got["all-reduce"] == pytest.approx(2 * 512 * 2 * 1 / 2)  # group of 2
    assert got["reduce-scatter"] == pytest.approx(256 * 4 * 3)
    assert got["collective-permute"] == pytest.approx(128 * 4)
    assert "add" not in got


@pytest.mark.parametrize("lhs,rhs,kw", [
    ((2, 3, 16, 16), (8, 3, 3, 3), dict(window_strides=(1, 1),
                                        padding="SAME")),
    ((2, 4, 15, 9), (6, 2, 3, 2), dict(window_strides=(2, 1),
                                       padding=((1, 2), (0, 1)),
                                       lhs_dilation=(2, 1),
                                       rhs_dilation=(1, 2),
                                       feature_group_count=2)),
    ((1, 8, 32), (8, 8, 5), dict(window_strides=(3,), padding="VALID",
                                 rhs_dilation=(2,))),
    ((64, 48), (48, 32), None),
], ids=["same", "dilated-grouped", "strided-1d", "dot"])
def test_matmul_flops_equal_cost_analysis_outside_loops(lhs, rhs, kw):
    """Outside any loop the HLO matmul count is XLA's own: padding,
    dilation holes, strides and feature groups as cost analysis counts
    them."""
    import jax
    import jax.numpy as jnp

    from repro.roofline.analysis import HloCounts

    def f(a, b):
        if kw is None:
            return a @ b
        return jax.lax.conv_general_dilated(a, b, **kw)

    compiled = jax.jit(f).lower(jnp.zeros(lhs), jnp.zeros(rhs)).compile()
    assert HloCounts(compiled.as_text(), 1).flops() == pytest.approx(
        compiled.cost_analysis()["flops"])


def test_hw_model_terms_and_ladders():
    hw = HwModel(n_chips=256)
    t = hw.roofline_terms(flops=197e12 * 256, hbm_bytes=0, collective_bytes=0)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["dominant"] == "compute_s"
    slow = HwModel(n_chips=256, hbm_scale=HBM_LADDER[0])
    t2 = slow.roofline_terms(flops=1, hbm_bytes=819e9 * 256, collective_bytes=0)
    assert t2["memory_s"] == pytest.approx(16.0)  # 1/16 EMC-analogue ladder


def test_power_monotone_in_clock():
    art_flops, art_bytes = 197e12 * 256 * 0.5, 819e9 * 256 * 0.1
    p = []
    for cs in (0.5, 0.75, 1.0):
        hw = HwModel(n_chips=256, clock_scale=cs)
        t = hw.roofline_terms(art_flops, art_bytes, 0)["step_time_s"]
        p.append(hw.power_w(art_flops, art_bytes, t))
    assert p[0] < p[1] < p[2]


def test_traffic_model_decode_dominated_by_weights_and_cache():
    arch = get_arch("glm4-9b")
    flags = BuildFlags()
    n_dev, dp, tp = 256, 16, 16
    got = analytic_hbm_bytes_per_device(arch, SHAPES["decode_32k"], flags,
                                        n_dev, dp, tp)
    w = arch.param_count() * 2 / tp
    cache = (128 * 32768 * 2 * arch.n_kv_heads * arch.d_head * 2 *
             arch.n_layers) / n_dev
    assert got == pytest.approx(w + cache, rel=0.35)


def test_traffic_model_train_scales_with_remat():
    arch = get_arch("tinyllama-1.1b")
    n_full = analytic_hbm_bytes_per_device(
        arch, SHAPES["train_4k"], BuildFlags(remat="full"), 256, 16, 16)
    n_none = analytic_hbm_bytes_per_device(
        arch, SHAPES["train_4k"], BuildFlags(remat="none"), 256, 16, 16)
    assert n_full > n_none


def test_traffic_model_moe_decode_touch_fraction():
    """long_500k (batch=1, top-1 of 128 experts) touches ~1/128 of expert
    weights; decode_32k (batch=128) touches most of them."""
    arch = get_arch("llama4-maverick-400b-a17b")
    flags = BuildFlags()
    b1 = analytic_hbm_bytes_per_device(arch, SHAPES["long_500k"], flags, 256, 16, 16)
    b128 = analytic_hbm_bytes_per_device(arch, SHAPES["decode_32k"], flags, 256, 16, 16)
    assert b1 < 0.2 * b128


def test_sliding_window_caps_decode_cache_traffic():
    g = get_arch("gemma3-27b")
    flags = BuildFlags()
    long = analytic_hbm_bytes_per_device(g, SHAPES["long_500k"], flags, 256, 16, 16)
    # hypothetical all-global variant: replace pattern with full attention
    import dataclasses
    from repro.configs.base import LayerSpec

    g_full = dataclasses.replace(g, pattern=(LayerSpec(mixer="attn"),),
                                 name="gemma-all-global")
    long_full = analytic_hbm_bytes_per_device(g_full, SHAPES["long_500k"],
                                              flags, 256, 16, 16)
    # weights dominate both totals; what the 5/6 windowed layers save is
    # *cache* traffic: n_local·(S - W)·2·hkv·dh·b per batch — check the delta
    n_local = sum(1 for sp in g.layer_specs() if sp.mixer == "attn_local")
    expect_delta = (n_local * (524288 - 1024) * 2 * g.n_kv_heads
                    * g.d_head * 2) / 256
    assert long < long_full
    assert abs((long_full - long) - expect_delta) < 0.4 * expect_delta

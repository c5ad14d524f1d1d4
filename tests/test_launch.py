"""Launcher plumbing that decides where work runs: the compile-cache
placement, the ``--chips`` default, and the roofline's chip-kind table."""
import os
import types

import jax
import pytest

from repro.configs import get_arch
from repro.launch import compile_cache
from repro.roofline import hw


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert config_updates == []


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert config_updates == [("jax_compilation_cache_dir", path)]
    assert compile_cache.enable_compile_cache() == path     # stable


@pytest.mark.parametrize("argv,want", [([], None), (["--chips", "4"], 4)])
def test_explore_chips_default_is_devices_present(argv, want):
    from repro.launch import explore

    args = explore.parse_args(argv)
    assert args.chips == (want or len(jax.devices()))


def test_chip_peaks_hold_v5e():
    peaks = hw.chip_peaks("TPU v5 lite")
    assert peaks["flops_bf16"] == hw.PEAK_FLOPS_BF16 == 197e12
    assert peaks["hbm_bw"] == hw.HBM_BW


@pytest.mark.parametrize("kind", ["TPU v5", "TPU v6 lite", "cpu"])
def test_chip_peaks_refuse_other_kinds(kind):
    with pytest.raises(ValueError, match="no roofline peaks"):
        hw.chip_peaks(kind)


def test_build_fn_refuses_tpu_kind_without_peaks(monkeypatch):
    from repro.core import JConfig
    from repro.launch import explore

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    args = explore.parse_args(["--chips", "1"])
    space = explore.generation_space(get_arch("llama2-7b"), 1)
    with pytest.raises(ValueError, match="TPU v99"):
        explore.make_build_fn(args, JConfig(space, n_chips=1))

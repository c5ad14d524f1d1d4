"""Compiles for a described TPU v5e, with no chip attached: the model kernels
of the main path at published widths, the jitted calls of the device GP
(``--gp jax``), and the refusal of the Pallas GP on a TPU backend.

The topology is described inside a module fixture and never at import, so
every pytest worker collects the same tests and only the worker that runs
this file loads the TPU compiler.  The backend here is still the CPU, so
the kernels' own ``_interpret()`` switch is steered to the TPU branch by
the ``tpu_branch`` fixture.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_branch(monkeypatch):
    from repro.kernels import gp_ops, ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(gp_ops, "_interpret", lambda: False)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(jitted, static, *args, **kw):
    """Retrace ``jitted``'s Python body (so the steered branch is taken,
    not a cached CPU trace) and compile it for the described chip."""
    fn = jax.jit(jitted.__wrapped__, static_argnames=static)
    return fn.lower(*args, **kw).compile()


def test_flash_attention_compiles_llama2_7b(one_chip, tpu_branch):
    from repro.kernels import ops

    arch = get_arch("llama2-7b")
    dh = arch.d_head
    q = _spec(one_chip, (1, 4096, arch.n_heads, dh), jnp.bfloat16)
    kv = _spec(one_chip, (1, 4096, arch.n_kv_heads, dh), jnp.bfloat16)
    c = _compile(ops.flash_attention, ("causal", "window", "block_q",
                                       "block_kv"), q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles_mamba2_780m(one_chip, tpu_branch):
    from repro.kernels import ops

    arch = get_arch("mamba2-780m")
    h, p, n = arch.n_ssm_heads, arch.ssm_head_dim, arch.ssm_state
    s = 2 * arch.ssm_chunk
    x = _spec(one_chip, (1, s, h, p), jnp.bfloat16)
    per_head = _spec(one_chip, (1, s, h))
    bc = _spec(one_chip, (1, s, n), jnp.bfloat16)
    c = _compile(ops.ssd_scan, ("chunk",), x, per_head, bc, bc, per_head,
                 chunk=arch.ssm_chunk)
    assert "tpu_custom_call" in c.as_text()


def test_topk_gating_compiles_deepseek_moe_16b(one_chip, tpu_branch):
    from repro.kernels import ops

    arch = get_arch("deepseek-moe-16b")
    logits = _spec(one_chip, (4096, arch.n_experts))
    c = _compile(ops.topk_gating, ("k", "block_t"), logits, arch.moe_top_k)
    assert "tpu_custom_call" in c.as_text()


# the jitted calls of JaxIncrementalGP, at float64 inside its x64 scope:
# capacity 256, 8 knob dims, a 4-row tell block, a 512-candidate pool
_CAP, _D, _B, _P = 256, 8, 4, 512


def _gp_calls():
    from repro.core.search import gp_jax

    f64, i32 = jnp.float64, jnp.int32
    return {
        "append": (gp_jax._append_jit, [
            ((_CAP, _D), f64), ((_CAP, _CAP), f64), ((_CAP, _CAP), f64),
            ((), i32), ((), i32), ((_B, _D), f64), ((), f64), ((), f64),
            ((), f64)]),
        "refactor": (gp_jax._refactor_jit, [
            ((_CAP, _D), f64), ((), i32), ((), f64), ((), f64), ((), f64)]),
        "predict": (gp_jax._predict_jit, [
            ((_CAP, _D), f64), ((_CAP, _CAP), f64), ((_CAP, 1), f64),
            ((), i32), ((_P, _D), f64), ((), f64), ((), f64)]),
        "ehvi": (gp_jax._ehvi_jit, [
            ((_CAP, _D), f64), ((_CAP, 2), f64), ((), i32), ((_P, _D), f64),
            ((16, 2), f64), ((2,), f64), ((2,), f64), ((2,), f64), ((), f64),
            ((), f64)]),
    }


@pytest.mark.parametrize("call", ["append", "refactor", "predict", "ehvi"])
def test_device_gp_compiles_float64(one_chip, call):
    with jax.enable_x64(True):
        fn, shapes = _gp_calls()[call]
        args = [_spec(one_chip, s, d) for s, d in shapes]
        c = fn.lower(*args).compile()
        assert "f64" in c.as_text()


def test_pallas_gp_refuses_tpu_backend(tpu_branch):
    from repro.core.search.gp_pallas import PallasIncrementalGP

    with pytest.raises(NotImplementedError, match="float64"):
        PallasIncrementalGP()


@pytest.mark.parametrize("kernel", ["append", "ehvi"])
def test_pallas_gp_kernels_compile_float32(one_chip, kernel):
    """The float32 starting point of a TPU device GP: with x64 off the two
    Pallas GP kernels lower through Mosaic."""
    from repro.kernels import gp_ops

    cap, blk = 512, 256
    if kernel == "append":
        args = [_spec(one_chip, (cap, _D)), _spec(one_chip, (cap, cap)),
                _spec(one_chip, (cap, cap)), _spec(one_chip, (), jnp.int32),
                _spec(one_chip, (), jnp.int32), _spec(one_chip, (8, _D)),
                None, np.float32(1e-3)]
        fn = jax.jit(gp_ops.gp_append.__wrapped__,
                     static_argnames=("ls2", "signal", "block", "interpret"))
    else:
        args = [_spec(one_chip, (cap, _D)), _spec(one_chip, (cap, 2)),
                _spec(one_chip, (), jnp.int32), _spec(one_chip, (_P, _D)),
                _spec(one_chip, (3, 17)), _spec(one_chip, (2, 2)), None]
        fn = jax.jit(gp_ops.gp_fused_ehvi.__wrapped__,
                     static_argnames=("ls2", "signal", "block", "pool_block",
                                      "interpret"))
    kw = dict(ls2=0.09, signal=1.0, block=blk, interpret=False)
    if kernel == "ehvi":
        kw["pool_block"] = 256
    c = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in c.as_text()

"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax


def _make(shape, axes):
    """A mesh on the first prod(shape) devices, every axis Auto-sharded."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; ×2 pods when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh_dp_tp(dp: int, tp: int, pods: int = 1):
    """Explicit factorisation (the dp_degree design-space knob)."""
    if pods > 1:
        return _make((pods, dp, tp), ("pod", "data", "model"))
    return _make((dp, tp), ("data", "model"))


def make_host_mesh():
    """Whatever devices this process actually has — smoke tests/examples."""
    n = len(jax.devices())
    return _make((n,), ("data",)) if n > 1 else _make((1,), ("data",))

"""jit'd public wrappers around the Pallas kernels.

Off-TPU (this container) the kernels execute in interpret mode; on a real TPU
backend they lower through Mosaic.  Wrappers handle layout (B,S,H,D) ↔ kernel
layout, sequence padding to block multiples, and VMEM-budget assertions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import topk_gating as _tg

VMEM_BUDGET_BYTES = 16 * 1024 * 1024  # v5e VMEM per core


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention_vmem_bytes(block_q, block_kv, d):
    return 4 * (2 * block_q * d + 2 * block_kv * d + block_q * block_kv + 2 * block_q)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_kv"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=256, block_kv=256):
    """q: (B, S, H, d); k, v: (B, S, Hkv, d) -> (B, S, H, d)."""
    assert flash_attention_vmem_bytes(block_q, block_kv, q.shape[-1]) < VMEM_BUDGET_BYTES
    b, s, h, d = q.shape
    hkv = k.shape[2]
    block_q = min(block_q, max(16, 1 << (s - 1).bit_length()))
    block_kv = min(block_kv, max(16, 1 << (s - 1).bit_length()))
    pad = (-s) % max(block_q, block_kv)
    qt = q.swapaxes(1, 2).reshape(b * h, s, d)
    kt = k.swapaxes(1, 2).reshape(b * hkv, s, d)
    vt = v.swapaxes(1, 2).reshape(b * hkv, s, d)
    if pad:
        qt = jnp.pad(qt, ((0, 0), (0, pad), (0, 0)))
        kt = jnp.pad(kt, ((0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad), (0, 0)))
    o = _fa.flash_attention_fwd(qt, kt, vt, causal=causal, window=window,
                                block_q=block_q, block_kv=block_kv,
                                interpret=_interpret())
    o = o[:, :s].reshape(b, h, s, d).swapaxes(1, 2)
    return o


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, a_log, b, c, dt, *, chunk=256):
    """Chunked SSD; pads S to a chunk multiple (dt=0 ⇒ pads are inert).

    x: (B,S,H,P); a_log, dt: (B,S,H); b, c: (B,S,N).  Heads move ahead of
    the sequence for the kernel, so its blocks are (chunk, P) and
    (1, chunk) tiles instead of single-head slivers of (S, H, ...)."""
    s = x.shape[1]
    chunk = min(chunk, max(8, 1 << (s - 1).bit_length()))
    pad = (-s) % chunk
    if pad:
        padf = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, a_log, b, c, dt = map(padf, (x, a_log, b, c, dt))
    heads_major = lambda t: t.swapaxes(1, 2)[:, :, None, :]     # (B,H,1,S)
    y, state = _ssd.ssd_scan_fwd(x.swapaxes(1, 2), heads_major(a_log), b, c,
                                 heads_major(dt), chunk=chunk,
                                 interpret=_interpret())
    return y.swapaxes(1, 2)[:, :s], state


@functools.partial(jax.jit, static_argnames=("k", "block_t"))
def topk_gating(logits, k, *, block_t=1024):
    return _tg.topk_gating_fwd(logits, k, block_t=block_t, interpret=_interpret())

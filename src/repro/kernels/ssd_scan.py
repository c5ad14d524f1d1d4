"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

grid = (B, H, n_chunks); the chunk axis is the minor (sequential) grid
dimension, so the (P × N) per-head SSM state lives in VMEM scratch and is
carried across chunk iterations — the inter-chunk recurrence costs no HBM
round-trips.  Each program computes one chunk of one head:

  intra-chunk:  Y += tril((C·Bᵀ) ∘ exp(cum_i − cum_j) ∘ dt_j) @ X   (MXU matmuls)
  state-in:     Y += (C @ stateᵀ) ∘ exp(cum)
  state-out:    state = state·exp(total) + (X ∘ dt·exp(total−cum))ᵀ @ B

VMEM per program ≈ (Q·P + 2·Q·N + Q·Q + P·N) × 4 B; with Q=256, P=64, N=128
that is ~0.6 MiB — far under budget, so chunks can be widened via the JConfig
``ssd_chunk`` knob.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, dt_ref, y_ref, state_ref,
                state_scr, *, n_chunks, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)            # (Q, P)
    a = a_ref[0, 0].astype(jnp.float32)            # (1, Q) row
    bb = b_ref[0].astype(jnp.float32)              # (Q, N)
    cc = c_ref[0].astype(jnp.float32)              # (Q, N)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (1, Q) row

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = cols <= rows
    diag = cols == rows
    # chunk-local inclusive cumsum as a column (cum_i), then moved to a row
    # (cum_j) and dt to a column through the diagonal: masked lane/sublane
    # reductions, which Mosaic lowers, in place of cumsum and transposes
    cum = jnp.sum(jnp.where(causal, a, 0.0), axis=1, keepdims=True)   # (Q, 1)
    cum_row = jnp.sum(jnp.where(diag, cum, 0.0), axis=0, keepdims=True)
    dt_col = jnp.sum(jnp.where(diag, dt, 0.0), axis=1, keepdims=True)

    cb = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)     # (Q, Q)
    decay = jnp.exp(cum - cum_row)
    sm = jnp.where(causal, cb * decay * dt, 0.0)
    y = jax.lax.dot_general(sm, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)      # (Q, P)

    state = state_scr[...]                         # (P, N)
    y += jax.lax.dot_general(cc, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * jnp.exp(cum)

    total = jnp.sum(a, axis=1, keepdims=True)      # (1, 1)
    rem = jnp.exp(total - cum)                     # (Q, 1)
    dx = x * (dt_col * rem)                        # (Q, P)
    new_state = state * jnp.exp(total) + jax.lax.dot_general(
        dx, bb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_scr[...] = new_state

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_ref[0, 0] = new_state


def ssd_scan_fwd(x, a_log, b, c, dt, *, chunk=256, interpret=False):
    """Kernel layout, heads major so every block tiles:
    x: (B,H,S,P); a_log, dt: (B,H,1,S); b, c: (B,S,N).  S % chunk == 0,
    and on a TPU chunk is a multiple of 128 or equals S.

    Returns (y (B,H,S,P), state (B,H,P,N) fp32).
    """
    bsz, h, s, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, n_chunks=nc, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, a_log, b, c, dt)

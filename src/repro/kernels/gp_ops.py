"""Pallas kernels for the incremental-GP ask path (``gp_mode="pallas"``).

Two hot loops dominate BayesOpt/PAL ask latency once observation counts pass
~10⁴, and both spend their time re-touching the *full* pow2-padded device
capacity even though only the first n rows are active:

* **Masked rank-append Cholesky update** (``gp_append``) — the jnp append
  (``gp_jax._append_jit``) runs ``L⁻¹ @ K₁₂`` and ``wᵀ @ L⁻¹`` as
  full-capacity GEMMs and then re-masks the whole (cap, cap) factor pair,
  O(capacity²) per tell.  Here both triangular applications are tiled Pallas
  kernels over a (cap/blk)² grid whose inactive and upper-triangular tiles
  are skipped with ``pl.when`` — the dynamic active count ``n`` rides in as
  a prefetched scalar — so a tell does O(n·block) work and the trailing
  (SYRK-sized) B×B Schur block is the only dense factorisation left.  The
  cross-kernel block K₁₂ is computed tile-by-tile *inside* the kernel from
  the X rows, never materialised at (cap, B) in HBM.
* **Fused predict+EHVI pool sweep** (``gp_fused_ehvi``) — one kernel tiles
  over pool candidates (major) and training rows (minor), computing the
  cross-covariance tile, accumulating posterior means in VMEM scratch, and
  on the last training tile denormalising and sweeping the precomputed EHVI
  staircase — the (P, n) cross-kernel matrix never exists in HBM, which is
  what blows the jnp fused call up at large active sets.

Both kernels follow the in-repo ``flash_attention.py``/``ssd_scan.py``
idiom: pltpu block specs, VMEM scratch carried across the minor grid
dimension, and an interpret-mode fallback (``jax.default_backend() !=
"tpu"``) so CPU CI exercises the exact kernel code.  The GP state is
float64 (scoped ``jax.enable_x64(True)`` in the caller), which interpret
mode executes exactly; the parity suite pins that path against the numpy
reference.  There is no TPU path yet: Mosaic has no float64, and at
float32 inside the x64 scope it rejects the i64 index maps, so
``PallasIncrementalGP`` refuses to start on a TPU.  Both kernels do lower
to ``tpu_custom_call`` at float32 with x64 off — the starting point for a
float32 device GP.

Lengthscales: ``ls2`` is a *static* scalar (isotropic ls², retraced only on
a hyper refresh).  ARD per-dimension lengthscales are handled by the caller
pre-scaling X by the reciprocal vector and passing ``ls2=1.0`` — the same
two forms the numpy/jnp ``_kern`` uses, keeping cross-mode parity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.linalg import solve_triangular

VMEM_BUDGET_BYTES = 16 * 1024 * 1024  # v5e VMEM per core


def gp_ops_available() -> bool:
    """Import gate for callers that must degrade gracefully (ci_smoke)."""
    return True


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tile_kern(a, b, ls2, signal):
    """RBF tile via ‖a‖² + ‖b‖² − 2a·b — the same GEMM form (and the same
    ls²-divide) as ``gp_jax._kern``, so per-tile values match the jnp path
    elementwise; only the cross-tile summation order differs."""
    d2 = (jnp.sum(a * a, axis=1)[:, None]
          + jnp.sum(b * b, axis=1)[None, :]
          - 2.0 * jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                      preferred_element_type=a.dtype))
    d2 = jnp.maximum(d2, 0.0)
    return signal * jnp.exp(-0.5 * d2 / ls2)


# ---------------------------------------------------------------------------
# kernel 1a: w = L⁻¹ K₁₂ over active rows only (K₁₂ built in-tile)
# ---------------------------------------------------------------------------


def _w_kernel(nm_ref, lib_ref, xs_ref, xq_ref, w_ref, acc, *, blk, ls2,
              signal):
    i = pl.program_id(0)            # output row tile
    j = pl.program_id(1)            # contraction tile (minor, sequential)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    n = nm_ref[0]
    m = nm_ref[1]
    # L⁻¹ is lower-triangular with rows/cols ≥ n exactly zero (the buffer
    # invariant), so upper tiles and fully-inactive tiles contribute nothing
    # — skip their FLOPs entirely.
    @pl.when((j <= i) & (j * blk < n) & (i * blk < n))
    def _compute():
        bq = xq_ref.shape[0]
        k12 = _tile_kern(xs_ref[...], xq_ref[...], ls2, signal)  # (blk, bq)
        grow = j * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, bq), 0)
        bcol = jax.lax.broadcasted_iota(jnp.int32, (blk, bq), 1)
        k12 = k12 * ((grow < n) & (bcol < m)).astype(k12.dtype)
        acc[...] += jax.lax.dot_general(
            lib_ref[...], k12, (((1,), (0,)), ((), ())),
            preferred_element_type=acc.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        w_ref[...] = acc[...]


# ---------------------------------------------------------------------------
# kernel 1b: g = wᵀ L⁻¹ over active rows only (the new L⁻¹ row slab)
# ---------------------------------------------------------------------------


def _g_kernel(nm_ref, w_ref, lib_ref, g_ref, acc, *, blk):
    j = pl.program_id(0)            # output column tile
    i = pl.program_id(1)            # contraction row tile (minor, sequential)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    n = nm_ref[0]
    # lower-triangular: lib[i, j] is zero for i < j; w rows ≥ n are zero
    @pl.when((i >= j) & (i * blk < n) & (j * blk < n))
    def _compute():
        acc[...] += jax.lax.dot_general(
            w_ref[...], lib_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=acc.dtype)

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        g_ref[...] = acc[...]


def gp_append_vmem_bytes(blk, bq, d, itemsize=8):
    # lib tile + x tile + new block + k12 tile + two (blk, bq)/(bq, blk) accs
    return itemsize * (blk * blk + blk * d + bq * d + 3 * blk * bq)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("ls2", "signal", "block", "interpret"))
def gp_append(xb, lb, lib, n, m, xnew, ils, noise, *, ls2, signal, block,
              interpret):
    """Rank-append an m-row block (padded to xnew's static height B) with
    the triangular work tiled over active rows only.

    Mirrors ``gp_jax._append_jit`` (same masks, same identity-diagonal
    padding trick, same finiteness flag for the degenerate fallback) but
    runs the two O(n·B·n) triangular applications as Pallas kernels and
    writes only the B new rows — no full-capacity GEMM or re-mask anywhere.
    ``ils`` is the reciprocal ARD lengthscale row (or None when isotropic;
    then ``ls2`` carries ls²).
    """
    cap = xb.shape[0]
    B = xnew.shape[0]
    dt = xb.dtype
    assert cap % block == 0
    assert gp_append_vmem_bytes(block, B, xb.shape[1]) < VMEM_BUDGET_BYTES
    zero = jnp.int32(0)
    bvalid = (jnp.arange(B, dtype=jnp.int32) < m).astype(dt)
    xnew = xnew * bvalid[:, None]
    xb = jax.lax.dynamic_update_slice(xb, xnew, (n, zero))
    if ils is None:
        xs, xqs = xb, xnew
    else:
        xs, xqs = xb * ils[None, :], xnew * ils[None, :]
    nm = jnp.stack([n, m]).astype(jnp.int32)
    ni = cap // block

    w = pl.pallas_call(
        functools.partial(_w_kernel, blk=block, ls2=ls2, signal=signal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(ni, ni),
            in_specs=[
                pl.BlockSpec((block, block), lambda i, j, nm: (i, j)),
                pl.BlockSpec((block, xs.shape[1]), lambda i, j, nm: (j, 0)),
                pl.BlockSpec((B, xs.shape[1]), lambda i, j, nm: (0, 0)),
            ],
            out_specs=pl.BlockSpec((block, B), lambda i, j, nm: (i, 0)),
            scratch_shapes=[pltpu.VMEM((block, B), dt)],
        ),
        out_shape=jax.ShapeDtypeStruct((cap, B), dt),
        interpret=interpret,
    )(nm, lib, xs, xqs)

    # trailing B×B Schur block: dense, tiny, and the only LAPACK-shaped work
    k22 = _tile_kern(xqs, xqs, ls2, signal) + noise * jnp.eye(B, dtype=dt)
    k22 = (k22 * bvalid[:, None] * bvalid[None, :]
           + jnp.diag(1.0 - bvalid))          # identity diag on padding rows
    l22 = jnp.linalg.cholesky(k22 - w.T @ w)
    ok = jnp.all(jnp.isfinite(jnp.diagonal(l22) * bvalid + (1.0 - bvalid)))
    li22 = solve_triangular(l22, jnp.eye(B, dtype=dt), lower=True)

    g = pl.pallas_call(
        functools.partial(_g_kernel, blk=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(ni, ni),
            in_specs=[
                pl.BlockSpec((block, B), lambda j, i, nm: (i, 0)),
                pl.BlockSpec((block, block), lambda j, i, nm: (i, j)),
            ],
            out_specs=pl.BlockSpec((B, block), lambda j, i, nm: (0, j)),
            scratch_shapes=[pltpu.VMEM((B, block), dt)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, cap), dt),
        interpret=interpret,
    )(nm, w, lib)

    # write the B new rows only: w has rows ≥ n and padding columns already
    # zero (kernel masks), so the slab writes preserve the zero invariant
    # without touching — let alone re-masking — the old capacity
    bmask = bvalid[:, None] * bvalid[None, :]
    lb = jax.lax.dynamic_update_slice(lb, w.T, (n, zero))
    lb = jax.lax.dynamic_update_slice(lb, l22 * bmask, (n, n))
    lib = jax.lax.dynamic_update_slice(lib, -((li22 * bmask) @ g), (n, zero))
    lib = jax.lax.dynamic_update_slice(lib, li22 * bmask, (n, n))
    return xb, lb, lib, ok


# ---------------------------------------------------------------------------
# kernel 2: fused predict + EHVI staircase pool sweep
# ---------------------------------------------------------------------------


def _ehvi_kernel(n_ref, xq_ref, xs_ref, alpha_ref, stair_ref, ymd_ref, o_ref,
                 acc, *, blk, ls2, signal):
    p = pl.program_id(0)            # pool tile
    j = pl.program_id(1)            # training-row tile (minor, sequential)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    n = n_ref[0]

    @pl.when(j * blk < n)
    def _compute():
        bp = xq_ref.shape[0]
        ks = _tile_kern(xq_ref[...], xs_ref[...], ls2, signal)   # (bp, blk)
        gcol = j * blk + jax.lax.broadcasted_iota(jnp.int32, (bp, blk), 1)
        ks = ks * (gcol < n).astype(ks.dtype)
        acc[...] += jax.lax.dot_general(
            ks, alpha_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=acc.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # denormalise the accumulated posterior means, then sweep the
        # precomputed staircase (lows/ups/levels; zero-width pad segments)
        mu = acc[...] * ymd_ref[1:2, :] + ymd_ref[0:1, :]        # (bp, 2)
        lows = stair_ref[0:1, :]                                 # (1, S)
        ups = stair_ref[1:2, :]
        levels = stair_ref[2:3, :]
        width = jnp.clip(ups - jnp.maximum(lows, mu[:, 0:1]), 0.0, None)
        height = jnp.clip(levels - mu[:, 1:2], 0.0, None)
        o_ref[...] = jnp.sum(width * height, axis=1, keepdims=True)


def gp_ehvi_vmem_bytes(bp, blk, d, s, itemsize=8):
    return itemsize * (bp * d + blk * d + 2 * blk + 3 * s
                       + 2 * bp * blk + bp * s + 4 * bp)


@functools.partial(jax.jit, static_argnames=("ls2", "signal", "block",
                                             "pool_block", "interpret"))
def gp_fused_ehvi(xb, alpha, n, xq, stair, ymd, ils, *, ls2, signal, block,
                  pool_block, interpret):
    """EHVI scores for the whole pool in one kernel: cross-covariance tiles,
    posterior-mean accumulation, denormalisation, and the staircase sweep
    all live in VMEM — the (P, n) kernel matrix is never materialised.

    ``stair`` is (3, S): lows/ups/levels rows of the sorted-front staircase,
    padded with zero-width segments; ``ymd`` is (2, 2): the fit's per-target
    mean/std rows.  Returns (P,) hypervolume improvements.
    """
    cap = xb.shape[0]
    P = xq.shape[0]
    dt = xb.dtype
    assert cap % block == 0 and P % pool_block == 0
    assert alpha.shape[1] == 2, "EHVI sweep is 2-objective"
    assert gp_ehvi_vmem_bytes(pool_block, block, xb.shape[1],
                              stair.shape[1]) < VMEM_BUDGET_BYTES
    if ils is None:
        xs, xqs = xb, xq
    else:
        xs, xqs = xb * ils[None, :], xq * ils[None, :]
    nm = jnp.stack([n]).astype(jnp.int32)

    out = pl.pallas_call(
        functools.partial(_ehvi_kernel, blk=block, ls2=ls2, signal=signal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(P // pool_block, cap // block),
            in_specs=[
                pl.BlockSpec((pool_block, xq.shape[1]),
                             lambda p, j, nm: (p, 0)),
                pl.BlockSpec((block, xs.shape[1]), lambda p, j, nm: (j, 0)),
                pl.BlockSpec((block, 2), lambda p, j, nm: (j, 0)),
                pl.BlockSpec(stair.shape, lambda p, j, nm: (0, 0)),
                pl.BlockSpec((2, 2), lambda p, j, nm: (0, 0)),
            ],
            out_specs=pl.BlockSpec((pool_block, 1), lambda p, j, nm: (p, 0)),
            scratch_shapes=[pltpu.VMEM((pool_block, 2), dt)],
        ),
        out_shape=jax.ShapeDtypeStruct((P, 1), dt),
        interpret=interpret,
    )(nm, xqs, xs, alpha, stair, ymd)
    return out[:, 0]

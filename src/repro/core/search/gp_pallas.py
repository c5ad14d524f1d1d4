"""Pallas-kernel incremental GP — the ``gp_mode="pallas"`` ask path.

``PallasIncrementalGP`` subclasses ``JaxIncrementalGP`` and replaces
exactly the two device calls that dominate past n≈10⁴ with the tiled
kernels from ``repro.kernels.gp_ops``:

* ``_append_active`` → ``gp_ops.gp_append`` — the rank-append Cholesky's
  triangular applications run only over active-row tiles (the dynamic
  count rides in as a prefetched scalar), O(n·block) per tell instead of
  the full-capacity O(capacity²) GEMMs of ``_append_jit``.
* ``score_ehvi`` → ``gp_ops.gp_fused_ehvi`` — cross-covariance, posterior
  mean, denormalisation, and the EHVI staircase sweep stay in VMEM per
  (pool, train) tile; the (P, n) kernel matrix never touches HBM.

Everything else — buffers, archive, inducing thinning/re-thinning, fits,
predicts, the degenerate-append refactor fallback — is inherited, so the
pallas mode keeps the parent's numpy-parity behaviour for free and the
parity suite only has to pin the two overridden calls.

On non-TPU backends the kernels run in Pallas interpret mode (float64
exact), so CPU CI exercises the real kernel bodies; block sizes are
clamped to the current buffer capacity, keeping small-n traces cheap.
On a TPU the kernels would lower through Mosaic, which has no float64, so
construction refuses there instead of failing mid-sweep; a float32 device
GP is what would lift that.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.search.gp_jax import JaxIncrementalGP, _fetch, _pow2_small
from repro.core.tracing import span
from repro.kernels import gp_ops


def pallas_available() -> bool:
    """Import gate for callers that must degrade gracefully (ci_smoke)."""
    return gp_ops.gp_ops_available()


class PallasIncrementalGP(JaxIncrementalGP):
    """Drop-in for ``JaxIncrementalGP`` with Pallas tell/ask hot loops.

    ``block`` tiles the training-row axis of both kernels; ``pool_block``
    tiles the candidate axis of the fused EHVI sweep.  Both must be powers
    of two (they are clamped down to the live capacity / pool size, which
    are pow2 by construction, so divisibility always holds).  The default
    256 is a multiple of the 128-lane MXU tile and keeps grid-step count —
    the dominant overhead both for the sequential minor dimension on TPU
    and for interpret mode on CPU — 4× lower than 128 at the same VMEM
    budget (the kernels' scratch asserts stay comfortably under 16 MB).
    """

    def __init__(self, lengthscale=0.3, noise: float = 1e-3,
                 signal: float = 1.0, inducing_threshold=None,
                 inducing_overflow: float = 1.25, block: int = 256,
                 pool_block: int = 256):
        if not gp_ops._interpret():
            raise NotImplementedError(
                "gp_mode='pallas' keeps float64 GP state, and Mosaic (the "
                f"{jax.default_backend()} Pallas compiler) has no float64; "
                "use gp_mode='jax' on this backend")
        super().__init__(lengthscale=lengthscale, noise=noise, signal=signal,
                         inducing_threshold=inducing_threshold,
                         inducing_overflow=inducing_overflow)
        assert block > 0 and (block & (block - 1)) == 0, "block must be pow2"
        assert pool_block > 0 and (pool_block & (pool_block - 1)) == 0
        self.block = int(block)
        self.pool_block = int(pool_block)
        self.n_pallas_appends = 0
        self.n_pallas_scores = 0

    # -- kernel lengthscale plumbing ------------------------------------------
    def _ls_args(self):
        """(static ls², reciprocal-ARD row or None) — the same two kernel
        forms as ``gp_jax._kern``: scalar ls divides distances by ls²
        (bit-for-bit historical path); an ARD vector pre-scales inputs."""
        if np.ndim(self.ls):
            with jax.enable_x64(True):
                ils = jnp.asarray(1.0 / np.asarray(self.ls, float))
            return 1.0, ils
        return float(self.ls) ** 2, None

    # -- overridden hot loops -------------------------------------------------
    def _append_active(self, xa: np.ndarray, idx: np.ndarray) -> None:
        m, d = xa.shape
        B = _pow2_small(m)
        self._ensure_cap(self._n + B, d)
        xpad = np.zeros((B, d))
        xpad[:m] = xa
        ls2, ils = self._ls_args()
        with jax.enable_x64(True):
            self._xb, self._lb, self._lib, ok = gp_ops.gp_append(
                self._xb, self._lb, self._lib,
                np.int32(self._n), np.int32(m), jnp.asarray(xpad), ils,
                self.noise, ls2=ls2, signal=self.signal,
                block=min(self.block, self._cap),
                interpret=gp_ops._interpret())
        self._active_idx[self._n:self._n + m] = idx
        self._n += m
        self._version += 1
        self.n_appends += 1
        self.n_pallas_appends += 1
        if not bool(_fetch(ok)):
            # degenerate block: same masked-refactor fallback as numpy/jnp
            self._refactor()

    def score_ehvi(self, xs: np.ndarray, front_y: np.ndarray,
                   ref: np.ndarray) -> np.ndarray:
        xs = np.atleast_2d(np.asarray(xs, float))
        if len(xs) == 0:
            return np.zeros(0)
        ref = np.asarray(ref, float)
        front = np.asarray(front_y, float)
        front = front[np.all(front < ref, axis=1)]
        if len(front) == 0:
            # no valid front: plain rectangle-to-ref product (inherited
            # jnp mean path — cold branch, first iterations only)
            mu = self.predict_mean_multi(xs)
            return (np.clip(ref[0] - mu[:, 0], 0.0, None)
                    * np.clip(ref[1] - mu[:, 1], 0.0, None))
        from repro.core.results import nondominated_mask

        front = front[nondominated_mask(front)]
        front = front[np.argsort(front[:, 0])]
        F = _pow2_small(len(front))
        pad = np.repeat([[ref[0], front[-1, 1]]], F - len(front), axis=0)
        fpad = np.vstack([front, pad])
        # precompute the (3, S) staircase host-side: lows/ups/levels rows,
        # sentinel-padded segments are zero-width so the sum is exact
        x, y = fpad[:, 0], fpad[:, 1]
        stair = np.stack([
            np.concatenate([[-np.inf], x]),
            np.concatenate([x, ref[0:1]]),
            np.concatenate([ref[1:2], y]),
        ])
        ymd = np.stack([np.asarray(self._ym_m, float),
                        np.asarray(self._ys_m, float)])
        with span("jx.gp.score_ehvi", cap=self._cap, rows=len(xs)):
            xq, M = self._pad_pool(xs)
            ls2, ils = self._ls_args()
            with jax.enable_x64(True):
                s = gp_ops.gp_fused_ehvi(
                    self._xb, self._alpha_m, np.int32(self._n), xq,
                    jnp.asarray(stair), jnp.asarray(ymd), ils,
                    ls2=ls2, signal=self.signal,
                    block=min(self.block, self._cap),
                    pool_block=min(self.pool_block, xq.shape[0]),
                    interpret=gp_ops._interpret())
            self.n_pallas_scores += 1
            return _fetch(s)[:M]

    def stats(self) -> dict:
        out = super().stats()
        out["pallas_appends"] = self.n_pallas_appends
        out["pallas_scores"] = self.n_pallas_scores
        return out

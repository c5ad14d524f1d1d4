"""JAX-backed incremental GP — the device fast path behind ``gp_mode="jax"``.

``JaxIncrementalGP`` mirrors the numpy ``IncrementalGP`` contract
(observe / fit_x / fit_y / predict / the ``_multi`` family) but keeps the
kernel state on the accelerator as fixed-capacity, zero-padded device
buffers and runs every hot step as one jitted call:

* **Rank-append Cholesky on device** — ``observe`` pads the new block to a
  power-of-two width and calls a single donated jit (``_append_jit``) that
  writes X, extends L with ``[[L, 0], [wᵀ, chol(K₂₂ − wᵀw)]]`` and L⁻¹ with
  the matching block inverse.  Buffers double amortizedly exactly like the
  numpy layout, so jit retraces happen per *capacity*, not per call.  The
  padding rows get an identity diagonal inside the jit (the Cholesky of a
  block-diag ``[[K, 0], [0, I]]`` is ``[[L, 0], [0, I]]``) and are re-masked
  to zero afterwards, keeping the invariant every other kernel GEMM relies
  on: rows/cols at index ≥ n are exactly zero.
* **Fused pool scoring** — ``predict_multi`` / ``predict_mean_multi`` /
  ``score_ehvi`` each run kernel GEMM + solve (+ the EHVI staircase sweep)
  over the whole candidate pool in one device call; pools are row-padded to
  powers of two so retraces stay bounded.
* **Pool posterior reuse** — the posterior variance depends on the factor
  and the pool, not on the targets.  ``predict`` / ``predict_multi`` keep
  the padded device pool and the host sd of their last full call; a later
  call on equal rows (``np.array_equal``) against the same factor and
  hyperparameters runs only the mean, so ParEGO's picks over one pool pay
  for one pool copy and one variance product per ask.  Every reassignment
  of the factor bumps ``_version``, which drops the reuse.
* **Staged picks** — ``stage`` fits a block of targets, one column each,
  and scores them all over a pool in one fit and one predict program.  A
  later ``fit_y`` on targets equal to a staged column, and the ``predict``
  on equal rows that follows it, take that column's posterior with no
  device call; the block comes to the host in one fetch.  So ParEGO's
  picks of an ask, each with its own scalarisation, cost one target copy,
  two dispatches and one round trip between them.
* **Inducing points (subset-of-data)** — every observation lands in a
  host-side archive, but past ``inducing_threshold`` active points the
  factor is periodically *thinned* back to an evenly-strided subset of the
  archive (overflow factor 1.25 amortizes the O(m³) refactor over ~m/4
  appends), so tell stays O(m²) and ask latency flat into the 10⁴–10⁶
  regime.  Below the threshold the active set is the full archive and the
  posterior matches the numpy path to float64 round-off.
* **float64 without global flags** — every device call runs inside
  ``jax.enable_x64(True)``, a thread-local scope, so GP parity
  with the float64 numpy reference does not require flipping the process-
  wide ``jax_enable_x64`` switch under the rest of the suite (kernel and
  model code elsewhere still sees default float32).

``jnp.linalg.cholesky`` signals a non-PD input with NaNs instead of the
LinAlgError the numpy path catches, so the append jit also returns a
finiteness flag for the new diagonal block; a degenerate append falls back
to one masked full-capacity refactor (``_refactor_jit``), same as numpy.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from repro.core.tracing import span


def jax_available() -> bool:
    """Import gate for callers that must degrade gracefully (ci_smoke)."""
    return True


def _pow2(n: int) -> int:
    p = 16
    while p < n:
        p *= 2
    return p


def _pow2_small(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _fetch(x) -> np.ndarray:
    """A device result copied to the host: the host waits on the chip."""
    with span("jx.gp.fetch", bytes=int(x.nbytes)):
        return np.asarray(x)


# ---------------------------------------------------------------------------
# jitted kernels — module-level so every JaxIncrementalGP instance shares the
# trace cache (shapes, not instances, key the cache)
# ---------------------------------------------------------------------------


def _kern(a, b, ls, signal):
    """RBF via ‖a‖² + ‖b‖² − 2a·b — the same GEMM form as the numpy path,
    so the two modes agree to float64 round-off.

    ``ls`` may be a scalar (isotropic, the historical path — kept bit-for-
    bit: divide the distances by ls²) or a per-dimension (d,) vector (ARD):
    inputs are pre-scaled by the reciprocal lengthscales and the distances
    used raw.  The numpy reference and the pallas kernels use the exact
    same two forms, so cross-mode parity survives the ARD extension."""
    ls = jnp.asarray(ls)
    if ls.ndim:
        a = a * (1.0 / ls)
        b = b * (1.0 / ls)
        den = 1.0
    else:
        den = ls * ls
    d2 = (jnp.sum(a * a, axis=1)[:, None]
          + jnp.sum(b * b, axis=1)[None, :] - 2.0 * (a @ b.T))
    d2 = jnp.maximum(d2, 0.0)
    return signal * jnp.exp(-0.5 * d2 / den)


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _append_jit(xb, lb, lib, n, m, xnew, ls, noise, signal):
    """Rank-append an m-row block (padded to xnew's static height B).

    ``n``/``m`` are traced int32 scalars; indices for dynamic_update_slice
    stay int32 throughout (x64 mode would otherwise mix int dtypes).
    Returns the donated buffers plus a finite-diagonal flag — NaN means the
    block was not PD (numpy raises LinAlgError here) and the caller must
    refactor.
    """
    cap = xb.shape[0]
    B = xnew.shape[0]
    zero = jnp.int32(0)
    n2 = n + m
    rows = jnp.arange(cap, dtype=jnp.int32)
    mask_old = (rows < n).astype(xb.dtype)             # pre-append valid rows
    mask_new = (rows < n2).astype(xb.dtype)
    bvalid = (jnp.arange(B, dtype=jnp.int32) < m).astype(xb.dtype)

    xb = jax.lax.dynamic_update_slice(xb, xnew * bvalid[:, None], (n, zero))
    # kernel strips against the *valid* rows only (zero-padding ⇒ mask once)
    k12 = _kern(xb, xnew, ls, signal) * mask_old[:, None] * bvalid[None, :]
    k22 = (_kern(xnew, xnew, ls, signal) + noise * jnp.eye(B, dtype=xb.dtype))
    # padding rows of the block get an identity diagonal so chol is exact
    k22 = (k22 * bvalid[:, None] * bvalid[None, :]
           + jnp.diag(1.0 - bvalid))
    w = lib @ k12                                      # (cap, B); L⁻¹K₁₂
    l22 = jnp.linalg.cholesky(k22 - w.T @ w)
    ok = jnp.all(jnp.isfinite(jnp.diagonal(l22) * bvalid + (1.0 - bvalid)))
    li22 = solve_triangular(l22, jnp.eye(B, dtype=xb.dtype), lower=True)
    lb = jax.lax.dynamic_update_slice(lb, w.T, (n, zero))
    lb = jax.lax.dynamic_update_slice(
        lb, l22, (n, n))
    lib = jax.lax.dynamic_update_slice(lib, -li22 @ (w.T @ lib), (n, zero))
    lib = jax.lax.dynamic_update_slice(lib, li22, (n, n))
    # restore the zero invariant outside the new valid n2×n2 block (the
    # identity rows of padded appends must not leak into later GEMMs)
    lb = lb * mask_new[:, None] * mask_new[None, :]
    lib = lib * mask_new[:, None] * mask_new[None, :]
    return xb, lb, lib, ok


@jax.jit
def _refactor_jit(xb, n, ls, noise, signal):
    """Masked full-capacity refactor: chol of [[K, 0], [0, I]] then re-zero.

    O(cap³) but called only on degenerate appends, thinning, and
    lengthscale refreshes — all amortized."""
    cap = xb.shape[0]
    rows = jnp.arange(cap, dtype=jnp.int32)
    mask = (rows < n).astype(xb.dtype)
    k = _kern(xb, xb, ls, signal) * mask[:, None] * mask[None, :]
    k = k + noise * jnp.eye(cap, dtype=xb.dtype) * mask \
        + jnp.diag(1.0 - mask)
    lb = jnp.linalg.cholesky(k)
    lib = solve_triangular(lb, jnp.eye(cap, dtype=xb.dtype), lower=True)
    lb = lb * mask[:, None] * mask[None, :]
    lib = lib * mask[:, None] * mask[None, :]
    return lb, lib


@jax.jit
def _fit_y_jit(lib, yn):
    """alpha = L⁻ᵀ L⁻¹ y over the full (zero-padded) capacity."""
    return lib.T @ (lib @ yn)


@partial(jax.jit, donate_argnums=(0,))
def _rethin_jit(xb, xrows, m):
    """In-place active-row rebuild for a re-thin at unchanged capacity.

    ``xrows`` is the (threshold, d) host block (rows ≥ m zero-masked here);
    the donated buffer is zeroed and the block written at the top — no
    fresh (cap, d) allocation and only a (threshold, d) transfer, instead
    of the zeros+set pair that re-pads the whole capacity."""
    bvalid = (jnp.arange(xrows.shape[0], dtype=jnp.int32) < m)
    xrows = xrows * bvalid.astype(xb.dtype)[:, None]
    return jax.lax.dynamic_update_slice(
        xb * 0.0, xrows, (jnp.int32(0), jnp.int32(0)))


def _ls_equal(a, b) -> bool:
    """Lengthscale equality across the scalar/ARD-vector forms."""
    a = np.asarray(a, float)
    b = np.asarray(b, float)
    return a.shape == b.shape and bool(np.all(a == b))


@jax.jit
def _predict_jit(xb, lib, alpha, n, xq, ls, signal):
    cap = xb.shape[0]
    mask = (jnp.arange(cap, dtype=jnp.int32) < n).astype(xb.dtype)
    ks = _kern(xq, xb, ls, signal) * mask[None, :]      # (P, cap)
    mu = ks @ alpha                                     # (P, J) normalized
    v = lib @ ks.T
    var = jnp.clip(signal - jnp.sum(v * v, axis=0), 1e-9, None)
    return jnp.concatenate([mu, var[:, None]], axis=1)  # one fetch: (P, J+1)


@jax.jit
def _predict_mean_jit(xb, alpha, n, xq, ls, signal):
    cap = xb.shape[0]
    mask = (jnp.arange(cap, dtype=jnp.int32) < n).astype(xb.dtype)
    return (_kern(xq, xb, ls, signal) * mask[None, :]) @ alpha


@jax.jit
def _ehvi_jit(xb, alpha, n, xq, front, ref, ym, ysd, ls, signal):
    """Fused: pool kernel GEMM → posterior means → denormalize → staircase
    EHVI sweep, one device call for the whole candidate pool.

    ``front`` is the sorted valid front padded with ``(ref[0], y_last)``
    sentinel rows — each contributes a zero-width segment, so the sum
    matches the unpadded numpy staircase exactly."""
    cap = xb.shape[0]
    mask = (jnp.arange(cap, dtype=jnp.int32) < n).astype(xb.dtype)
    ks = _kern(xq, xb, ls, signal) * mask[None, :]
    mu = ks @ alpha * ysd + ym                          # (P, 2) denormalized
    x, y = front[:, 0], front[:, 1]
    neg_inf = jnp.full((1,), -jnp.inf, dtype=xb.dtype)
    lows = jnp.concatenate([neg_inf, x])
    ups = jnp.concatenate([x, ref[0:1]])
    levels = jnp.concatenate([ref[1:2], y])
    width = jnp.clip(ups[None, :] - jnp.maximum(lows[None, :], mu[:, 0:1]),
                     0.0, None)
    height = jnp.clip(levels[None, :] - mu[:, 1:2], 0.0, None)
    return jnp.sum(width * height, axis=1)


class _Staged:
    """A ``stage()``: its key and pool rows, the raw target columns with
    their means and stds, the device alpha (cap, J) and posterior (P, J+1),
    and that posterior's host copy once fetched."""

    def __init__(self, key, rows, cols, ym, ys, alpha, post):
        self.key, self.rows, self.cols = key, rows, cols
        self.ym, self.ys = ym, ys
        self.alpha, self.post = alpha, post
        self.out: Optional[np.ndarray] = None


class JaxIncrementalGP:
    """Drop-in for ``IncrementalGP`` with device buffers + inducing points.

    ``inducing_threshold=None`` (or a huge value) keeps every observation
    active — exact numpy parity; with a threshold, ``len(gp)`` is the
    active-set size and ``gp.n_total`` the archive size.
    """

    def __init__(self, lengthscale=0.3, noise: float = 1e-3,
                 signal: float = 1.0,
                 inducing_threshold: Optional[int] = None,
                 inducing_overflow: float = 1.25):
        self.ls = (np.asarray(lengthscale, float)
                   if np.ndim(lengthscale) else float(lengthscale))
        self.noise = float(noise)
        self.signal = float(signal)
        self.inducing_threshold = inducing_threshold
        self.inducing_overflow = float(inducing_overflow)
        self._n = 0                       # active rows on device
        self._cap = 0
        self._dim = 0
        self._xb = self._lb = self._lib = None
        # full observation archive (host): the thinning source
        self._ax: Optional[np.ndarray] = None
        self._n_all = 0
        self._active_idx = np.zeros(0, np.int64)   # archive row per active row
        self.n_appends = 0
        self.n_refactors = 0
        self.n_thins = 0
        self.n_rethins = 0      # thins that reused the buffers in place
        self.n_predicts = 0
        self.n_predict_reuses = 0   # predicts that reused the pool's sd
        self.n_staged = 0           # predicts served from a stage()
        # bumped whenever the factor (_xb, _lb, _lib, _n) is reassigned;
        # _pool holds (_pool_key(), rows, device pool, sd) of the last full
        # predict, its sd None while that is a stage() not fetched yet
        self._version = 0
        self._pool = None
        self._staged: Optional[_Staged] = None
        # fit state (single- and multi-target kept separate, like numpy);
        # the single-target mean is column _col1 of _alpha1, and _served the
        # stage a served fit_y took it from
        self._alpha1 = self._ym = self._ys = None
        self._col1 = 0
        self._served: Optional[_Staged] = None
        self._alpha_m = self._ym_m = self._ys_m = None

    def __len__(self) -> int:
        return self._n

    @property
    def n_total(self) -> int:
        return self._n_all

    # -- buffers --------------------------------------------------------------
    def _ensure_cap(self, need: int, dim: int) -> None:
        if self._cap >= need and self._dim == dim:
            return
        cap = _pow2(need)
        with jax.enable_x64(True):
            xb = jnp.zeros((cap, dim), jnp.float64)
            lb = jnp.zeros((cap, cap), jnp.float64)
            lib = jnp.zeros((cap, cap), jnp.float64)
            n = self._n
            if n:
                xb = xb.at[:n, :].set(self._xb[:n, :])
                lb = lb.at[:n, :n].set(self._lb[:n, :n])
                lib = lib.at[:n, :n].set(self._lib[:n, :n])
        self._xb, self._lb, self._lib = xb, lb, lib
        self._cap, self._dim = cap, dim
        self._version += 1
        act = np.zeros(cap, np.int64)
        act[:self._n] = self._active_idx[:self._n]
        self._active_idx = act

    def _archive(self, x_new: np.ndarray) -> np.ndarray:
        m = len(x_new)
        need = self._n_all + m
        if self._ax is None or len(self._ax) < need:
            cap = _pow2(need)
            ax = np.zeros((cap, x_new.shape[1]))
            if self._n_all:
                ax[:self._n_all] = self._ax[:self._n_all]
            self._ax = ax
        self._ax[self._n_all:need] = x_new
        idx = np.arange(self._n_all, need, dtype=np.int64)
        self._n_all = need
        return idx

    # -- incremental growth ---------------------------------------------------
    def observe(self, x_new: np.ndarray) -> "JaxIncrementalGP":
        x_new = np.atleast_2d(np.asarray(x_new, float))
        m = len(x_new)
        if m == 0:
            return self
        idx = self._archive(x_new)
        with span("jx.gp.append", cap=self._cap, rows=m):
            self._append_active(x_new, idx)
        thr = self.inducing_threshold
        if thr is not None and self._n > int(thr * self.inducing_overflow):
            self._thin()
        return self

    def _append_active(self, xa: np.ndarray, idx: np.ndarray) -> None:
        m, d = xa.shape
        B = _pow2_small(m)
        # capacity must cover the *padded* block: dynamic_update_slice
        # clamps out-of-bounds starts, which would silently corrupt rows
        self._ensure_cap(self._n + B, d)
        xpad = np.zeros((B, d))
        xpad[:m] = xa
        with jax.enable_x64(True):
            self._xb, self._lb, self._lib, ok = _append_jit(
                self._xb, self._lb, self._lib,
                np.int32(self._n), np.int32(m), jnp.asarray(xpad),
                self.ls, self.noise, self.signal)
        self._active_idx[self._n:self._n + m] = idx
        self._n += m
        self._version += 1
        self.n_appends += 1
        if not bool(_fetch(ok)):
            # degenerate block (duplicated rows beyond the noise jitter):
            # same fallback as the numpy LinAlgError path
            self._refactor()

    def _refactor(self) -> None:
        with span("jx.gp.refactor", cap=self._cap, rows=self._n), \
                jax.enable_x64(True):
            self._lb, self._lib = _refactor_jit(
                self._xb, np.int32(self._n), self.ls, self.noise, self.signal)
        self._version += 1
        self.n_refactors += 1

    def _thin(self) -> None:
        """Shrink the active set to an evenly-strided archive subset.

        A re-thin never grows the active set, so once the pow2 capacity has
        settled every thin lands back in the same-sized buffers: that case
        rides ``_rethin_jit`` (donated in-place row write, threshold-sized
        transfer) instead of reallocating and re-padding full-capacity
        device arrays.  ``stats()['rethins']`` counts the in-place ones.
        """
        thr = int(self.inducing_threshold)
        sel = np.unique(np.linspace(0, self._n_all - 1, thr).round()
                        .astype(np.int64))
        xa = self._ax[sel]
        m, d = xa.shape
        with span("jx.gp.rethin", cap=self._cap, rows=m):
            if (self._xb is not None and self._dim == d
                    and _pow2(m) <= self._cap):
                xpad = np.zeros((thr, d))
                xpad[:m] = xa
                with jax.enable_x64(True):
                    self._xb = _rethin_jit(self._xb, jnp.asarray(xpad),
                                           np.int32(m))
                self.n_rethins += 1
            else:
                self._n = 0
                self._ensure_cap(m, d)
                with jax.enable_x64(True):
                    self._xb = (jnp.zeros((self._cap, d), jnp.float64)
                                .at[:m, :].set(jnp.asarray(xa)))
            self._n = m
            self._version += 1
            self._active_idx[:m] = sel
            self._refactor()
        self.n_thins += 1

    def set_lengthscale(self, ls) -> "JaxIncrementalGP":
        """Hyperparameter refresh: new lengthscale (scalar or per-dimension
        ARD vector), one masked refactor riding the existing device buffers."""
        if _ls_equal(ls, self.ls):
            return self
        new = np.asarray(ls, float)
        self.ls = new if new.ndim else float(new)
        if self._n:
            self._refactor()
        return self

    def fit_x(self, x: np.ndarray) -> "JaxIncrementalGP":
        """Reset and bulk-load (equivalence/refit entry point)."""
        self._n = 0
        self._n_all = 0
        self._version += 1
        return self.observe(x)

    # -- fits -----------------------------------------------------------------
    def _active_targets(self, Y: np.ndarray) -> np.ndarray:
        """Archive-aligned targets → active subset (SoD selection)."""
        Y = np.asarray(Y, float)
        if len(Y) == self._n:
            return Y
        assert len(Y) == self._n_all, (
            f"targets must align with the archive ({self._n_all}) or the "
            f"active set ({self._n}), got {len(Y)}")
        return Y[self._active_idx[:self._n]]

    def _padded(self, ya: np.ndarray) -> jnp.ndarray:
        out = np.zeros((self._cap,) + ya.shape[1:])
        out[:self._n] = ya
        return jnp.asarray(out)

    def fit_y(self, y: np.ndarray) -> "JaxIncrementalGP":
        assert self._n > 0, "observe first"
        y = np.asarray(y, float)
        j = self._staged_column(y)
        if j is not None:
            st = self._served = self._staged
            with span("jx.gp.fit_y", cap=self._cap, rows=self._n, staged=1):
                self._alpha1, self._col1 = st.alpha, j
                self._ym, self._ys = st.ym[j], st.ys[j]
            return self
        ya = self._active_targets(y)
        self._ym = float(np.mean(ya))
        self._ys = float(np.std(ya)) or 1.0
        self._col1, self._served = 0, None
        with span("jx.gp.fit_y", cap=self._cap, rows=self._n, staged=0), \
                jax.enable_x64(True):
            self._alpha1 = _fit_y_jit(
                self._lib, self._padded((ya - self._ym) / self._ys)[:, None])
        return self

    def fit(self, x: np.ndarray, y: np.ndarray) -> "JaxIncrementalGP":
        return self.fit_x(x).fit_y(y)

    def fit_y_multi(self, Y: np.ndarray) -> "JaxIncrementalGP":
        assert self._n > 0, "observe first"
        ya = self._active_targets(Y)
        self._ym_m = ya.mean(axis=0)
        std = ya.std(axis=0)
        self._ys_m = np.where(std > 0, std, 1.0)
        with span("jx.gp.fit_y", cap=self._cap, rows=self._n), \
                jax.enable_x64(True):
            self._alpha_m = _fit_y_jit(
                self._lib, self._padded((ya - self._ym_m) / self._ys_m))
        return self

    # -- staged picks ----------------------------------------------------------
    def stage(self, targets: np.ndarray, xs: np.ndarray) -> "JaxIncrementalGP":
        """Fit each column of ``targets`` and score every column over the
        pool ``xs``, ahead of the per-pick ``fit_y`` and ``predict`` calls.

        Each column is standardised as ``fit_y`` does it (its own mean, its
        std or 1.0), the columns are padded to a power of two and copied in
        one transfer, and one ``_fit_y_jit`` and one ``_predict_jit`` run
        over them all.  Nothing is fetched here: the first served predict
        copies the (M, J+1) posterior to the host.  The stage holds while
        ``_pool_key()`` does, and becomes the pool a later unstaged predict
        on the same rows reuses the sd of."""
        assert self._n > 0, "observe first"
        xs = np.atleast_2d(np.asarray(xs, float))
        cols = [np.ascontiguousarray(c)
                for c in np.asarray(targets, float).reshape(
                    len(targets), -1).T]
        yn = np.zeros((self._cap, _pow2_small(len(cols))))
        ym, ys = [], []
        for j, c in enumerate(cols):
            ya = self._active_targets(c)
            ym.append(float(np.mean(ya)))
            ys.append(float(np.std(ya)) or 1.0)
            yn[:self._n, j] = (ya - ym[j]) / ys[j]
        key = self._pool_key()
        with span("jx.gp.stage", cap=self._cap, rows=len(xs),
                  targets=len(cols)):
            xq, _ = self._pad_pool(xs)
            with jax.enable_x64(True):
                alpha = _fit_y_jit(self._lib, jnp.asarray(yn))
                post = _predict_jit(self._xb, self._lib, alpha,
                                    np.int32(self._n), xq, self.ls,
                                    self.signal)
        self._staged = _Staged(key, xs.copy(), cols, ym, ys, alpha, post)
        self._pool = (key, self._staged.rows, xq, None)
        return self

    def _staged_column(self, y: np.ndarray) -> Optional[int]:
        """The staged column equal to the targets ``y``, if the stage
        still holds."""
        st = self._staged
        if st is None or st.key != self._pool_key():
            return None
        return next((j for j, c in enumerate(st.cols)
                     if np.array_equal(c, y)), None)

    @staticmethod
    def _staged_out(st: _Staged) -> np.ndarray:
        """The stage's (M, J+1) posterior on the host, fetched once."""
        if st.out is None:
            st.out = _fetch(st.post)[:len(st.rows)]
        return st.out

    # -- predicts -------------------------------------------------------------
    def _pad_pool(self, xs: np.ndarray):
        xs = np.atleast_2d(np.asarray(xs, float))
        P = _pow2_small(max(len(xs), 1))
        xq = np.zeros((P, xs.shape[1]))
        xq[:len(xs)] = xs
        # the device transfer must happen inside the x64 scope: outside it,
        # jnp.asarray silently truncates the queries to float32 and every
        # downstream GEMM runs on f32-rounded inputs (≈1e-7 posterior error
        # — the exact silent-precision bug this module exists to avoid)
        with jax.enable_x64(True):
            xq = jnp.asarray(xq)
        return xq, len(xs)

    def _pool_key(self) -> tuple:
        """What the pool's sd depends on besides its rows."""
        return (self._version, np.ndim(self.ls),
                np.asarray(self.ls, float).tobytes(), self.noise, self.signal)

    def _posterior(self, xs: np.ndarray, alpha):
        """Normalised mean (M, J) and sd (M,) over the rows ``xs``.

        Rows equal to the last full predict's, against the same factor and
        hyperparameters, run the mean alone on that call's device pool and
        take its sd; any other rows run mean and variance in one program
        and come back in one fetch."""
        xs = np.atleast_2d(np.asarray(xs, float))
        key = self._pool_key()
        pool = self._pool
        reuse = (pool is not None and pool[0] == key
                 and np.array_equal(pool[1], xs))
        self.n_predicts += 1
        self.n_predict_reuses += reuse
        with span("jx.gp.predict", cap=self._cap, rows=len(xs),
                  reuse=int(reuse)):
            if reuse:
                xq, sd = pool[2], pool[3]
                if sd is None:
                    sd = np.sqrt(self._staged_out(self._staged)[:, -1])
                with jax.enable_x64(True):
                    mu = _predict_mean_jit(self._xb, alpha, np.int32(self._n),
                                           xq, self.ls, self.signal)
                return _fetch(mu)[:len(xs)], sd
            xq, M = self._pad_pool(xs)
            with jax.enable_x64(True):
                out = _fetch(_predict_jit(self._xb, self._lib, alpha,
                                          np.int32(self._n), xq, self.ls,
                                          self.signal))[:M]
            sd = np.sqrt(out[:, -1])
            self._pool = (key, xs.copy(), xq, sd)
            return out[:, :-1], sd

    def predict(self, xs: np.ndarray):
        st = self._served
        if (st is not None and st.key == self._pool_key()
                and np.array_equal(st.rows, np.atleast_2d(xs))):
            # the served fit_y's column of the staged posterior; the first
            # such predict of a stage fetches it, the rest reuse it
            reuse = st.out is not None
            self.n_predicts += 1
            self.n_predict_reuses += reuse
            self.n_staged += 1
            with span("jx.gp.predict", cap=self._cap, rows=len(st.rows),
                      reuse=int(reuse), staged=1):
                out = self._staged_out(st)
            mu, sd = out[:, self._col1], np.sqrt(out[:, -1])
        else:
            mu, sd = self._posterior(xs, self._alpha1)
            mu = mu[:, self._col1]
        return mu * self._ys + self._ym, sd * self._ys

    def predict_multi(self, xs: np.ndarray):
        mu, sd = self._posterior(xs, self._alpha_m)
        return mu * self._ys_m + self._ym_m, sd[:, None] * self._ys_m

    def predict_mean_multi(self, xs: np.ndarray) -> np.ndarray:
        with span("jx.gp.predict", cap=self._cap, rows=len(xs)):
            xq, M = self._pad_pool(xs)
            with jax.enable_x64(True):
                mu = _predict_mean_jit(self._xb, self._alpha_m,
                                       np.int32(self._n), xq, self.ls,
                                       self.signal)
            return _fetch(mu)[:M] * self._ys_m + self._ym_m

    def score_ehvi(self, xs: np.ndarray, front_y: np.ndarray,
                   ref: np.ndarray) -> np.ndarray:
        """Fused EHVI over the pool: posterior means + staircase sweep in
        one device call (means are *not* round-tripped to the host)."""
        xs = np.atleast_2d(np.asarray(xs, float))
        if len(xs) == 0:
            return np.zeros(0)
        ref = np.asarray(ref, float)
        front = np.asarray(front_y, float)
        front = front[np.all(front < ref, axis=1)]
        if len(front) == 0:
            mu = self.predict_mean_multi(xs)
            return (np.clip(ref[0] - mu[:, 0], 0.0, None)
                    * np.clip(ref[1] - mu[:, 1], 0.0, None))
        from repro.core.results import nondominated_mask

        front = front[nondominated_mask(front)]
        front = front[np.argsort(front[:, 0])]
        F = _pow2_small(len(front))
        pad = np.repeat([[ref[0], front[-1, 1]]], F - len(front), axis=0)
        fpad = np.vstack([front, pad])
        with span("jx.gp.score_ehvi", cap=self._cap, rows=len(xs)):
            xq, M = self._pad_pool(xs)
            with jax.enable_x64(True):
                s = _ehvi_jit(self._xb, self._alpha_m, np.int32(self._n), xq,
                              jnp.asarray(fpad), jnp.asarray(ref),
                              jnp.asarray(self._ym_m),
                              jnp.asarray(self._ys_m), self.ls, self.signal)
            return _fetch(s)[:M]

    def stats(self) -> dict:
        return {"n_active": self._n, "n_total": self._n_all,
                "capacity": self._cap, "appends": self.n_appends,
                "refactors": self.n_refactors, "thins": self.n_thins,
                "rethins": self.n_rethins, "predicts": self.n_predicts,
                "predict_reuses": self.n_predict_reuses,
                "staged": self.n_staged}

    # -- durable state ---------------------------------------------------------
    def state_dict(self) -> dict:
        """Exported factor + archive for checkpoint/resume.

        The device factor travels as trimmed host-numpy copies — *not* a
        refit on restore, which would reorder float ops and break the
        bit-exact resumed-pick guarantee.  float64 round-trips device→host→
        device losslessly, so the restored factor is bit-identical.  Fit
        caches (``_alpha*``) are recomputed on the next ask and not saved.
        """
        n = self._n
        return {"kind": "jax", "ls": self.ls, "noise": self.noise,
                "signal": self.signal,
                "inducing_threshold": self.inducing_threshold,
                "inducing_overflow": self.inducing_overflow,
                "n": n, "n_all": self._n_all,
                "xb": None if n == 0 else np.asarray(self._xb)[:n].copy(),
                "lb": None if n == 0 else np.asarray(self._lb)[:n, :n].copy(),
                "lib": None if n == 0 else np.asarray(self._lib)[:n, :n].copy(),
                "ax": (None if self._n_all == 0
                       else np.array(self._ax[:self._n_all])),
                "active_idx": np.array(self._active_idx[:n]),
                "n_appends": self.n_appends,
                "n_refactors": self.n_refactors,
                "n_thins": self.n_thins, "n_rethins": self.n_rethins}

    def load_state(self, state: dict) -> "JaxIncrementalGP":
        self.ls = state["ls"]
        self.noise = state["noise"]
        self.signal = state["signal"]
        self.inducing_threshold = state["inducing_threshold"]
        self.inducing_overflow = state["inducing_overflow"]
        self._n = self._cap = self._dim = 0
        self._xb = self._lb = self._lib = None
        self._ax = None
        self._n_all = 0
        self._active_idx = np.zeros(0, np.int64)
        n = int(state["n"])
        if state["ax"] is not None:
            ax = np.asarray(state["ax"], float)
            cap = _pow2(len(ax))
            self._ax = np.zeros((cap, ax.shape[1]))
            self._ax[:len(ax)] = ax
            self._n_all = int(state["n_all"])
        if n:
            d = state["xb"].shape[1]
            self._ensure_cap(n, d)     # allocates zeroed pow2 device buffers
            with jax.enable_x64(True):
                self._xb = self._xb.at[:n, :].set(jnp.asarray(state["xb"]))
                self._lb = self._lb.at[:n, :n].set(jnp.asarray(state["lb"]))
                self._lib = self._lib.at[:n, :n].set(jnp.asarray(state["lib"]))
            self._n = n
            self._active_idx[:n] = np.asarray(state["active_idx"], np.int64)
        self.n_appends = state["n_appends"]
        self.n_refactors = state["n_refactors"]
        self.n_thins = state["n_thins"]
        self.n_rethins = state["n_rethins"]
        self._version += 1
        self._staged = self._served = None
        self._alpha1 = self._ym = self._ys = None
        self._col1 = 0
        self._alpha_m = self._ym_m = self._ys_m = None
        return self

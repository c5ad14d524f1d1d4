"""Floating-point operations of the device GP's calls, from their shapes.

The surrogate works on zero-padded buffers of a power-of-two ``cap`` rows, so
the device does the work of the padded shapes: that is what is counted.  A
matrix product (m, k) @ (k, n) counts 2·m·k·n; an RBF kernel block of (a, b)
rows in ``dim`` dimensions counts its Gram product plus 6 operations per
entry (norms, scale, exponent).  Elementwise masks and clips are left out,
and so is the full refactor a degenerate append falls back to (it cannot be
seen from outside the GP), so the counts are a lower bound of what the calls
do.
"""
from __future__ import annotations


def kernel_flops(a: int, b: int, dim: int) -> int:
    return 2 * a * b * dim + 2 * (a + b) * dim + 6 * a * b


def append_flops(cap: int, block: int, dim: int) -> int:
    """Rank-append of a ``block``-row (padded) batch into a ``cap`` factor:
    kernel strips, w = L⁻¹K₁₂, the Schur block, its Cholesky and inverse,
    and the new rows of L⁻¹."""
    c, b = cap, block
    return (kernel_flops(c, b, dim) + kernel_flops(b, b, dim)
            + 2 * c * c * b              # w = lib @ k12
            + 2 * b * b * c              # w.T @ w
            + b ** 3 // 3 + b ** 3       # cholesky, triangular inverse
            + 2 * b * c * c              # w.T @ lib
            + 2 * b * b * c)             # li22 @ (w.T @ lib)


def fit_y_flops(cap: int, targets: int = 1) -> int:
    """alpha = L⁻ᵀ (L⁻¹ y): two (cap, cap) x (cap, targets) products."""
    return 4 * cap * cap * targets


def predict_mean_flops(cap: int, pool: int, dim: int, targets: int = 1) -> int:
    """Posterior mean alone over a ``pool``-row (padded) batch: the kernel
    block and ks @ alpha, all a predict runs where it reuses the last full
    predict's pool and variance."""
    return kernel_flops(pool, cap, dim) + 2 * pool * cap * targets


def predict_flops(cap: int, pool: int, dim: int, targets: int = 1) -> int:
    """Posterior mean and variance over a ``pool``-row (padded) batch."""
    return (predict_mean_flops(cap, pool, dim, targets)
            + 2 * cap * cap * pool       # v = lib @ ks.T
            + 2 * cap * pool)            # column sums of v * v

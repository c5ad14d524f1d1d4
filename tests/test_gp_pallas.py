"""Pallas GP ask path (gp_mode="pallas"): kernel-vs-jnp-vs-numpy parity in
interpret mode across doubling and tile boundaries, the fused EHVI sweep
(≤1e-6 equivalence gate, actually ~1e-15), degenerate-append masked-refactor
fallback, in-place inducing re-thins, ARD lengthscale refresh parity, and
BayesOpt/PAL pick-sequence equality against the numpy reference."""
import numpy as np
import pytest

from repro.core.search.bayesopt import (BayesOpt, IncrementalGP, PAL,
                                        ehvi_improvements, tune_lengthscale)
from repro.core.search.gp_jax import JaxIncrementalGP
from repro.core.search.gp_pallas import PallasIncrementalGP
from repro.core.space import tpu_pod_space


def _toy_objectives(space, knobs):
    x = space.encode(knobs)
    time = 2.0 - 1.2 * x[0] + 0.4 * x[1] + 0.1 * np.sin(7 * x.sum())
    power = 0.5 + 1.5 * x[0] ** 2 + 0.2 * x[2]
    return np.array([time, power])


# ---------------------------------------------------------------------------
# kernel parity: append + factors across doubling / tile boundaries
# ---------------------------------------------------------------------------


def test_pallas_matches_numpy_across_doubling_boundaries():
    """block=16 forces multi-tile grids as soon as capacity doubles past 16:
    the tiled triangular kernels must reproduce the numpy rank-append path
    at float64 round-off, through the same mixed block sizes as the jax
    suite (crossing capacities 16 → 32 → 64)."""
    rng = np.random.default_rng(0)
    ref = IncrementalGP()
    pgp = PallasIncrementalGP(block=16, pool_block=16)
    xs = np.zeros((0, 5))
    for step in (1, 1, 3, 1, 10, 1, 2, 17):
        xn = rng.random((step, 5))
        xs = np.vstack([xs, xn])
        ref.observe(xn)
        pgp.observe(xn)
        assert len(pgp) == len(xs)
    assert pgp.stats()["pallas_appends"] == 8
    y = rng.random(len(xs))
    Y = rng.random((len(xs), 2))
    q = rng.random((9, 5))
    mu_r, sig_r = ref.fit_y(y).predict(q)
    mu_p, sig_p = pgp.fit_y(y).predict(q)
    np.testing.assert_allclose(mu_p, mu_r, atol=1e-10)
    np.testing.assert_allclose(sig_p, sig_r, atol=1e-10)
    mu_r, sig_r = ref.fit_y_multi(Y).predict_multi(q)
    mu_p, sig_p = pgp.fit_y_multi(Y).predict_multi(q)
    np.testing.assert_allclose(mu_p, mu_r, atol=1e-10)
    np.testing.assert_allclose(sig_p, sig_r, atol=1e-10)


def test_pallas_factors_match_jax_bitwise_shapes():
    """The tiled append and the jnp append must agree on the raw factor
    buffers (not just posteriors) — including the exact-zero invariant on
    inactive rows that the tile-skipping relies on."""
    rng = np.random.default_rng(1)
    jgp = JaxIncrementalGP()
    pgp = PallasIncrementalGP(block=16, pool_block=16)
    for step in (4, 13, 1, 30):            # lands exactly on a 16-multiple too
        xn = rng.random((step, 3))
        jgp.observe(xn)
        pgp.observe(xn)
    lb_j, lb_p = np.asarray(jgp._lb), np.asarray(pgp._lb)
    lib_j, lib_p = np.asarray(jgp._lib), np.asarray(pgp._lib)
    np.testing.assert_allclose(lb_p, lb_j, atol=1e-12)
    np.testing.assert_allclose(lib_p, lib_j, atol=1e-12)
    n = len(pgp)
    assert np.all(lb_p[n:] == 0.0) and np.all(lb_p[:, n:] == 0.0)
    assert np.all(lib_p[n:] == 0.0) and np.all(lib_p[:, n:] == 0.0)


def test_tile_boundary_appends():
    """Appends whose padded block lands exactly on tile boundaries (B equal
    to, above, and below the kernel block) stay exact."""
    rng = np.random.default_rng(2)
    ref = IncrementalGP()
    pgp = PallasIncrementalGP(block=8, pool_block=8)
    for step in (8, 16, 7, 9, 8):
        xn = rng.random((step, 4))
        ref.observe(xn)
        pgp.observe(xn)
    y = rng.random(len(ref))
    q = rng.random((5, 4))
    mu_r, sig_r = ref.fit_y(y).predict(q)
    mu_p, sig_p = pgp.fit_y(y).predict(q)
    np.testing.assert_allclose(mu_p, mu_r, atol=1e-10)
    np.testing.assert_allclose(sig_p, sig_r, atol=1e-10)


def test_degenerate_append_masked_refactor_fallback():
    """Zero noise + duplicated rows: the Schur block's Cholesky goes NaN,
    the kernel append's finiteness flag trips, and the masked full refactor
    engages — same contract as the jnp and numpy paths."""
    rng = np.random.default_rng(3)
    xs = rng.random((12, 3))
    pgp = PallasIncrementalGP(noise=0.0, block=16, pool_block=16).fit_x(xs)
    before = pgp.n_refactors
    pgp.observe(np.vstack([xs[3][None], xs[3][None]]))
    assert pgp.n_refactors == before + 1
    assert len(pgp) == 14                     # the data still landed


# ---------------------------------------------------------------------------
# fused EHVI sweep
# ---------------------------------------------------------------------------


def test_fused_ehvi_matches_jnp_and_numpy_staircase():
    rng = np.random.default_rng(4)
    xs = rng.random((30, 4))
    Y = rng.random((30, 2))
    pool = rng.random((25, 4))
    ref_pt = Y.max(0) * 1.1 + 1e-9
    ref = IncrementalGP().fit_x(xs).fit_y_multi(Y)
    want = ehvi_improvements(Y, ref_pt, ref.predict_mean_multi(pool))
    jgp = JaxIncrementalGP().fit_x(xs)
    jgp.fit_y_multi(Y)
    want_j = jgp.score_ehvi(pool, Y, ref_pt)
    pgp = PallasIncrementalGP(block=16, pool_block=8).fit_x(xs)
    pgp.fit_y_multi(Y)
    got = pgp.score_ehvi(pool, Y, ref_pt)
    # the ISSUE gate is 1e-6; the kernel actually holds float64 round-off
    np.testing.assert_allclose(got, want_j, atol=1e-8)
    np.testing.assert_allclose(got, want, atol=1e-8)
    assert pgp.stats()["pallas_scores"] == 1


def test_fused_ehvi_empty_front_and_empty_pool():
    rng = np.random.default_rng(5)
    xs = rng.random((10, 3))
    Y = rng.random((10, 2))
    pgp = PallasIncrementalGP(block=16, pool_block=16).fit_x(xs)
    pgp.fit_y_multi(Y)
    ref_pt = Y.min(0) - 1.0                   # nothing beats the reference
    s = pgp.score_ehvi(rng.random((4, 3)), Y, ref_pt)
    assert s.shape == (4,) and np.all(s == 0.0)
    assert pgp.score_ehvi(np.zeros((0, 3)), Y, Y.max(0) + 1).shape == (0,)


# ---------------------------------------------------------------------------
# inducing points: engagement + in-place re-thins
# ---------------------------------------------------------------------------


def test_inducing_rethin_reuses_buffers_in_place():
    """Once the pow2 capacity settles, every further thin must ride the
    donated in-place path (no realloc/re-pad) and be counted in stats()."""
    rng = np.random.default_rng(6)
    pgp = PallasIncrementalGP(inducing_threshold=64, block=16, pool_block=16)
    xs = rng.random((300, 3))
    for i in range(0, 300, 25):
        pgp.observe(xs[i:i + 25])
    s = pgp.stats()
    assert s["n_total"] == 300
    assert s["thins"] > 1
    # capacity (128) already covers the thinned set when the first thin
    # fires, so every thin here must ride the donated in-place path
    assert s["rethins"] == s["thins"]
    assert s["capacity"] == 128               # settled, never re-grown
    assert len(pgp) <= int(64 * pgp.inducing_overflow)
    # and the thinned posterior still matches an exact GP loosely
    y = np.sin(3 * xs[:, 0]) + 0.5 * np.cos(2 * xs[:, 1])
    pgp.fit_y(y)
    q = rng.random((40, 3))
    mu, _ = pgp.predict(q)
    want = np.sin(3 * q[:, 0]) + 0.5 * np.cos(2 * q[:, 1])
    assert float(np.sqrt(np.mean((mu - want) ** 2))) < 0.15


# ---------------------------------------------------------------------------
# ARD lengthscale refresh
# ---------------------------------------------------------------------------


def test_ard_lengthscale_parity_across_modes():
    """A per-dimension vector adopted via set_lengthscale must produce the
    same posterior in numpy, jnp, and pallas modes — through both the
    refactor path (set after observe) and the append path (set before)."""
    rng = np.random.default_rng(7)
    d = 4
    ls = np.array([0.2, 0.5, 0.9, 0.35])
    xs = rng.random((23, d))
    y = rng.random(23)
    q = rng.random((9, d))
    gps = [IncrementalGP(), JaxIncrementalGP(),
           PallasIncrementalGP(block=16, pool_block=16)]
    outs = []
    for g in gps:
        g.observe(xs)
        g.set_lengthscale(ls)
        outs.append(g.fit_y(y).predict(q))
    for mu, sig in outs[1:]:
        np.testing.assert_allclose(mu, outs[0][0], atol=1e-10)
        np.testing.assert_allclose(sig, outs[0][1], atol=1e-10)
    # append path under a vector lengthscale (pallas pre-scales in-kernel)
    ref = IncrementalGP(lengthscale=ls)
    pgp = PallasIncrementalGP(lengthscale=ls, block=16, pool_block=16)
    for step in (3, 14, 6):
        xn = rng.random((step, d))
        ref.observe(xn)
        pgp.observe(xn)
    y2 = rng.random(len(ref))
    mu_r, sig_r = ref.fit_y(y2).predict(q)
    mu_p, sig_p = pgp.fit_y(y2).predict(q)
    np.testing.assert_allclose(mu_p, mu_r, atol=1e-10)
    np.testing.assert_allclose(sig_p, sig_r, atol=1e-10)


def test_ard_refresh_fires_through_pallas_searcher():
    """The --gp-refresh schedule must run (and possibly adopt an ARD
    vector) on the pallas surrogate without disturbing the search loop."""
    space = tpu_pod_space(n_chips=256)
    algo = BayesOpt(space, seed=3, n_init=6, pool_size=64, strategy="ehvi",
                    gp_mode="pallas", hyper_refresh_every=10)
    for _ in range(30):
        c = algo.ask(1)[0]
        x = space.encode(c)
        algo.tell(c, np.array([x[0] + 0.5 * x[1], 1.0 - x[0] + 0.3 * x[2]]))
    assert algo.n_hyper_refreshes >= 2
    assert np.all(np.atleast_1d(algo._gp.ls) > 0.3)


def test_tune_lengthscale_prefers_ard_on_anisotropic_data():
    rng = np.random.default_rng(8)
    xs = rng.random((80, 3)) * np.array([1.0, 0.05, 1.0])
    y = np.sin(6 * xs[:, 0]) + 50.0 * xs[:, 1]
    ls = tune_lengthscale(xs, y, current=0.3)
    assert np.ndim(ls) == 1 and ls.shape == (3,)
    assert ls[1] < ls[0]                     # tight dim gets the tight scale


# ---------------------------------------------------------------------------
# pick-sequence equality through the searchers (vs the numpy reference)
# ---------------------------------------------------------------------------


def test_bayesopt_pallas_picks_match_incremental():
    space = tpu_pod_space(n_chips=256)
    seqs = {}
    for mode in ("incremental", "pallas"):
        algo = BayesOpt(space, seed=3, n_init=6, pool_size=64,
                        strategy="ehvi", gp_mode=mode)
        seq = []
        for _ in range(30):
            c = algo.ask(1)[0]
            algo.tell(c, _toy_objectives(space, c))
            seq.append(c)
        seqs[mode] = seq
    assert seqs["pallas"] == seqs["incremental"]


def test_pal_pallas_picks_match_incremental():
    space = tpu_pod_space(n_chips=256)
    seqs = {}
    for mode in ("incremental", "pallas"):
        algo = PAL(space, seed=3, n_init=6, pool_size=64, gp_mode=mode)
        seq = []
        for _ in range(20):
            c = algo.ask(1)[0]
            algo.tell(c, _toy_objectives(space, c))
            seq.append(c)
        seqs[mode] = seq
    assert seqs["pallas"] == seqs["incremental"]

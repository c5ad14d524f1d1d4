"""JAX GP fast path (gp_mode="jax"): numerical equivalence to the numpy
reference across doubling boundaries, float64 regression (no silent float32
and no global x64 leak), the fused EHVI device sweep, subset-of-data
inducing points (engagement + error bound), degenerate-append fallback,
the pool posterior's reuse across predicts and what drops it,
pick-sequence equality through BayesOpt/PAL, and the hyperparameter refresh
schedule riding the device buffers."""
import numpy as np
import pytest

from repro.core.search.bayesopt import (BayesOpt, GP, IncrementalGP, PAL,
                                        ehvi_improvements)
from repro.core.search import gp_jax
from repro.core.search.gp_jax import JaxIncrementalGP
from repro.core.search.gp_pallas import PallasIncrementalGP
from repro.core.space import tpu_pod_space


def _toy_objectives(space, knobs):
    x = space.encode(knobs)
    time = 2.0 - 1.2 * x[0] + 0.4 * x[1] + 0.1 * np.sin(7 * x.sum())
    power = 0.5 + 1.5 * x[0] ** 2 + 0.2 * x[2]
    return np.array([time, power])


# ---------------------------------------------------------------------------
# numerical equivalence to the numpy IncrementalGP
# ---------------------------------------------------------------------------


def test_jax_matches_numpy_across_doubling_boundaries():
    """Mixed append block sizes crossing the capacity doublings (16, 32)
    must produce posteriors equal to the numpy rank-append path at float64
    round-off — single-target and multi-target."""
    rng = np.random.default_rng(0)
    ref = IncrementalGP()
    jgp = JaxIncrementalGP()
    xs = np.zeros((0, 5))
    for step in (1, 1, 3, 1, 10, 1, 2, 17):
        xn = rng.random((step, 5))
        xs = np.vstack([xs, xn])
        ref.observe(xn)
        jgp.observe(xn)
        assert len(jgp) == len(xs)
    y = rng.random(len(xs))
    Y = rng.random((len(xs), 2))
    q = rng.random((9, 5))
    mu_r, sig_r = ref.fit_y(y).predict(q)
    mu_j, sig_j = jgp.fit_y(y).predict(q)
    np.testing.assert_allclose(mu_j, mu_r, atol=1e-10)
    np.testing.assert_allclose(sig_j, sig_r, atol=1e-10)
    mu_r, sig_r = ref.fit_y_multi(Y).predict_multi(q)
    mu_j, sig_j = jgp.fit_y_multi(Y).predict_multi(q)
    np.testing.assert_allclose(mu_j, mu_r, atol=1e-10)
    np.testing.assert_allclose(sig_j, sig_r, atol=1e-10)
    np.testing.assert_allclose(jgp.predict_mean_multi(q),
                               ref.predict_mean_multi(q), atol=1e-10)


def test_float64_end_to_end_no_global_leak():
    """The device path must run in true float64 — a silently-float32 path
    cannot hit 1e-12 against the numpy reference — while jax's global
    default dtype stays float32 outside the scoped enable_x64 blocks."""
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    xs = rng.random((40, 4))
    y = rng.random(40)
    q = rng.random((8, 4))
    jgp = JaxIncrementalGP().fit_x(xs).fit_y(y)
    assert jgp._xb.dtype == jnp.float64
    assert jgp._lb.dtype == jnp.float64
    mu_r, sig_r = IncrementalGP().fit_x(xs).fit_y(y).predict(q)
    mu_j, sig_j = jgp.predict(q)
    np.testing.assert_allclose(mu_j, mu_r, atol=1e-12)
    np.testing.assert_allclose(sig_j, sig_r, atol=1e-12)
    assert mu_j.dtype == np.float64
    # scoping regression: enable_x64 must not leak into the process default
    assert jnp.zeros(1).dtype == jnp.float32


def test_fused_ehvi_matches_numpy_staircase():
    rng = np.random.default_rng(2)
    xs = rng.random((30, 4))
    Y = rng.random((30, 2))
    pool = rng.random((25, 4))
    ref_pt = Y.max(0) * 1.1 + 1e-9
    ref = IncrementalGP().fit_x(xs).fit_y_multi(Y)
    mus = ref.predict_mean_multi(pool)
    want = ehvi_improvements(Y, ref_pt, mus)
    jgp = JaxIncrementalGP().fit_x(xs)
    jgp.fit_y_multi(Y)
    got = jgp.score_ehvi(pool, Y, ref_pt)
    np.testing.assert_allclose(got, want, atol=1e-10)


def test_degenerate_append_triggers_nan_flag_fallback():
    """With zero noise an exact duplicate makes the append's Schur
    complement numerically non-PD.  ``jnp.linalg.cholesky`` returns NaN
    instead of raising (unlike numpy's LinAlgError), so the append jit
    reports a finiteness flag and the masked full refactor engages."""
    rng = np.random.default_rng(3)
    xs = rng.random((12, 3))
    jgp = JaxIncrementalGP(noise=0.0).fit_x(xs)
    before = jgp.n_refactors
    jgp.observe(np.vstack([xs[3][None], xs[3][None]]))
    assert jgp.n_refactors == before + 1
    assert len(jgp) == 14                     # the data still landed


def test_masked_refactor_matches_numpy_factorisation():
    """The fallback payload: a full masked refactor over the zero-padded
    device buffers must reproduce the numpy factorisation exactly."""
    rng = np.random.default_rng(6)
    xs = rng.random((20, 3))
    jgp = JaxIncrementalGP().fit_x(xs)
    jgp._refactor()                           # force the fallback path
    y = rng.random(20)
    q = rng.random((6, 3))
    mu_j, sig_j = jgp.fit_y(y).predict(q)
    mu_r, sig_r = IncrementalGP().fit_x(xs).fit_y(y).predict(q)
    np.testing.assert_allclose(mu_j, mu_r, atol=1e-10)
    np.testing.assert_allclose(sig_j, sig_r, atol=1e-10)


# ---------------------------------------------------------------------------
# inducing points (subset-of-data)
# ---------------------------------------------------------------------------


def test_inducing_points_engage_and_stay_bounded():
    rng = np.random.default_rng(4)
    jgp = JaxIncrementalGP(inducing_threshold=64)
    xs = rng.random((300, 3))
    for i in range(0, 300, 25):
        jgp.observe(xs[i:i + 25])
    assert jgp.n_total == 300
    # active set stays within the thinning band around the threshold
    assert len(jgp) <= int(64 * jgp.inducing_overflow)
    assert jgp.n_thins > 0
    s = jgp.stats()
    assert s["n_active"] == len(jgp) and s["n_total"] == 300


def test_inducing_error_bounded_on_smooth_function():
    """SoD on a smooth target: the thinned posterior tracks the function to
    a loose tolerance (far tighter than the function's range)."""
    rng = np.random.default_rng(5)
    xs = rng.random((300, 2))

    def f(x):
        return np.sin(3 * x[:, 0]) + 0.5 * np.cos(2 * x[:, 1])

    jgp = JaxIncrementalGP(inducing_threshold=64).fit_x(xs).fit_y(f(xs))
    q = rng.random((50, 2))
    mu, _ = jgp.predict(q)
    rmse = float(np.sqrt(np.mean((mu - f(q)) ** 2)))
    assert rmse < 0.15                     # function range is ~3.0


# ---------------------------------------------------------------------------
# pool posterior reuse: the sd of the last full predict, for equal rows
# against an unchanged factor
# ---------------------------------------------------------------------------


def _same_pool(gp, x, pool, rng):
    return pool


def _observe(gp, x, pool, rng):
    gp.observe(rng.random((2, x.shape[1])))
    return pool


def _set_lengthscale(gp, x, pool, rng):
    gp.set_lengthscale(0.45)
    return pool


def _thin(gp, x, pool, rng):
    gp._thin()
    return pool


def _load_state(gp, x, pool, rng):
    gp.load_state(gp.state_dict())
    return pool


def _signal(gp, x, pool, rng):
    gp.signal = 1.5
    return pool


def _noise(gp, x, pool, rng):
    gp.noise = 2e-3
    return pool


def _other_pool(gp, x, pool, rng):
    return rng.random(pool.shape)


def _other_shape(gp, x, pool, rng):
    return pool[:-1]


@pytest.mark.parametrize("kind", ["jax", "pallas"])
@pytest.mark.parametrize("change", [
    _same_pool, _observe, _set_lengthscale, _thin, _load_state, _signal,
    _noise, _other_pool, _other_shape], ids=lambda f: f.__name__.strip("_"))
def test_pool_posterior_reused_only_for_the_same_rows_and_factor(kind,
                                                                 change):
    """A second predict on the same pool with new targets runs the mean
    alone and equals a fresh GP's full predict; after any change of the
    factor, the hyperparameters or the rows it runs in full again.  The
    pallas GP changes the factor through its own ``_append_active``."""
    rng = np.random.default_rng(7)
    make = {"jax": JaxIncrementalGP, "pallas": PallasIncrementalGP}[kind]
    x = rng.random((28, 4))
    pool = rng.random((20, 4))
    gp = make(inducing_threshold=24).fit_x(x)
    gp.fit_y(rng.random(len(x))).predict(pool)
    gp.fit_y_multi(rng.random((len(x), 2))).predict_multi(pool)
    before = gp.stats()
    assert before["predicts"] == 2 and before["predict_reuses"] == 1
    pool = change(gp, x, pool, rng)
    y = rng.random(gp.n_total)
    mu, sd = gp.fit_y(y).predict(pool)
    after = gp.stats()
    reused = change is _same_pool
    assert after["predicts"] == before["predicts"] + 1
    assert after["predict_reuses"] == before["predict_reuses"] + reused
    fresh = make(lengthscale=gp.ls, noise=gp.noise, signal=gp.signal,
                 inducing_threshold=24)
    fresh.fit_x(gp._ax[gp._active_idx[:len(gp)]])
    mu_f, sd_f = fresh.fit_y(gp._active_targets(y)).predict(pool)
    assert fresh.stats()["predict_reuses"] == 0
    if change in (_signal, _noise):
        # the factor was built with the old hyperparameters: only the miss
        # is asserted, a fresh factor would differ
        return
    np.testing.assert_allclose(mu, mu_f, rtol=1e-12,
                               atol=1e-12 * np.abs(mu_f).max())
    np.testing.assert_allclose(sd, sd_f, rtol=1e-12)


# ---------------------------------------------------------------------------
# staged picks: a block of targets fitted and scored over a pool at once,
# served to the per-pick fit_y and predict
# ---------------------------------------------------------------------------


def _other_targets(gp, x, pool, rng):
    return pool


def _fresh_posterior(make, gp, y, pool):
    fresh = make(lengthscale=gp.ls, noise=gp.noise, signal=gp.signal,
                 inducing_threshold=24)
    fresh.fit_x(gp._ax[gp._active_idx[:len(gp)]])
    return fresh.fit_y(gp._active_targets(y)).predict(pool)


def _assert_close(got, want):
    (mu, sd), (mu_f, sd_f) = got, want
    np.testing.assert_allclose(mu, mu_f, rtol=1e-12,
                               atol=1e-12 * np.abs(mu_f).max())
    np.testing.assert_allclose(sd, sd_f, rtol=1e-12)


@pytest.mark.parametrize("kind", ["jax", "pallas"])
@pytest.mark.parametrize("change", [
    _same_pool, _observe, _set_lengthscale, _thin, _load_state, _signal,
    _noise, _other_pool, _other_shape, _other_targets],
    ids=lambda f: f.__name__.strip("_"))
def test_staged_picks_served_only_for_the_staged_targets_rows_and_factor(
        kind, change):
    """A pick whose targets equal a staged column, over the staged rows,
    against the same factor and hyperparameters, is served from the stage
    and equals a fresh GP's fit and predict; after any change of those, or
    for targets not staged, the pick runs unstaged and still equals it.
    ``staged`` counts the served picks alone."""
    rng = np.random.default_rng(11)
    make = {"jax": JaxIncrementalGP, "pallas": PallasIncrementalGP}[kind]
    x = rng.random((28, 4))
    pool = rng.random((20, 4))
    gp = make(inducing_threshold=24).fit_x(x)
    targets = rng.random((gp.n_total, 4))
    gp.stage(targets, pool)
    first = gp.fit_y(targets[:, 0]).predict(pool)
    _assert_close(first, _fresh_posterior(make, gp, targets[:, 0], pool))
    before = gp.stats()
    assert (before["staged"], before["predicts"],
            before["predict_reuses"]) == (1, 1, 0)
    pool = change(gp, x, pool, rng)
    y = np.concatenate([targets[:, 1],
                        rng.random(gp.n_total - len(targets))])
    if change is _other_targets:
        y = rng.random(gp.n_total)
    got = gp.fit_y(y).predict(pool)
    after = gp.stats()
    served = change is _same_pool
    assert after["staged"] == before["staged"] + served
    assert after["predicts"] == before["predicts"] + 1
    if change in (_signal, _noise):
        # the factor was built with the old hyperparameters: only the miss
        # is asserted, a fresh factor would differ
        return
    _assert_close(got, _fresh_posterior(make, gp, y, pool))


@pytest.mark.parametrize("kind", ["jax", "pallas"])
def test_an_unstaged_pick_on_the_staged_rows_reuses_the_stages_sd(
        kind, monkeypatch):
    """Targets not staged, over the staged rows, run the mean alone and
    take the sd from the stage's one fetch."""
    rng = np.random.default_rng(12)
    make = {"jax": JaxIncrementalGP, "pallas": PallasIncrementalGP}[kind]
    x = rng.random((20, 3))
    pool = rng.random((12, 3))
    gp = make(inducing_threshold=24).fit_x(x)
    fetched = []
    fetch = gp_jax._fetch
    monkeypatch.setattr(gp_jax, "_fetch",
                        lambda a: fetched.append(a.shape) or fetch(a))
    gp.stage(rng.random((20, 2)), pool)
    y = rng.random(20)
    got = gp.fit_y(y).predict(pool)
    assert fetched == [(16, 3), (16, 1)]       # the stage's, then the mean
    s = gp.stats()
    assert (s["staged"], s["predicts"], s["predict_reuses"]) == (0, 1, 1)
    monkeypatch.undo()
    _assert_close(got, _fresh_posterior(make, gp, y, pool))


@pytest.mark.parametrize("kind", ["jax", "pallas"])
def test_a_stage_over_an_empty_pool_serves_empty_picks(kind):
    """A space that has run out leaves a pool of no rows: the stage and
    the picks it serves score nothing and raise nothing."""
    rng = np.random.default_rng(13)
    make = {"jax": JaxIncrementalGP, "pallas": PallasIncrementalGP}[kind]
    gp = make(inducing_threshold=24).fit_x(rng.random((10, 3)))
    pool = np.zeros((0, 3))
    targets = rng.random((10, 4))
    gp.stage(targets, pool)
    for j in range(4):
        mu, sd = gp.fit_y(targets[:, j]).predict(pool)
        assert mu.shape == sd.shape == (0,)
    assert gp.stats()["staged"] == 4


# ---------------------------------------------------------------------------
# pick-sequence equality through the searchers
# ---------------------------------------------------------------------------


def test_bayesopt_jax_picks_match_incremental():
    space = tpu_pod_space(n_chips=256)
    seqs = {}
    for mode in ("incremental", "jax"):
        algo = BayesOpt(space, seed=3, n_init=6, pool_size=64,
                        strategy="ehvi", gp_mode=mode)
        seq = []
        for _ in range(30):
            c = algo.ask(1)[0]
            algo.tell(c, _toy_objectives(space, c))
            seq.append(c)
        seqs[mode] = seq
    assert seqs["jax"] == seqs["incremental"]


@pytest.mark.parametrize("batch,pool_size,rounds", [
    (1, 64, 24), (4, 64, 8), (16, 64, 4), (8, 4, 6)],
    ids=["batch1", "batch4", "batch16", "short_pool"])
def test_bayesopt_parego_jax_picks_match_incremental(batch, pool_size,
                                                     rounds):
    """Staged picks pick what the host GP picks, also where the pool is
    shorter than the batch and the searcher's rng pads the picks."""
    space = tpu_pod_space(n_chips=256)
    seqs = {}
    for mode in ("incremental", "jax"):
        algo = BayesOpt(space, seed=5, n_init=8, pool_size=pool_size,
                        strategy="parego", gp_mode=mode)
        seq, modelled = [], 0
        for _ in range(rounds):
            modelled += batch * (len(algo.history_x) >= algo.n_init)
            picks = algo.ask(batch)
            for c in picks:
                algo.tell(c, _toy_objectives(space, c))
            seq += picks
        seqs[mode] = seq
        if mode == "jax":
            assert algo._gp.stats()["staged"] == modelled
    assert seqs["jax"] == seqs["incremental"]


@pytest.mark.parametrize("n", [4, 16])
def test_parego_ask_fetches_twice_and_serves_every_pick_from_the_stage(
        monkeypatch, n):
    """At steady state a ParEGO ask of n stages its picks: it copies the
    append's flag and one stacked posterior to the host, every pick is
    served from the stage, and all but the first reuse its fetch."""
    space = tpu_pod_space(n_chips=256)
    algo = BayesOpt(space, seed=5, n_init=8, pool_size=64,
                    strategy="parego", gp_mode="jax")
    for _ in range(4):
        for c in algo.ask(n):
            algo.tell(c, _toy_objectives(space, c))
    fetched = []
    fetch = gp_jax._fetch
    monkeypatch.setattr(gp_jax, "_fetch",
                        lambda x: fetched.append(x.shape) or fetch(x))
    before = algo._gp.stats()
    batch = algo.ask(n)
    after = algo._gp.stats()
    assert len(batch) == n
    assert len(fetched) == 2, fetched
    assert fetched[1] == (64, n + 1)
    assert after["predicts"] - before["predicts"] == n
    assert after["predict_reuses"] - before["predict_reuses"] == n - 1
    assert after["staged"] - before["staged"] == n


def test_pal_jax_picks_match_incremental():
    space = tpu_pod_space(n_chips=256)
    seqs = {}
    for mode in ("incremental", "jax"):
        algo = PAL(space, seed=3, n_init=6, pool_size=64, gp_mode=mode)
        seq = []
        for _ in range(20):
            c = algo.ask(1)[0]
            algo.tell(c, _toy_objectives(space, c))
            seq.append(c)
        seqs[mode] = seq
    assert seqs["jax"] == seqs["incremental"]


def test_jax_hyper_refresh_retunes_lengthscale():
    """On a purely linear target the log-ML prefers a larger lengthscale
    than the 0.3 default — the schedule must both fire and actually move
    the hyperparameter on the device buffers."""
    space = tpu_pod_space(n_chips=256)
    algo = BayesOpt(space, seed=3, n_init=6, pool_size=64,
                    strategy="ehvi", gp_mode="jax", hyper_refresh_every=10)
    for _ in range(30):
        c = algo.ask(1)[0]
        x = space.encode(c)
        algo.tell(c, np.array([x[0] + 0.5 * x[1], 1.0 - x[0] + 0.3 * x[2]]))
    assert algo.n_hyper_refreshes >= 2
    # the winner may be an isotropic scalar or a per-dimension ARD vector
    assert np.all(np.atleast_1d(algo._gp.ls) > 0.3)

"""Multi-ball engine (streamsvm_fit_many): one data pass, B models.

Parity sweeps against (a) the same engine fitting each model alone, as a
bank of one, and (b) the pure-jnp bank reference, across (B, N, D, block_n)
including unaligned shapes; bank checkpoint/restart; fit_ovr / fit_c_grid
against the lax.scan reference (core.fit) vmapped over their models.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import fit, fit_ball, fit_bank, fit_c_grid, fit_ovr, ovr_signs
from repro.kernels import streamsvm_fit_many
from repro.kernels.ref import streamsvm_scan_many_ref


def _bank_data(b, n, d, seed):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Y = jnp.asarray(np.sign(rng.normal(size=(b, n))).astype(np.float32))
    cs = jnp.asarray(np.exp(rng.uniform(-1, 4, size=b)).astype(np.float32))
    return X, Y, cs


@pytest.mark.parametrize("b,n,d,block_n", [
    (8, 300, 20, 64),
    (8, 512, 128, 128),
    (11, 257, 33, 64),    # everything unaligned: B, N, D
    (3, 129, 7, 256),     # N < block_n (single padded block)
    (16, 1000, 90, 256),
])
def test_fit_many_matches_per_ball_loop(b, n, d, block_n):
    X, Y, cs = _bank_data(b, n, d, seed=b * n)
    bank = streamsvm_fit_many(X, Y, cs, block_n=block_n)
    assert bank.w.shape == (b, d)
    for i in range(b):
        alone = streamsvm_fit_many(X, Y[i : i + 1], cs[i : i + 1], block_n=block_n)
        single = jax.tree.map(lambda v: v[0], alone)
        np.testing.assert_allclose(
            np.asarray(bank.w[i]), np.asarray(single.w), rtol=2e-4, atol=2e-5
        )
        np.testing.assert_allclose(float(bank.r[i]), float(single.r), rtol=1e-4)
        np.testing.assert_allclose(
            float(bank.xi2[i]), float(single.xi2), rtol=1e-3, atol=1e-6
        )
        assert int(bank.m[i]) == int(single.m)


@pytest.mark.parametrize("b,n,d,block_n", [
    (8, 400, 24, 128),
    (5, 333, 17, 64),
])
@pytest.mark.parametrize("variant", ["exact", "paper-listing"])
def test_fit_many_matches_bank_ref(b, n, d, block_n, variant):
    X, Y, cs = _bank_data(b, n, d, seed=7 * b + n)
    bank = streamsvm_fit_many(X, Y, cs, variant=variant, block_n=block_n)
    c_inv = 1.0 / cs
    gain = c_inv if variant == "exact" else jnp.ones_like(c_inv)
    W0 = Y[:, 0:1] * X[0][None, :]
    w, r, xi2, m = streamsvm_scan_many_ref(
        X[1:], Y[:, 1:], W0, 0.0, gain, c_inv, 1, gain=gain
    )
    np.testing.assert_allclose(np.asarray(bank.w), np.asarray(w), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(bank.r), np.asarray(r), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(bank.xi2), np.asarray(xi2), rtol=1e-3, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(bank.m), np.asarray(m))


def test_bank_restart_equals_continuous_pass():
    """Mid-stream bank checkpoint/resume == one continuous pass."""
    b, n, d = 9, 514, 41
    X, Y, cs = _bank_data(b, n, d, seed=99)
    full = streamsvm_fit_many(X, Y, cs, block_n=64)
    for cut in (1, 200, 257, 513):
        head = streamsvm_fit_many(X[:cut], Y[:, :cut], cs, block_n=64)
        rest = streamsvm_fit_many(X[cut:], Y[:, cut:], cs, head, block_n=64)
        np.testing.assert_allclose(
            np.asarray(rest.w), np.asarray(full.w), rtol=2e-4, atol=2e-5
        )
        np.testing.assert_array_equal(np.asarray(rest.m), np.asarray(full.m))


def test_block_size_invariance():
    """The engine result must not depend on the HBM tiling."""
    X, Y, cs = _bank_data(8, 500, 30, seed=5)
    ref = streamsvm_fit_many(X, Y, cs, block_n=32)
    for block_n in (64, 128, 256):
        bank = streamsvm_fit_many(X, Y, cs, block_n=block_n)
        np.testing.assert_allclose(
            np.asarray(bank.w), np.asarray(ref.w), rtol=2e-4, atol=2e-5
        )
        np.testing.assert_array_equal(np.asarray(bank.m), np.asarray(ref.m))


def test_fit_ovr_engine_matches_scan_path():
    rng = np.random.default_rng(17)
    X = jnp.asarray(rng.normal(size=(600, 12)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 8, size=600))
    be = fit_ovr(X, labels, 8, 10.0)
    bs = jax.vmap(lambda yv: fit(X, yv, 10.0))(ovr_signs(labels, 8))
    np.testing.assert_allclose(np.asarray(be.w), np.asarray(bs.w), rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(be.m), np.asarray(bs.m))


def test_fit_c_grid_engine_matches_scan_path():
    rng = np.random.default_rng(23)
    X = jnp.asarray(rng.normal(size=(700, 19)).astype(np.float32))
    y = jnp.asarray(np.sign(rng.normal(size=700) + X[:, 0]))
    grid = jnp.asarray([0.5, 1.0, 10.0, 100.0, 1000.0])
    ge = fit_c_grid(X, y, grid)
    gs = jax.vmap(lambda c: fit(X, y, c))(grid)
    np.testing.assert_allclose(np.asarray(ge.w), np.asarray(gs.w), rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(ge.m), np.asarray(gs.m))


def test_fit_bank_continues_from_single_model_states():
    """A bank assembled from heterogeneous per-model states keeps each lane
    independent (no cross-model leakage through the shared Gram tile)."""
    from repro.core import bank_stack

    rng = np.random.default_rng(31)
    X = jnp.asarray(rng.normal(size=(400, 16)).astype(np.float32))
    Y = jnp.asarray(np.sign(rng.normal(size=(8, 400))).astype(np.float32))
    cs = jnp.asarray([0.3, 1.0, 3.0, 10.0, 30.0, 100.0, 300.0, 1000.0])
    singles = [fit(X[:150], Y[i, :150], float(cs[i])) for i in range(8)]
    bank = fit_bank(X[150:], Y[:, 150:], cs, bank_stack(singles))
    for i in range(8):
        cont = fit_ball(singles[i], X[150:], Y[i, 150:], float(cs[i]))
        np.testing.assert_allclose(
            np.asarray(bank.w[i]), np.asarray(cont.w), rtol=2e-4, atol=2e-5
        )
        assert int(bank.m[i]) == int(cont.m)

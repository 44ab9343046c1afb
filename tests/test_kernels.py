"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp ref.

One model trains through the bank engine as a bank of one (Y = y[None]).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bank_take, fit, fit_ball, fit_bank
from repro.kernels import gram
from repro.kernels.ref import gram_ref, streamsvm_scan_ref


@pytest.mark.parametrize("n,d,block_n", [
    (64, 16, 32),
    (500, 100, 128),
    (1000, 300, 256),
    (257, 129, 64),     # deliberately unaligned
])
def test_streamsvm_kernel_vs_ref(n, d, block_n):
    rng = np.random.default_rng(n + d)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = jnp.asarray(np.sign(rng.normal(size=n)).astype(np.float32))
    ball = bank_take(fit_bank(X, y[None], 7.0, block_n=block_n), 0)
    w, r, xi2, m = streamsvm_scan_ref(
        X[1:], y[1:], y[0] * X[0], 0.0, 1.0 / 7.0, 1.0 / 7.0, 1
    )
    np.testing.assert_allclose(np.asarray(ball.w), np.asarray(w), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(ball.r), float(r), rtol=1e-4)
    np.testing.assert_allclose(float(ball.xi2), float(xi2), rtol=1e-3, atol=1e-6)
    assert int(ball.m) == int(m)


def test_streamsvm_kernel_equals_core_fit():
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(777, 90)).astype(np.float32))
    y = jnp.asarray(np.sign(rng.normal(size=777)).astype(np.float32))
    bk = bank_take(fit_bank(X, y[None], 3.0), 0)
    bc = fit(X, y, 3.0)
    np.testing.assert_allclose(np.asarray(bk.w), np.asarray(bc.w), rtol=2e-4, atol=2e-5)
    assert int(bk.m) == int(bc.m)


@pytest.mark.parametrize("m,n,d", [(64, 64, 128), (100, 513, 300), (8, 1024, 512)])
@pytest.mark.parametrize("epilogue", ["linear", "rbf"])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_gram_kernel_vs_ref(m, n, d, epilogue, dtype):
    rng = np.random.default_rng(m * n)
    A = jnp.asarray(rng.normal(size=(m, d)).astype(dtype))
    B = jnp.asarray(rng.normal(size=(n, d)).astype(dtype))
    K1 = gram(A, B, epilogue=epilogue, gamma=0.05, bk=128)
    K2 = gram_ref(A, B, epilogue=epilogue, gamma=0.05)
    np.testing.assert_allclose(np.asarray(K1), np.asarray(K2), rtol=2e-3, atol=2e-3)


def test_streamsvm_kernel_continues_from_ball():
    """Kernel restart mid-stream from a reference ball == the reference's
    one continuous pass."""
    rng = np.random.default_rng(5)
    X = jnp.asarray(rng.normal(size=(512, 64)).astype(np.float32))
    y = jnp.asarray(np.sign(rng.normal(size=512)).astype(np.float32))
    b_half = fit(X[:256], y[:256], 5.0)
    half = jax.tree.map(lambda v: jnp.asarray(v)[None], b_half)
    b_rest = bank_take(fit_bank(X[256:], y[None, 256:], 5.0, half), 0)
    b_full = fit_ball(b_half, X[256:], y[256:], 5.0)
    np.testing.assert_allclose(np.asarray(b_rest.w), np.asarray(b_full.w), rtol=2e-4, atol=2e-5)
    assert int(b_rest.m) == int(b_full.m)


def test_interpret_mode_is_resolved_in_one_place():
    """ops.resolve_interpret decides interpret mode for every kernel: the
    interpreter exactly off-TPU by default, and an explicit choice kept
    where it is legal (on a TPU, interpret=True is refused)."""
    import jax

    from repro.kernels.ops import resolve_interpret

    on_tpu = jax.default_backend() == "tpu"
    assert resolve_interpret(None) is (not on_tpu)
    assert resolve_interpret(False) is False
    if not on_tpu:
        assert resolve_interpret(True) is True

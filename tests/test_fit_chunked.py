"""fit_chunked / fit_chunked_many streaming-driver semantics.

Chunked + resumed runs must equal a single fit over the concatenated stream
(lookahead=1 exactly; lookahead>1 up to the documented chunk-boundary flush),
and the bank driver must carry the whole bank through checkpoint/resume.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    fit,
    fit_c_grid,
    fit_chunked,
    fit_chunked_many,
    fit_lookahead_ball,
    init_ball,
    ovr_signs,
)
from repro.data.stream import chunk_stream


def _data(n, d, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = np.sign(rng.normal(size=n) + X[:, 0]).astype(np.float32)
    y[y == 0] = 1
    return X, y


@pytest.mark.parametrize("chunk", [64, 100, 256, 1000, 2048])
def test_chunked_equals_fit_any_chunking(chunk):
    """Chunk size (incl. ragged final chunks and chunk > N) must not matter."""
    X, y = _data(1000, 6, 0)
    full = fit(jnp.asarray(X), jnp.asarray(y), 10.0)
    ck = fit_chunked(chunk_stream(X, y, chunk), 10.0)
    np.testing.assert_allclose(
        np.asarray(ck.ball.w), np.asarray(full.w), rtol=1e-4, atol=1e-5
    )
    assert int(ck.ball.m) == int(full.m)
    assert ck.position == 1000


@pytest.mark.parametrize("ckpt_every", [100, 333, 512])
def test_chunked_resume_equals_fit(ckpt_every):
    """Preempt at any checkpoint: resumed run == single fit on the full stream."""
    X, y = _data(900, 5, 1)
    full = fit(jnp.asarray(X), jnp.asarray(y), 5.0)
    saved = []
    fit_chunked(
        chunk_stream(X, y, 100), 5.0,
        checkpoint_every=ckpt_every, checkpoint_cb=saved.append,
    )
    assert saved, "no checkpoint emitted"
    first = saved[0]
    assert first.position < 900
    rest = fit_chunked(
        chunk_stream(X, y, 100, start=first.position), 5.0, resume=first
    )
    np.testing.assert_allclose(
        np.asarray(rest.ball.w), np.asarray(full.w), rtol=1e-4, atol=1e-5
    )
    assert int(rest.ball.m) == int(full.m)
    assert rest.position == 900


def test_chunked_lookahead_boundary_flush_semantics():
    """lookahead>1 flushes its violator buffer at every chunk boundary; the
    driver must equal manually applying fit_lookahead_ball chunk by chunk."""
    X, y = _data(640, 7, 2)
    L, c, chunk = 4, 10.0, 160
    ck = fit_chunked(chunk_stream(X, y, chunk), c, lookahead=L)

    ball = init_ball(jnp.asarray(X[0]), jnp.asarray(y[0]), c)
    first = True
    for Xc, yc in chunk_stream(X, y, chunk):
        Xc, yc = jnp.asarray(Xc), jnp.asarray(yc)
        if first:
            Xc, yc = Xc[1:], yc[1:]
            first = False
        ball = fit_lookahead_ball(ball, Xc, yc, c, L)
    np.testing.assert_allclose(
        np.asarray(ck.ball.w), np.asarray(ball.w), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(float(ck.ball.r), float(ball.r), rtol=1e-4)
    assert int(ck.ball.m) == int(ball.m)


def test_chunked_lookahead_resume_equals_continuous_chunked():
    """With lookahead>1, resume from a checkpoint == the continuous chunked
    run over the same boundaries (flush state is part of the contract)."""
    X, y = _data(800, 6, 3)
    L, c, chunk = 5, 10.0, 200
    cont = fit_chunked(chunk_stream(X, y, chunk), c, lookahead=L)
    saved = []
    fit_chunked(
        chunk_stream(X, y, chunk), c, lookahead=L,
        checkpoint_every=400, checkpoint_cb=saved.append,
    )
    first = saved[0]
    rest = fit_chunked(
        chunk_stream(X, y, chunk, start=first.position), c,
        lookahead=L, resume=first,
    )
    np.testing.assert_allclose(
        np.asarray(rest.ball.w), np.asarray(cont.ball.w), rtol=1e-5, atol=1e-6
    )
    assert int(rest.ball.m) == int(cont.ball.m)


def test_chunked_many_grid_resume_equals_full_grid():
    """Bank driver: chunked + resumed C-grid == one-call grid fit; the
    checkpoint carries the whole bank (O(B*D) state)."""
    X, y = _data(700, 9, 4)
    cs = jnp.asarray([1.0, 10.0, 100.0])
    full = fit_c_grid(jnp.asarray(X), jnp.asarray(y), cs)
    saved = []
    fit_chunked_many(
        chunk_stream(X, y, 128), cs,
        checkpoint_every=256, checkpoint_cb=saved.append,
    )
    first = saved[0]
    assert first.ball.w.shape == (3, 9)
    rest = fit_chunked_many(
        chunk_stream(X, y, 128, start=first.position), cs, resume=first
    )
    np.testing.assert_allclose(
        np.asarray(rest.ball.w), np.asarray(full.w), rtol=2e-4, atol=2e-5
    )
    np.testing.assert_array_equal(np.asarray(rest.ball.m), np.asarray(full.m))
    assert rest.position == 700


def test_chunked_many_ovr_sign_rows():
    """(B, n) per-model sign chunks (one-vs-rest) stream correctly."""
    rng = np.random.default_rng(5)
    X = rng.normal(size=(500, 8)).astype(np.float32)
    labels = rng.integers(0, 4, size=500)
    Y = np.asarray(ovr_signs(jnp.asarray(labels), 4))
    cs = jnp.full((4,), 10.0)

    def chunks():
        for lo in range(0, 500, 125):
            yield X[lo : lo + 125], Y[:, lo : lo + 125]

    out = fit_chunked_many(chunks(), cs)
    for k in range(4):
        single = fit(jnp.asarray(X), jnp.asarray(Y[k]), 10.0)
        np.testing.assert_allclose(
            np.asarray(out.ball.w[k]), np.asarray(single.w), rtol=2e-4, atol=2e-5
        )
        assert int(out.ball.m[k]) == int(single.m)

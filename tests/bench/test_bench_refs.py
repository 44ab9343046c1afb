"""The copied float64 references against direct computations."""
import numpy as np
import pytest

from benchmarks.chip.refs import bank_ref


def _stream(seed, n=300, d=6, k=4):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    Y = np.where(rng.random((k, n)) < 0.3, 1.0, -1.0).astype(np.float32)
    cs = np.array([0.5, 1.0, 10.0, 100.0][:k])
    return X, Y, cs


def _alg1_loop(X, Y, c):
    """Algorithm 1 for one model, one row at a time, in float64."""
    X = X.astype(np.float64)
    w, r, xi2, m = Y[0] * X[0], 0.0, 1.0 / c, 1
    for i in range(1, len(X)):
        row = Y[i] * X[i]
        d = np.sqrt(max(np.sum((w - row) ** 2) + xi2 + 1.0 / c, 1e-12))
        if d >= r and Y[i] != 0:
            s = 0.5 * (1.0 - r / d)
            w = (1 - s) * w + s * row
            r = r + 0.5 * (d - r)
            xi2 = xi2 * (1 - s) ** 2 + s * s / c
            m += 1
    return w, r, xi2, m


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_alg1_ref_matches_a_per_model_loop(seed):
    X, Y, cs = _stream(seed)
    w, r, xi2, m = bank_ref.alg1_ref(X, Y, cs)
    for b in range(len(cs)):
        wb, rb, xb, mb = _alg1_loop(X, Y[b], cs[b])
        np.testing.assert_allclose(w[b], wb, rtol=1e-12, atol=1e-12)
        assert r[b] == pytest.approx(rb, rel=1e-12)
        assert xi2[b] == pytest.approx(xb, rel=1e-12)
        assert m[b] == mb


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_blocked_reference_equals_the_row_loop(seed):
    X, Y, cs = _stream(seed, n=2000)
    Y[1, 500:520] = 0  # inert rows for one model
    want = bank_ref.alg1_ref(X, Y, cs)
    got = bank_ref.alg1_blocked(X, Y, cs)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-10, atol=1e-12)


def test_merge_encloses_both_balls():
    X, Y, cs = _stream(3, n=800)
    a = bank_ref.alg1_ref(X[:400], Y[:, :400], cs)
    b = bank_ref.alg1_ref(X[400:], Y[:, 400:], cs)
    w, r, xi2, m = bank_ref.merge_ref(a, b)
    for wa, ra, xa, _ in (a, b):
        # the merged center lies on the segment between the two centers, so
        # the slack part of its distance to either is (sqrt(xi2) - ...)^2;
        # enclosing means dist + r_part <= r, checked through the radius
        # growth bound r >= max(r_a, r_b)
        assert np.all(r + 1e-12 >= ra)
    dist = np.sqrt(np.sum((a[0] - b[0]) ** 2, axis=1) + a[2] + b[2])
    assert np.all(r <= 0.5 * (a[1] + b[1] + dist) + 1e-12)
    np.testing.assert_array_equal(m, a[3] + b[3])


def test_sharded_reference_folds_per_range_fits_in_order():
    X, Y, cs = _stream(4, n=1000)
    got = bank_ref.sharded_ref(X, Y, cs, 4)
    ranges = bank_ref.shard_bounds(1000, 4)
    assert ranges == [(0, 250), (250, 500), (500, 750), (750, 1000)]
    banks = [bank_ref.alg1_ref(X[lo:hi], Y[:, lo:hi], cs) for lo, hi in ranges]
    want = banks[0]
    for bk in banks[1:]:
        want = bank_ref.merge_ref(want, bk)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-10, atol=1e-12)
    one = bank_ref.sharded_ref(X, Y, cs, 1)
    for a, b in zip(one, bank_ref.alg1_ref(X, Y, cs)):
        np.testing.assert_allclose(a, b, rtol=1e-10, atol=1e-12)


def test_fit_errors_reads_the_worst_model():
    w = np.ones((3, 4))
    ref = (w, np.ones(3), np.ones(3), np.array([10, 10, 10]))
    got = (w.copy(), np.array([1.0, 1.0, 1.5]), np.ones(3), np.array([10, 11, 10]))
    got[0][1] *= 1.01
    e = bank_ref.fit_errors(got, ref)
    assert e["center_err"] == pytest.approx(0.01)
    assert e["radius_err"] == pytest.approx(0.5)
    assert e["count_err"] == pytest.approx(0.1)


def test_ovr_errors_against_a_direct_readout():
    rng = np.random.default_rng(5)
    K, G, D, q = 5, 2, 8, 30
    W = rng.standard_normal((G * K, D))
    Xq = rng.standard_normal((q, D))
    S = Xq @ W.T
    cls = np.stack([np.argmax(S[:, g * K:(g + 1) * K], 1) for g in range(G)], 1)
    margin = np.stack([np.max(S[:, g * K:(g + 1) * K], 1) for g in range(G)], 1)
    e = bank_ref.ovr_errors(cls, margin.astype(np.float32), Xq, W, K, 1e-6)
    assert e["wrong_classes"] == 0 and e["margin_err"] < 1e-7
    bad = cls.copy()
    bad[0] = (bad[0] + 1) % K
    assert bank_ref.ovr_errors(bad, margin, Xq, W, K, 1e-6)["wrong_classes"] >= 1


def test_topk_errors_against_a_direct_readout():
    rng = np.random.default_rng(7)
    W, Xq, k = rng.standard_normal((20, 8)), rng.standard_normal((30, 8)), 3
    S = Xq @ W.T
    ids = np.argsort(-S, axis=1)[:, :k]
    vals = np.take_along_axis(S, ids, axis=1).astype(np.float32)
    e = bank_ref.topk_errors(vals, ids, Xq, W, k, 1e-6)
    assert e["wrong_ids"] == 0 and e["score_err"] < 1e-7
    bad = ids.copy()
    bad[:, [0, 1]] = bad[:, [1, 0]]
    assert bank_ref.topk_errors(vals, bad, Xq, W, k, 1e-6)["wrong_ids"] >= 1


def test_rbf_scores_ref_matches_a_loop():
    rng = np.random.default_rng(6)
    Xq, P, coef = (rng.standard_normal((4, 3)), rng.standard_normal((2, 5, 3)),
                   rng.standard_normal((2, 5)))
    want = np.array([[sum(coef[b, s] * np.exp(-0.5 * np.sum((x - P[b, s]) ** 2))
                          for s in range(5)) for b in range(2)] for x in Xq])
    np.testing.assert_allclose(bank_ref.rbf_scores_ref(Xq, P, coef, 0.5), want,
                               rtol=1e-12)

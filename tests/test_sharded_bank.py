"""Sharded bank engine: M stream shards x B models in one pass + merge suite.

Three layers:

1. FAST, no devices needed — the engine's sign-0 inert-row contract (the
   padding primitive ``fit_bank_sharded`` is built on) and the bank-
   vectorized ``fold_merge`` (vmap dispatch, live-mask skipping, bank-axis
   equivariance, agreement with an EXPLICIT augmented-space embedding that
   tracks every slack coordinate — the oracle the implicit xi2 recursion is
   checked against).

2. Property tests (optional ``hypothesis`` dependency, like
   test_core_streamsvm_properties.py): permutation-invariance and
   associativity of the merge up to its PROVABLE geometric slack. The fold
   is not pointwise order-independent — but every fold order must (a) agree
   with the explicit embedding, (b) enclose every input ball, (c) land its
   center in the convex hull of the input centers (so any two orders are
   within min(r_a, r_b) of each other), and (d) have radius in
   [R*, 2 R*] for the same R*, so any two orders' radii are within 2x.
   (a)-(d) are theorems, not tuning, so the tests cannot flake under
   hypothesis shrinking.

3. SLOW, 8 host devices (the CI slow job exports
   XLA_FLAGS=--xla_force_host_platform_device_count=8; locally run
   ``XLA_FLAGS=... pytest -m slow tests/test_sharded_bank.py``):
   shard-count invariance of ``fit_bank_sharded`` against the manually
   folded ragged ranges (exact + lookahead, N % n_shards != 0,
   B % b_tile != 0, fully-dead shards), statistical parity with the
   single-device ``fit_bank``, mesh routing of fit_ovr / fit_c_grid /
   fit_chunked_many, and checkpoint/resume under a mesh including an
   elastic reshard.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import fit_bank, fold_merge, merge_balls, merge_banks
from repro.core.meb import Ball

try:
    import hypothesis  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _bank_data(b, n, d, seed):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Y = jnp.asarray(np.sign(rng.normal(size=(b, n))).astype(np.float32))
    cs = jnp.asarray(np.exp(rng.uniform(-1, 3, size=b)).astype(np.float32))
    return X, Y, cs


def _random_balls(s, b, d, seed):
    """(s,) stacked banks of b models in d dims with positive r / xi2."""
    rng = np.random.default_rng(seed)
    return Ball(
        w=jnp.asarray(rng.normal(size=(s, b, d)).astype(np.float32)),
        r=jnp.asarray(np.abs(rng.normal(size=(s, b))).astype(np.float32)),
        xi2=jnp.asarray(
            (0.01 + np.abs(rng.normal(size=(s, b)))).astype(np.float32)
        ),
        m=jnp.asarray(rng.integers(1, 50, size=(s, b)).astype(np.int32)),
    )


def _explicit_embed(ws, rs, xi2s):
    """Embed S balls with mutually-orthogonal slack blocks explicitly.

    Ball i's slack block is one coordinate (D + i) carrying norm sqrt(xi2_i)
    — a faithful model of disjoint per-shard slack (meb.py docstring).
    Returns (centers (S, D+S), radii (S,)).
    """
    s, d = len(ws), len(ws[0])
    cs = np.zeros((s, d + s), np.float64)
    for i in range(s):
        cs[i, :d] = ws[i]
        cs[i, d + i] = np.sqrt(xi2s[i])
    return cs, np.asarray(rs, np.float64)


def _emerge(c1, r1, c2, r2):
    """merge_balls in explicit coordinates (the numpy oracle)."""
    d = float(np.linalg.norm(c1 - c2))
    if d + r1 <= r2:
        return c2.copy(), r2
    if d + r2 <= r1:
        return c1.copy(), r1
    rj = 0.5 * (r1 + r2 + d)
    t = np.clip((rj - r1) / max(d, 1e-12), 0.0, 1.0)
    return c1 + t * (c2 - c1), rj


def _explicit_fold(centers, radii, order):
    c, r = centers[order[0]].copy(), radii[order[0]]
    for i in order[1:]:
        c, r = _emerge(c, r, centers[i], radii[i])
    return c, r


def _implicit_fold_single(ws, rs, xi2s, order):
    """fold_merge on stacked single balls in the given order."""
    stacked = Ball(
        w=jnp.asarray(np.stack([ws[i] for i in order]), jnp.float32),
        r=jnp.asarray([rs[i] for i in order], jnp.float32),
        xi2=jnp.asarray([xi2s[i] for i in order], jnp.float32),
        m=jnp.ones(len(order), jnp.int32),
    )
    return fold_merge(stacked)


def _check_fold_properties(ws, rs, xi2s, orders, atol=1e-4):
    """Assert the provable merge-fold properties for every order given."""
    centers, radii = _explicit_embed(ws, rs, xi2s)
    scale = max(1.0, float(np.max(np.abs(centers))), float(np.max(radii)))
    tol = atol * scale
    folds = []
    for order in orders:
        c_e, r_e = _explicit_fold(centers, radii, order)
        ball = _implicit_fold_single(ws, rs, xi2s, order)
        # (a) implicit xi2 recursion == explicit slack embedding
        np.testing.assert_allclose(
            np.asarray(ball.w), c_e[: len(ws[0])], rtol=1e-4, atol=tol
        )
        np.testing.assert_allclose(float(ball.r), r_e, rtol=1e-4, atol=tol)
        np.testing.assert_allclose(
            float(ball.xi2),
            float(np.sum(c_e[len(ws[0]):] ** 2)),
            rtol=1e-3,
            atol=tol,
        )
        # (b) enclosure: the fold contains every input ball
        for i in range(len(radii)):
            gap = np.linalg.norm(c_e - centers[i]) + radii[i] - r_e
            assert gap <= tol, (order, i, gap)
        folds.append((c_e, r_e))
    # (c) any two orders: centers within min radius of each other
    # (d) radii within the provable 2x band around R*
    for a in range(len(folds)):
        for b_ in range(a + 1, len(folds)):
            (ca, ra), (cb, rb) = folds[a], folds[b_]
            dist = np.linalg.norm(ca - cb)
            assert dist <= min(ra, rb) + tol, (dist, ra, rb)
            assert max(ra, rb) <= 2.0 * min(ra, rb) + tol, (ra, rb)


# ---------------------------------------------------------------------------
# FAST: engine padding contract (sign-0 rows are exact no-ops)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant,lookahead", [("exact", None), ("lookahead", 4)])
def test_sign0_rows_are_inert(variant, lookahead):
    """Appending (0-feature, 0-sign) rows — fit_bank_sharded's remainder
    padding — must not change a single bit of any model."""
    b, n, d, pad = 6, 257, 12, 31
    X, Y, cs = _bank_data(b, n, d, seed=3)
    plain = fit_bank(X, Y, cs, variant=variant, lookahead=lookahead, block_n=64)
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    Yp = jnp.pad(Y, ((0, 0), (0, pad)))
    padded = fit_bank(Xp, Yp, cs, variant=variant, lookahead=lookahead, block_n=64)
    np.testing.assert_array_equal(np.asarray(padded.w), np.asarray(plain.w))
    np.testing.assert_array_equal(np.asarray(padded.r), np.asarray(plain.r))
    np.testing.assert_array_equal(np.asarray(padded.xi2), np.asarray(plain.xi2))
    np.testing.assert_array_equal(np.asarray(padded.m), np.asarray(plain.m))


def test_sign0_rows_inert_in_ref_oracles():
    """The ref.py oracles honor the same contract (they anchor the kernel)."""
    from repro.kernels.ref import (
        streamsvm_scan_lookahead_ref,
        streamsvm_scan_ref,
    )

    rng = np.random.default_rng(9)
    X = rng.normal(size=(40, 5)).astype(np.float32)
    y = np.sign(rng.normal(size=40)).astype(np.float32)
    y[y == 0] = 1
    Xp = np.concatenate([X, rng.normal(size=(7, 5)).astype(np.float32)])
    yp = np.concatenate([y, np.zeros(7, np.float32)])
    for fn in (
        lambda X_, y_: streamsvm_scan_ref(X_, y_, y_[0] * X_[0], 0.0, 0.1, 0.1, 1),
        lambda X_, y_: streamsvm_scan_lookahead_ref(
            X_, y_, y_[0] * X_[0], 0.0, 0.1, 0.1, 1, 3
        ),
    ):
        w0, r0, xi0, m0 = fn(jnp.asarray(X), jnp.asarray(y))
        w1, r1, xi1, m1 = fn(jnp.asarray(Xp), jnp.asarray(yp))
        np.testing.assert_allclose(np.asarray(w1), np.asarray(w0), rtol=1e-6)
        assert int(m1) == int(m0)


# ---------------------------------------------------------------------------
# FAST: bank-vectorized fold_merge
# ---------------------------------------------------------------------------


def test_fold_merge_bank_matches_per_model_fold():
    """Folding an (S, B, ...) stack == independently folding each model lane."""
    s, b, d = 5, 7, 9
    banks = _random_balls(s, b, d, seed=11)
    folded = fold_merge(banks)
    assert folded.w.shape == (b, d)
    for k in range(b):
        lane = jax.tree.map(lambda x: x[:, k], banks)
        one = fold_merge(lane)
        np.testing.assert_allclose(
            np.asarray(folded.w[k]), np.asarray(one.w), rtol=1e-6, atol=1e-7
        )
        np.testing.assert_allclose(float(folded.r[k]), float(one.r), rtol=1e-6)
        np.testing.assert_allclose(
            float(folded.xi2[k]), float(one.xi2), rtol=1e-5
        )
        assert int(folded.m[k]) == int(one.m)


@pytest.mark.parametrize("live", [None, (True, False, True, True)])
def test_fold_merge_compiles_once_per_shape(live, caplog):
    """Folding new values of a shape it has folded before compiles nothing:
    the sharded fit folds once a pass, and a compile there costs more than
    the fold's work."""
    import logging

    live = None if live is None else jnp.asarray(live)
    fold_merge(_random_balls(4, 3, 8, seed=41), live)
    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        caplog.clear()
        jax.block_until_ready(fold_merge(_random_balls(4, 3, 8, seed=42), live))
    assert not [r for r in caplog.records if "Compiling" in r.getMessage()]


def test_fold_merge_bank_axis_permutation_equivariance():
    """Model lanes never interact: permuting B commutes with the fold."""
    banks = _random_balls(4, 6, 5, seed=21)
    perm = np.asarray([3, 0, 5, 1, 4, 2])
    direct = fold_merge(banks)
    permuted = fold_merge(jax.tree.map(lambda x: x[:, perm], banks))
    np.testing.assert_allclose(
        np.asarray(permuted.w), np.asarray(direct.w)[perm], rtol=1e-6, atol=1e-7
    )
    np.testing.assert_allclose(
        np.asarray(permuted.r), np.asarray(direct.r)[perm], rtol=1e-6
    )


def test_fold_merge_live_mask_skips_dead_entries():
    """Masked-out shards must be skipped EXACTLY (bit-equal to slicing them
    out) — this is what makes remainder padding shard-count invariant."""
    banks = _random_balls(6, 3, 4, seed=31)
    live = jnp.asarray([True, True, False, True, False, True])
    masked = fold_merge(banks, live=live)
    sliced = fold_merge(jax.tree.map(lambda x: x[np.asarray(live)], banks))
    np.testing.assert_allclose(
        np.asarray(masked.w), np.asarray(sliced.w), rtol=1e-6, atol=1e-7
    )
    np.testing.assert_allclose(
        np.asarray(masked.r), np.asarray(sliced.r), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(masked.xi2), np.asarray(sliced.xi2), rtol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(masked.m), np.asarray(sliced.m))


def test_fold_merge_dead_entry_zero():
    """A dead entry 0 must not contaminate the fold — the fold starts at the
    first LIVE entry (entry 0 could be a garbage placeholder ball)."""
    banks = _random_balls(5, 3, 4, seed=33)
    # poison entry 0 so any accidental inclusion is loud
    banks = Ball(
        w=banks.w.at[0].set(jnp.inf), r=banks.r, xi2=banks.xi2, m=banks.m
    )
    live = jnp.asarray([False, True, False, True, True])
    masked = fold_merge(banks, live=live)
    sliced = fold_merge(jax.tree.map(lambda x: x[np.asarray(live)], banks))
    assert np.isfinite(np.asarray(masked.w)).all()
    np.testing.assert_allclose(
        np.asarray(masked.w), np.asarray(sliced.w), rtol=1e-6, atol=1e-7
    )
    np.testing.assert_array_equal(np.asarray(masked.m), np.asarray(sliced.m))


def test_merge_banks_is_vmapped_merge_balls():
    b1 = jax.tree.map(lambda x: x[0], _random_balls(1, 5, 6, seed=41))
    b2 = jax.tree.map(lambda x: x[0], _random_balls(1, 5, 6, seed=42))
    out = merge_banks(b1, b2)
    for k in range(5):
        one = merge_balls(
            jax.tree.map(lambda x: x[k], b1), jax.tree.map(lambda x: x[k], b2)
        )
        np.testing.assert_allclose(
            np.asarray(out.w[k]), np.asarray(one.w), rtol=1e-6, atol=1e-7
        )
        np.testing.assert_allclose(float(out.r[k]), float(one.r), rtol=1e-6)


def test_merge_is_commutative():
    a = jax.tree.map(lambda x: x[0, 0], _random_balls(1, 1, 8, seed=51))
    b = jax.tree.map(lambda x: x[0, 0], _random_balls(1, 1, 8, seed=52))
    ab, ba = merge_balls(a, b), merge_balls(b, a)
    np.testing.assert_allclose(np.asarray(ab.w), np.asarray(ba.w), rtol=1e-6)
    np.testing.assert_allclose(float(ab.r), float(ba.r), rtol=1e-6)
    np.testing.assert_allclose(float(ab.xi2), float(ba.xi2), rtol=1e-5)


def test_fold_properties_deterministic():
    """Fixed-seed equivalent of the hypothesis properties (coverage must not
    depend on the optional dependency — repo convention)."""
    rng = np.random.default_rng(61)
    s, d = 5, 6
    ws = [rng.normal(size=d).astype(np.float32) for _ in range(s)]
    rs = [float(abs(rng.normal())) for _ in range(s)]
    xi2s = [float(0.01 + abs(rng.normal())) for _ in range(s)]
    orders = [list(range(s)), list(range(s))[::-1], [2, 0, 4, 1, 3]]
    _check_fold_properties(ws, rs, xi2s, orders)


# ---------------------------------------------------------------------------
# Property tests (optional hypothesis dependency)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        s=st.integers(2, 6),
        d=st.integers(1, 8),
        seed=st.integers(0, 10_000),
    )
    def test_fold_merge_permutation_invariant_up_to_tolerance(s, d, seed):
        """Any shard order: same explicit-embedding semantics, encloses all
        inputs, centers within min(r) of each other, radii within 2x."""
        rng = np.random.default_rng(seed)
        ws = [rng.normal(size=d).astype(np.float32) for _ in range(s)]
        rs = [float(abs(rng.normal())) for _ in range(s)]
        xi2s = [float(0.01 + abs(rng.normal())) for _ in range(s)]
        orders = [list(range(s))] + [
            list(rng.permutation(s)) for _ in range(3)
        ]
        _check_fold_properties(ws, rs, xi2s, orders)

    @settings(max_examples=25, deadline=None)
    @given(d=st.integers(1, 8), seed=st.integers(0, 10_000))
    def test_merge_associative_up_to_tolerance(d, seed):
        """merge(merge(a,b),c) vs merge(a,merge(b,c)): both enclose {a,b,c},
        centers within min radius, radii within the provable 2x band."""
        rng = np.random.default_rng(seed)
        ws = [rng.normal(size=d).astype(np.float32) for _ in range(3)]
        rs = [float(abs(rng.normal())) for _ in range(3)]
        xi2s = [float(0.01 + abs(rng.normal())) for _ in range(3)]
        centers, radii = _explicit_embed(ws, rs, xi2s)
        scale = max(1.0, float(np.max(np.abs(centers))), float(np.max(radii)))
        tol = 1e-4 * scale
        cl, rl = _explicit_fold(centers, radii, [0, 1, 2])  # (a+b)+c
        cbc, rbc = _emerge(centers[1], radii[1], centers[2], radii[2])
        cr, rr = _emerge(centers[0], radii[0], cbc, rbc)  # a+(b+c)
        for c_, r_ in ((cl, rl), (cr, rr)):
            for i in range(3):
                gap = np.linalg.norm(c_ - centers[i]) + radii[i] - r_
                assert gap <= tol, (i, gap)
        assert np.linalg.norm(cl - cr) <= min(rl, rr) + tol
        assert max(rl, rr) <= 2.0 * min(rl, rr) + tol

    @settings(max_examples=15, deadline=None)
    @given(
        s=st.integers(2, 5),
        b=st.integers(1, 4),
        d=st.integers(1, 6),
        seed=st.integers(0, 10_000),
    )
    def test_bank_fold_matches_scalar_folds(s, b, d, seed):
        """The bank-vectorized fold is exactly B independent scalar folds."""
        banks = _random_balls(s, b, d, seed=seed)
        folded = fold_merge(banks)
        for k in range(b):
            one = fold_merge(jax.tree.map(lambda x: x[:, k], banks))
            np.testing.assert_allclose(
                np.asarray(folded.w[k]), np.asarray(one.w), rtol=1e-6, atol=1e-7
            )
            np.testing.assert_allclose(float(folded.r[k]), float(one.r), rtol=1e-6)


# ---------------------------------------------------------------------------
# Four virtual devices in a child process: the eager fold's exactness
# ---------------------------------------------------------------------------

_HOST_FOLD_CHILD = r"""
import json, sys
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import fit_bank_sharded, fold_merge
from repro.core.distributed import _place_rows, _sharded_fits

assert len(jax.devices()) == 4, jax.devices()
mesh = jax.make_mesh((4,), ("data",))
b, d = 12, 128
out = {}
for n in (256, 9):
    rng = np.random.default_rng(n)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Y = jnp.asarray(np.sign(rng.normal(size=(b, n))).astype(np.float32))
    cs = jnp.asarray(np.exp(rng.uniform(-1, 3, size=b)).astype(np.float32))
    got = fit_bank_sharded(X, Y, cs, mesh)
    # The route through the host: the stack copied out, cut to its live
    # shards, copied back to the default device and folded there op by op.
    shard_n = -(-n // 4)
    stacked = _sharded_fits(
        _place_rows(X, NamedSharding(mesh, P("data")), 0, shard_n * 4),
        _place_rows(Y, NamedSharding(mesh, P(None, "data")), 1, shard_n * 4),
        cs, mesh=mesh, axes=("data",), n_rows=n, variant="exact",
        lookahead=None, block_n=256, b_tile=None, stream_dtype=None,
        bank_resident="auto", interpret=None)
    n_live = -(-n // shard_n)
    want = fold_merge.__wrapped__(jax.tree.map(
        lambda v: jax.device_put(np.asarray(v)[:n_live]), stacked))
    out[n] = {"n_live": n_live, "fields": {
        f: np.asarray(getattr(got, f)).tobytes()
        == np.asarray(getattr(want, f)).tobytes()
        for f in ("w", "r", "xi2", "m")}}
print(json.dumps(out))
"""


def test_eager_fold_is_bit_identical_to_host_route():
    """On four devices, the eager mesh fit folds the fold device's own
    replica of the gathered banks with the compiled fold; the bank it
    returns equals, bit for bit, the one folded op by op from the stack
    copied through the host, for an even split and for a ragged stream
    whose last shard is dead."""
    import json
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.abspath(src))
    proc = subprocess.run([sys.executable, "-c", _HOST_FOLD_CHILD], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["256"]["n_live"] == 4 and out["9"]["n_live"] == 3
    for n, case in out.items():
        assert all(case["fields"].values()), (n, case)


# ---------------------------------------------------------------------------
# SLOW: 8-device shard-count invariance and mesh routing
# ---------------------------------------------------------------------------


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(
            f"needs {n} devices (run with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n})"
        )
    return jax.make_mesh((n,), ("data",))


def _manual_ragged_fold(X, Y, cs, n_shards, **kw):
    """Oracle: fit each contiguous ragged range separately, fold the banks."""
    n = X.shape[0]
    shard_n = -(-n // n_shards)
    banks = []
    for k in range(n_shards):
        lo, hi = k * shard_n, min((k + 1) * shard_n, n)
        if lo >= n:
            break
        banks.append(fit_bank(X[lo:hi], Y[:, lo:hi], cs, **kw))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *banks)
    return fold_merge(stacked)


@pytest.mark.slow
@pytest.mark.parametrize(
    "b,n,d,b_tile,variant,lookahead",
    [
        (6, 640, 12, None, "exact", None),     # even split
        (6, 611, 12, None, "exact", None),     # N % n_shards != 0
        (11, 611, 12, 8, "exact", None),       # ... and B % b_tile != 0
        (6, 611, 12, None, "lookahead", 4),    # fused Algorithm 2
        (11, 613, 10, 8, "lookahead", (1, 3, 5, 2, 7, 4, 1, 6, 3, 2, 5)),
    ],
)
def test_fit_bank_sharded_matches_manual_ragged_fold(
    b, n, d, b_tile, variant, lookahead
):
    """The mesh path must equal per-range fits + bank fold — including inert
    remainder padding and padded bank lanes."""
    from repro.core import fit_bank_sharded

    mesh = _need_devices(8)
    X, Y, cs = _bank_data(b, n, d, seed=b + n)
    kw = dict(variant=variant, lookahead=lookahead, block_n=64, b_tile=b_tile)
    out = fit_bank_sharded(X, Y, cs, mesh, **kw)
    ref = _manual_ragged_fold(X, Y, cs, 8, **kw)
    np.testing.assert_allclose(
        np.asarray(out.w), np.asarray(ref.w), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(out.r), np.asarray(ref.r), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(out.xi2), np.asarray(ref.xi2), rtol=1e-4, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(out.m), np.asarray(ref.m))


@pytest.mark.slow
def test_fit_bank_sharded_dead_shards_masked():
    """N < usable rows per shard count: fully-padded shards must be skipped
    exactly (N=9 on 8 shards -> 3 dead shards of pure padding)."""
    from repro.core import fit_bank_sharded

    mesh = _need_devices(8)
    X, Y, cs = _bank_data(4, 9, 6, seed=7)
    out = fit_bank_sharded(X, Y, cs, mesh, block_n=64)
    ref = _manual_ragged_fold(X, Y, cs, 8, block_n=64)
    np.testing.assert_allclose(
        np.asarray(out.w), np.asarray(ref.w), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(out.m), np.asarray(ref.m))
    assert np.isfinite(np.asarray(out.w)).all()


@pytest.mark.slow
@pytest.mark.parametrize("variant,lookahead", [("exact", None), ("lookahead", 6)])
def test_fit_bank_sharded_vs_single_device_statistical(variant, lookahead):
    """Sharding + merge is a different (lossier) estimator than one
    sequential pass, but must stay in the same model class: per-model sign
    agreement high, merged radius within the 2x enclosure band."""
    from repro.core import fit_bank_sharded

    mesh = _need_devices(8)
    rng = np.random.default_rng(17)
    n, d, b = 2048, 24, 5
    Xn = rng.normal(size=(n, d)).astype(np.float32)
    Xn /= np.linalg.norm(Xn, axis=1, keepdims=True)
    X = jnp.asarray(Xn)
    y = np.sign(rng.normal(size=n) + 2 * Xn[:, 0]).astype(np.float32)
    y[y == 0] = 1
    Y = jnp.asarray(np.tile(y, (b, 1)))
    cs = jnp.asarray([0.5, 1.0, 10.0, 50.0, 100.0], jnp.float32)
    kw = dict(variant=variant, lookahead=lookahead, block_n=128)
    sharded = fit_bank_sharded(X, Y, cs, mesh, **kw)
    single = fit_bank(X, Y, cs, **kw)
    acc_s = np.mean(np.sign(Xn @ np.asarray(sharded.w).T) == y[:, None], axis=0)
    acc_1 = np.mean(np.sign(Xn @ np.asarray(single.w).T) == y[:, None], axis=0)
    assert np.all(np.abs(acc_s - acc_1) < 0.08), (acc_s, acc_1)
    assert np.all(np.asarray(sharded.r) <= 2.0 * np.asarray(single.r) + 1e-5)
    # total core vectors: sum of per-shard counts, bounded by the stream
    assert np.all(np.asarray(sharded.m) <= n)


@pytest.mark.slow
def test_fit_ovr_and_c_grid_route_through_mesh():
    """mesh= on the jit'd wrappers == calling fit_bank_sharded directly."""
    from repro.core import fit_bank_sharded, fit_c_grid, fit_ovr, ovr_signs, predict_ovr

    mesh = _need_devices(8)
    rng = np.random.default_rng(23)
    n, d, k = 900, 16, 6
    proto = rng.normal(size=(k, d)) * 4
    labels = rng.integers(0, k, size=n)
    Xn = (rng.normal(size=(n, d)) + proto[labels]).astype(np.float32)
    Xn /= np.linalg.norm(Xn, axis=1, keepdims=True)
    X, lab = jnp.asarray(Xn), jnp.asarray(labels)

    balls = fit_ovr(X, lab, k, 10.0, mesh=mesh, b_tile=8)
    direct = fit_bank_sharded(
        X, ovr_signs(lab, k), jnp.full((k,), 10.0), mesh, b_tile=8
    )
    np.testing.assert_allclose(
        np.asarray(balls.w), np.asarray(direct.w), rtol=1e-5, atol=1e-6
    )
    # the sharded OVR bank must still classify the clustered stream
    pred = predict_ovr(balls, X)
    assert float(jnp.mean(pred == lab)) > 0.9

    y = jnp.asarray(np.where(labels == 0, 1.0, -1.0).astype(np.float32))
    grid = jnp.asarray([1.0, 10.0, 100.0], jnp.float32)
    gb = fit_c_grid(X, y, grid, mesh=mesh)
    gd = fit_bank_sharded(
        X, jnp.broadcast_to(y[None, :], (3, n)), grid, mesh
    )
    np.testing.assert_allclose(
        np.asarray(gb.w), np.asarray(gd.w), rtol=1e-5, atol=1e-6
    )


@pytest.mark.slow
def test_chunked_many_mesh_resume_same_shard_count_exact():
    """Uninterrupted sharded chunk stream == checkpoint + resume (same mesh):
    the checkpoint carries ONE folded bank, so replay is deterministic."""
    from repro.core import fit_chunked_many
    from repro.data.stream import chunk_stream

    mesh = _need_devices(8)
    rng = np.random.default_rng(29)
    n, d = 803, 9
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = np.sign(rng.normal(size=n) + X[:, 0]).astype(np.float32)
    y[y == 0] = 1
    cs = jnp.asarray([1.0, 10.0, 100.0])
    cont = fit_chunked_many(chunk_stream(X, y, 128), cs, mesh=mesh, block_n=64)
    saved = []
    fit_chunked_many(
        chunk_stream(X, y, 128), cs, mesh=mesh, block_n=64,
        checkpoint_every=256, checkpoint_cb=saved.append,
    )
    first = saved[0]
    assert first.position < n
    assert first.ball.w.shape == (3, d)  # ONE folded bank, not per-shard
    rest = fit_chunked_many(
        chunk_stream(X, y, 128, start=first.position), cs,
        mesh=mesh, block_n=64, resume=first,
    )
    np.testing.assert_allclose(
        np.asarray(rest.ball.w), np.asarray(cont.ball.w), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_array_equal(
        np.asarray(rest.ball.m), np.asarray(cont.ball.m)
    )
    assert rest.position == n


@pytest.mark.slow
def test_chunked_many_mesh_resume_elastic_reshard():
    """Resume the SAME checkpoint on a different shard count: the post-resume
    merge partition differs, so the banks are not bit-equal — but the model
    class must be preserved (high sign agreement on the stream and radii in
    each other's 2x enclosure band)."""
    from repro.core import fit_chunked_many
    from repro.data.stream import chunk_stream

    mesh8 = _need_devices(8)
    mesh4 = jax.make_mesh((4,), ("data",))
    rng = np.random.default_rng(31)
    n, d = 900, 10
    X = rng.normal(size=(n, d)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    y = np.sign(rng.normal(size=n) + 2 * X[:, 0]).astype(np.float32)
    y[y == 0] = 1
    cs = jnp.asarray([1.0, 10.0, 100.0])
    cont = fit_chunked_many(chunk_stream(X, y, 150), cs, mesh=mesh8, block_n=64)
    saved = []
    fit_chunked_many(
        chunk_stream(X, y, 150), cs, mesh=mesh8, block_n=64,
        checkpoint_every=300, checkpoint_cb=saved.append,
    )
    rest = fit_chunked_many(
        chunk_stream(X, y, 150, start=saved[0].position), cs,
        mesh=mesh4, block_n=64, resume=saved[0],  # ELASTIC: 8 -> 4 shards
    )
    w_c, w_r = np.asarray(cont.ball.w), np.asarray(rest.ball.w)
    cos = np.sum(w_c * w_r, axis=1) / (
        np.linalg.norm(w_c, axis=1) * np.linalg.norm(w_r, axis=1)
    )
    assert np.all(cos > 0.85), cos
    acc_c = np.mean(np.sign(X @ w_c.T) == y[:, None], axis=0)
    acc_r = np.mean(np.sign(X @ w_r.T) == y[:, None], axis=0)
    assert np.all(np.abs(acc_c - acc_r) < 0.06), (acc_c, acc_r)
    r_c, r_r = np.asarray(cont.ball.r), np.asarray(rest.ball.r)
    assert np.all(r_r <= 2.0 * r_c + 1e-5) and np.all(r_c <= 2.0 * r_r + 1e-5)
    assert rest.position == n

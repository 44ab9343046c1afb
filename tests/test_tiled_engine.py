"""Tiled bank engine: b_tile sweeps, fused lookahead, bf16 tiles, recompiles.

The tiled 2-D grid path must be BIT-EXACT (f32) with the single-tile layout —
same per-lane arithmetic, only the grid decomposition changes — and the fused
in-kernel Algorithm 2 must match the plain-python oracle in ref.py across
(B, N, D, L, block_n), including L > block_n boundary flushes and per-model
L. bf16 stream tiles trade bounded precision for half the stream traffic.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    fit_bank, fit_lookahead, fit_lookahead_ball, fit_ovr, init_ball, predict_ovr,
)
from repro.core.distributed import fit_bank_sharded
from repro.kernels import streamsvm_fit_many
from repro.kernels.ops import bank_engine_grid
from repro.kernels.ref import (
    streamsvm_scan_lookahead_many_ref,
    streamsvm_scan_lookahead_ref,
    streamsvm_scan_many_ref,
)


def _bank_data(b, n, d, seed):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Y = jnp.asarray(np.sign(rng.normal(size=(b, n))).astype(np.float32))
    cs = jnp.asarray(np.exp(rng.uniform(-1, 4, size=b)).astype(np.float32))
    return X, Y, cs


# ---------------------------------------------------------------------------
# Bank tiling (tentpole): 2-D grid == single-tile, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,n,d,block_n,b_tile", [
    (64, 300, 20, 64, 8),      # 8 tiles: B = 8x the single-tile layout
    (16, 512, 128, 128, 8),
    (11, 257, 33, 64, 8),      # B not a multiple of b_tile (padded lanes)
    (13, 300, 20, 64, 3),      # b_tile not a multiple of 8 (rounded up)
    (24, 200, 40, 256, 8),     # N < block_n, multiple tiles
])
def test_tiled_bit_exact_with_single_tile(b, n, d, block_n, b_tile):
    """The grid decomposition must not change a single bit of f32 output."""
    X, Y, cs = _bank_data(b, n, d, seed=b * n + d)
    one = streamsvm_fit_many(X, Y, cs, block_n=block_n)
    tiled = streamsvm_fit_many(X, Y, cs, block_n=block_n, b_tile=b_tile)
    np.testing.assert_array_equal(np.asarray(tiled.w), np.asarray(one.w))
    np.testing.assert_array_equal(np.asarray(tiled.r), np.asarray(one.r))
    np.testing.assert_array_equal(np.asarray(tiled.xi2), np.asarray(one.xi2))
    np.testing.assert_array_equal(np.asarray(tiled.m), np.asarray(one.m))


@pytest.mark.parametrize("b,n,d,block_n,b_tile", [
    # read in place (row 0 seeds, so each block starts a row into its tile),
    # a ragged last block of 43 rows and a last bank tile of 5 models
    (21, 300, 128, 64, 8),
    (24, 300, 40, 64, 8),  # a zero-padded copy of the stream
])
@pytest.mark.parametrize("bank_resident", ["vmem", "hbm"])
@pytest.mark.parametrize("variant,lookahead", [
    ("exact", None), ("lookahead", (3, 1, 7)),
])
def test_block_gram_is_shared_by_the_bank_tiles(
    b, n, d, block_n, b_tile, bank_resident, variant, lookahead
):
    """Only a block's first bank tile fills the block Gram (and its band);
    the block's other tiles read it. A fit of J >= 3 tiles must give, tile
    by tile, the bits of that tile's models fitted alone (J = 1, where
    every visit fills the Gram): a Gram left from the previous block or
    filled at another tile fails it."""
    X, Y, cs = _bank_data(b, n, d, seed=b * n + d)
    ls = None if lookahead is None else (lookahead * b)[:b]
    kw = dict(variant=variant, block_n=block_n, bank_resident=bank_resident)
    blocks, tiles = bank_engine_grid(n, b, d, lookahead=ls, b_tile=b_tile, **kw)
    assert (blocks, tiles) == (-(-(n - 1) // block_n), -(-b // b_tile))
    assert tiles >= 3 and (n - 1) % block_n
    bank = streamsvm_fit_many(X, Y, cs, lookahead=ls, b_tile=b_tile, **kw)
    for lo in range(0, b, b_tile):
        hi = min(lo + b_tile, b)
        alone = streamsvm_fit_many(
            X, Y[lo:hi], cs[lo:hi], lookahead=ls and ls[lo:hi], **kw
        )
        for field in ("w", "r", "xi2", "m"):
            np.testing.assert_array_equal(
                np.asarray(getattr(bank, field))[lo:hi],
                np.asarray(getattr(alone, field)),
            )


def test_mesh_fit_span_counts_gram_fills_and_tile_visits(monkeypatch):
    """The eager mesh fit's ``fit.shards`` span carries the shard's Gram
    fills (its data blocks) and tile visits (blocks x bank tiles)."""
    spans = []

    class Span:
        def __init__(self, name, **args):
            spans.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    X, Y, cs = _bank_data(21, 300, 128, seed=4)
    mesh = jax.make_mesh((1,), ("data",))
    fit_bank_sharded(X, Y, cs, mesh, block_n=64, b_tile=8)
    args = dict(spans)["fit.shards"]
    # 299 streamed rows in blocks of 64, three tiles of 8 for 21 models
    assert args == dict(gram_fills=5, tile_visits=15)


def test_tiled_matches_bank_ref_at_8x_tile():
    """B = 8 * b_tile against the pure-jnp oracle (not just self-consistency)."""
    b, n, d, b_tile = 64, 400, 24, 8
    X, Y, cs = _bank_data(b, n, d, seed=17)
    bank = streamsvm_fit_many(X, Y, cs, block_n=128, b_tile=b_tile)
    c_inv = 1.0 / cs
    W0 = Y[:, 0:1] * X[0][None, :]
    w, r, xi2, m = streamsvm_scan_many_ref(
        X[1:], Y[:, 1:], W0, 0.0, c_inv, c_inv, 1, gain=c_inv
    )
    np.testing.assert_allclose(np.asarray(bank.w), np.asarray(w), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(bank.r), np.asarray(r), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(bank.m), np.asarray(m))


@pytest.mark.parametrize("b,n,d,block_n,b_tile", [
    (13, 301, 20, 24, 8),      # block_n not a power of two, ragged B
    (21, 250, 33, 40, 16),     # two bank tiles, the second half padding
    (7, 199, 9, 56, None),     # one tile of 8 rows holding 7 models
])
def test_row_reads_match_ref_with_inert_column(b, n, d, block_n, b_tile):
    """The row loop reads row j of the block Gram, of the running inner
    products and of the signs without slicing a value at the traced row
    (sublane reads of refs, one-hot lane sums). Against the plain-jnp scan
    at block sizes that are not powers of two, a ragged bank, and one stream
    row whose sign is 0 for every model (inert); the two residencies stay
    bit-exact with each other."""
    X, Y, cs = _bank_data(b, n, d, seed=3 * b + n)
    Y = Y.at[:, n // 2].set(0.0)
    kw = dict(block_n=block_n, b_tile=b_tile)
    vmem = streamsvm_fit_many(X, Y, cs, bank_resident="vmem", **kw)
    hbm = streamsvm_fit_many(X, Y, cs, bank_resident="hbm", **kw)
    for field in ("w", "r", "xi2", "m"):
        np.testing.assert_array_equal(
            np.asarray(getattr(hbm, field)), np.asarray(getattr(vmem, field))
        )
    c_inv = 1.0 / cs
    W0 = Y[:, 0:1] * X[0][None, :]
    w, r, xi2, m = streamsvm_scan_many_ref(
        X[1:], Y[:, 1:], W0, 0.0, c_inv, c_inv, 1, gain=c_inv
    )
    np.testing.assert_allclose(np.asarray(vmem.w), np.asarray(w), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(vmem.r), np.asarray(r), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(vmem.m), np.asarray(m))


def _edge_stream(kind, block_n, seed):
    """(X, Y, cs, accepted): a stream shaped to stress the row loop's
    look-ahead, and stream rows that every model must accept.

    Stream row k + 1 is row k of the kernel's blocks (row 0 seeds the ball).
    """
    rng = np.random.default_rng(seed)
    b, d, n = 5, 12, 2 * block_n + 40
    accepted = []
    if kind == "every_row_violates":
        # Nearly orthogonal rows whose norms grow 15% a row, and C large
        # enough that the slack never encloses the next one.
        n = d = 130
        base = 3.0 * np.eye(n) + 0.2 * rng.normal(size=(n, n))
        X = base * 1.15 ** (np.arange(n) - n + 1)[:, None]
        cs = np.exp(rng.uniform(9, 14, size=b))
    else:
        X = rng.normal(size=(n, d))
        cs = np.exp(rng.uniform(-1, 4, size=b))
    if kind == "violators_across_block_edge":
        # Far outliers at the first block's last row and the next block's
        # first row: two acceptances in a row across the block boundary.
        X[block_n : block_n + 2] *= 100.0
        accepted = [block_n, block_n + 1]
    if kind == "n_valid_mid_block":
        n = block_n + block_n // 2 + 1  # the last block is half padding
        X = X[:n]
    Y = np.sign(rng.normal(size=(b, n)))
    if kind == "sign0_next_to_live":
        Y[:, 2::5] = 0.0  # rows inert for every model, between live rows
        Y[rng.random(size=(b, n)) < 0.3] = 0.0  # and inert for single models
        Y[:, 0] = 1.0  # row 0 seeds every model
    return (
        jnp.asarray(X.astype(np.float32)), jnp.asarray(Y.astype(np.float32)),
        jnp.asarray(cs.astype(np.float32)), accepted,
    )


@pytest.mark.parametrize("block_n", [8, 128, 256])
@pytest.mark.parametrize("kind", [
    "every_row_violates", "violators_across_block_edge", "n_valid_mid_block",
    "sign0_next_to_live",
])
def test_row_loop_lookahead_matches_ref(kind, block_n):
    """The row loop reads its rows' g columns a step ahead and corrects them
    by each row's update. Against the plain-jnp scan: acceptances on
    consecutive rows, across a block boundary, a last block that ends mid
    way, and sign-0 rows next to live ones."""
    X, Y, cs, accepted = _edge_stream(kind, block_n, seed=block_n)
    bank = streamsvm_fit_many(X, Y, cs, block_n=block_n)
    c_inv = 1.0 / cs
    W0 = Y[:, 0:1] * X[0][None, :]

    def ref(k):  # the reference after stream rows [0, k)
        return streamsvm_scan_many_ref(
            X[1:k], Y[:, 1:k], W0, 0.0, c_inv, c_inv, 1, gain=c_inv
        )

    w, r, xi2, m = ref(X.shape[0])
    # the stream is what its name says
    if kind == "every_row_violates":
        np.testing.assert_array_equal(np.asarray(m), X.shape[0])
    for k in accepted:
        np.testing.assert_array_equal(
            np.asarray(ref(k + 1)[3]) - np.asarray(ref(k)[3]), 1
        )
    np.testing.assert_allclose(np.asarray(bank.w), np.asarray(w), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(bank.r), np.asarray(r), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(bank.xi2), np.asarray(xi2), rtol=1e-3, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(bank.m), np.asarray(m))


def test_padded_model_rows_stay_inert():
    """B % b_tile != 0 pads model lanes; results must equal the unpadded run
    and contain no NaN/inf leakage from the padded lanes."""
    b, n, d = 10, 333, 18
    X, Y, cs = _bank_data(b, n, d, seed=5)
    plain = streamsvm_fit_many(X, Y, cs, block_n=64)
    padded = streamsvm_fit_many(X, Y, cs, block_n=64, b_tile=8)  # pads to 16
    np.testing.assert_array_equal(np.asarray(padded.w), np.asarray(plain.w))
    np.testing.assert_array_equal(np.asarray(padded.m), np.asarray(plain.m))
    assert np.isfinite(np.asarray(padded.w)).all()
    assert np.isfinite(np.asarray(padded.r)).all()


def test_tiled_restart_equals_continuous_pass():
    """Bank checkpoint/resume with tiling == one continuous tiled pass.

    allclose, not bit-equal: the restart re-derives |w|^2 from the
    checkpointed center while the continuous pass maintains it by recursion
    (identical to the PR 1 restart semantics).
    """
    b, n, d = 20, 514, 41
    X, Y, cs = _bank_data(b, n, d, seed=99)
    full = streamsvm_fit_many(X, Y, cs, block_n=64, b_tile=8)
    head = streamsvm_fit_many(X[:200], Y[:, :200], cs, block_n=64, b_tile=8)
    rest = streamsvm_fit_many(X[200:], Y[:, 200:], cs, head, block_n=64, b_tile=8)
    np.testing.assert_allclose(
        np.asarray(rest.w), np.asarray(full.w), rtol=2e-4, atol=2e-5
    )
    np.testing.assert_array_equal(np.asarray(rest.m), np.asarray(full.m))


# ---------------------------------------------------------------------------
# Fused Algorithm-2 lookahead vs the ref.py oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,n,d,block_n,b_tile,ls", [
    (5, 257, 16, 64, 8, (1, 4, 7, 100, 3)),    # per-model L, L > block_n
    (8, 400, 24, 128, 8, 10),                  # shared L
    (3, 129, 7, 256, None, (2, 300, 5)),       # L >> N: single final flush
    (12, 300, 33, 64, 8, 6),                   # unaligned B/D
])
def test_lookahead_kernel_matches_oracle(b, n, d, block_n, b_tile, ls):
    X, Y, cs = _bank_data(b, n, d, seed=7 * b + n)
    bank = streamsvm_fit_many(
        X, Y, cs, variant="lookahead", lookahead=ls, block_n=block_n,
        b_tile=b_tile,
    )
    c_inv = 1.0 / np.asarray(cs)
    W0 = np.asarray(Y[:, 0:1] * X[0][None, :])
    w, r, xi2, m = streamsvm_scan_lookahead_many_ref(
        np.asarray(X[1:]), np.asarray(Y[:, 1:]), W0, 0.0, c_inv, c_inv, 1, ls,
        gain=c_inv,
    )
    np.testing.assert_allclose(np.asarray(bank.w), w, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(bank.r), r, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(bank.xi2), xi2, rtol=1e-3, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(bank.m), m)


def test_lookahead_paper_variant_honors_gain():
    """variant='lookahead-paper' must use the paper-listing slack gain (1.0),
    both through the kernel and through core.fit_lookahead's routing."""
    X, Y, cs = _bank_data(4, 200, 10, seed=37)
    exact = streamsvm_fit_many(X, Y, cs, variant="lookahead", lookahead=5, block_n=64)
    paper = streamsvm_fit_many(
        X, Y, cs, variant="lookahead-paper", lookahead=5, block_n=64
    )
    assert not np.allclose(np.asarray(paper.xi2), np.asarray(exact.xi2))
    c_inv = 1.0 / np.asarray(cs)
    W0 = np.asarray(Y[:, 0:1] * X[0][None, :])
    ones = np.ones_like(c_inv)
    w, r, xi2, m = streamsvm_scan_lookahead_many_ref(
        np.asarray(X[1:]), np.asarray(Y[:, 1:]), W0, 0.0, ones, c_inv, 1, 5,
        gain=ones,
    )
    np.testing.assert_allclose(np.asarray(paper.w), w, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(paper.m), m)
    one = fit_lookahead(X, Y[0], float(cs[0]), 5, variant="paper-listing", block_n=64)
    np.testing.assert_allclose(np.asarray(one.w), w[0], rtol=2e-4, atol=2e-5)


def test_lookahead_one_equals_algorithm_1():
    """L=1 buffers each violator and immediately flushes it: Algorithm 1."""
    X, Y, cs = _bank_data(6, 300, 12, seed=2)
    la = streamsvm_fit_many(X, Y, cs, variant="lookahead", lookahead=1, block_n=64)
    a1 = streamsvm_fit_many(X, Y, cs, block_n=64)
    np.testing.assert_allclose(
        np.asarray(la.w), np.asarray(a1.w), rtol=2e-5, atol=2e-6
    )
    np.testing.assert_array_equal(np.asarray(la.m), np.asarray(a1.m))


def test_lookahead_chunk_boundary_flush_semantics():
    """A chained lookahead fit flushes its windows at the pass boundary; the
    oracle applied chunk by chunk (each with its trailing flush) must agree."""
    b, n, d, L, cut = 4, 360, 10, 6, 150
    X, Y, cs = _bank_data(b, n, d, seed=11)
    head = streamsvm_fit_many(
        X[:cut], Y[:, :cut], cs, variant="lookahead", lookahead=L, block_n=64
    )
    rest = streamsvm_fit_many(
        X[cut:], Y[:, cut:], cs, head, variant="lookahead", lookahead=L,
        block_n=64,
    )
    c_inv = 1.0 / np.asarray(cs)
    W0 = np.asarray(Y[:, 0:1] * X[0][None, :])
    w, r, xi2, m = streamsvm_scan_lookahead_many_ref(
        np.asarray(X[1:cut]), np.asarray(Y[:, 1:cut]), W0, 0.0, c_inv, c_inv,
        1, L, gain=c_inv,
    )
    w, r, xi2, m = streamsvm_scan_lookahead_many_ref(
        np.asarray(X[cut:]), np.asarray(Y[:, cut:]), w, r, xi2, c_inv, m, L,
        gain=c_inv,
    )
    np.testing.assert_allclose(np.asarray(rest.w), w, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(rest.m), m)


def test_fit_lookahead_routes_to_engine():
    """core.fit_lookahead runs the fused kernel; one model must match the
    single-model oracle."""
    rng = np.random.default_rng(21)
    X = jnp.asarray(rng.normal(size=(400, 14)).astype(np.float32))
    y = jnp.asarray(np.sign(rng.normal(size=400)).astype(np.float32))
    ball = fit_lookahead(X, y, 10.0, 8)
    w, r, xi2, m = streamsvm_scan_lookahead_ref(
        np.asarray(X[1:]), np.asarray(y[1:]), np.asarray(y[0] * X[0]),
        0.0, 0.1, 0.1, 1, 8, gain=np.float32(0.1),
    )
    np.testing.assert_allclose(np.asarray(ball.w), w, rtol=2e-4, atol=2e-5)
    assert int(ball.m) == int(m)
    # the BC window-solve reference stays available
    qp = fit_lookahead_ball(init_ball(X[0], y[0], 10.0), X[1:], y[1:], 10.0, 8)
    assert qp.w.shape == ball.w.shape


def test_fit_ovr_lookahead_via_engine():
    """200-class-style OVR with in-kernel lookahead: correct and one-pass."""
    rng = np.random.default_rng(31)
    proto = rng.normal(size=(6, 16)) * 4
    labels = rng.integers(0, 6, size=900)
    X = (rng.normal(size=(900, 16)) + proto[labels]).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    balls = fit_ovr(
        jnp.asarray(X), jnp.asarray(labels), 6, 10.0, lookahead=8, b_tile=8
    )
    pred = predict_ovr(balls, jnp.asarray(X))
    assert float(jnp.mean(pred == jnp.asarray(labels))) > 0.9


# ---------------------------------------------------------------------------
# bf16 stream tiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b_tile", [None, 8])
def test_bf16_stream_tolerance(b_tile):
    """bf16 tiles halve stream bytes; the result must stay within a few bf16
    eps of the f32 run (labels are exact in bf16, features round)."""
    X, Y, cs = _bank_data(8, 600, 32, seed=13)
    f32 = streamsvm_fit_many(X, Y, cs, block_n=128, b_tile=b_tile)
    bf16 = streamsvm_fit_many(
        X, Y, cs, block_n=128, b_tile=b_tile, stream_dtype="bf16"
    )
    scale = np.abs(np.asarray(f32.w)).max()
    rel = np.abs(np.asarray(bf16.w) - np.asarray(f32.w)).max() / scale
    assert rel < 0.05, rel  # a sequential process: allow a few accumulated ulp
    np.testing.assert_allclose(
        np.asarray(bf16.r), np.asarray(f32.r), rtol=2e-2
    )
    # the models must still be *useful*: sign agreement on the stream
    agree = np.mean(
        np.sign(np.asarray(X) @ np.asarray(f32.w).T)
        == np.sign(np.asarray(X) @ np.asarray(bf16.w).T)
    )
    assert agree > 0.97, agree


def test_bf16_lookahead_runs():
    X, Y, cs = _bank_data(4, 300, 16, seed=23)
    bank = streamsvm_fit_many(
        X, Y, cs, variant="lookahead", lookahead=4, stream_dtype="bf16",
        block_n=64, b_tile=8,
    )
    assert np.isfinite(np.asarray(bank.w)).all()


# ---------------------------------------------------------------------------
# Compile-cache regressions: C sweeps must not recompile
# ---------------------------------------------------------------------------


def test_no_recompile_across_c_values():
    X, Y, _ = _bank_data(4, 96, 9, seed=41)
    start = streamsvm_fit_many._cache_size()
    for scale in (1.0, 2.0, 10.0):
        streamsvm_fit_many(X, Y, scale * jnp.ones((4,), jnp.float32), block_n=32)
    assert streamsvm_fit_many._cache_size() == start + 1


# ---------------------------------------------------------------------------
# Shape errors survive python -O and carry the offending shapes
# ---------------------------------------------------------------------------


def test_shape_errors_are_value_errors():
    X, Y, cs = _bank_data(4, 64, 8, seed=1)
    with pytest.raises(ValueError, match=r"\(4, 64\)"):
        streamsvm_fit_many(X[:32], Y, cs)  # Y rows don't match N
    with pytest.raises(ValueError, match="sign rows"):
        streamsvm_fit_many(X, Y.T, cs)
    with pytest.raises(ValueError, match=r"2-D.*\(64,\).*\(64, 8\)"):
        fit_bank(X, Y[0], 1.0)  # one model's labels passed as Y, not y[None]
    with pytest.raises(ValueError, match="variant"):
        streamsvm_fit_many(X, Y, cs, variant="bogus")
    with pytest.raises(ValueError, match="lookahead"):
        streamsvm_fit_many(X, Y, cs, variant="lookahead", lookahead=(2, 2))
    with pytest.raises(ValueError, match="stream_dtype"):
        streamsvm_fit_many(X, Y, cs, stream_dtype="int7")
    with pytest.raises(ValueError, match="variant"):
        fit_lookahead(X, Y[0], 1.0, 4, variant="lookahead")  # fit_bank-ism
    with pytest.raises(ValueError, match="variant"):
        fit_ovr(X, jnp.zeros(64, jnp.int32), 2, 1.0, lookahead=4, variant="exactt")


def test_scan_wrapper_validates_tiling():
    from repro.kernels.streamsvm_scan import streamsvm_scan_many_pallas

    X = jnp.zeros((128, 128), jnp.float32)
    Y = jnp.zeros((8, 128), jnp.float32)
    W0 = jnp.zeros((8, 128), jnp.float32)
    z = jnp.zeros((8,), jnp.float32)
    with pytest.raises(ValueError, match="b_tile"):
        streamsvm_scan_many_pallas(X, Y, W0, z, z, z, z, block_n=128, b_tile=3)
    with pytest.raises(ValueError, match="block_n"):
        streamsvm_scan_many_pallas(X[:100], Y[:, :100], W0, z, z, z, z, block_n=60)
    with pytest.raises(ValueError, match="lookahead_max"):
        streamsvm_scan_many_pallas(
            X, Y, W0, z, z, z, z, block_n=128,
            lookahead=jnp.ones((8,), jnp.int32),
        )

"""BankServer: golden train->serve handoff + scheduler semantics.

The golden test pins the whole deploy path — fit_bank -> checkpoint ->
BankServer.from_checkpoint -> held-out accuracy — EXACTLY (f32) against the
direct core.predict_ovr / predict_c_grid readouts. The scheduler tests pin
microbatch packing, slot-utilization accounting, and mid-stream bank
hot-swap (queued requests survive, old rows keep old results, no recompile).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import ckpt
from repro.core import (
    fit_bank,
    fit_chunked_many,
    ovr_signs,
    predict_c_grid,
    predict_ovr,
)
from repro.kernels import predict_bank
from repro.serve import BankServer


def _blobs(n, n_classes, d, seed, proto_seed=0):
    """Class-blob samples; a fixed proto_seed shares prototypes across
    train/test splits (different ``seed`` -> held-out draw, same classes)."""
    proto = (
        np.random.default_rng(proto_seed).normal(size=(n_classes, d)) * 3
    ).astype(np.float32)
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, size=n)
    X = (rng.normal(size=(n, d)) + proto[labels]).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    return X, labels


def _jnp_scores(queries: np.ndarray, W) -> np.ndarray:
    """The direct jnp readout the served scores must match bitwise (numpy's
    own matmul may differ in the last ulp — the contract is vs jnp)."""
    return np.asarray(jnp.asarray(queries) @ jnp.asarray(W).T)


# ---------------------------------------------------------------------------
# Golden end-to-end: train -> checkpoint -> serve == direct readout, exactly
# ---------------------------------------------------------------------------


def test_served_ovr_matches_direct_readout_exactly(tmp_path):
    """fit_chunked_many -> ckpt.save -> from_checkpoint -> score: the served
    class ids and f32 margins must equal core.predict_c_grid bit for bit,
    and the single-group slice must equal core.predict_ovr."""
    n_classes, c_pts, d = 5, (1.0, 10.0, 100.0), 24
    Xtr, ytr = _blobs(600, n_classes, d, seed=10)
    Xte, yte = _blobs(200, n_classes, d, seed=11)
    signs = ovr_signs(jnp.asarray(ytr), n_classes)
    Y = jnp.tile(signs, (len(c_pts), 1))  # (30, N), class-major per C point
    cs = jnp.repeat(jnp.asarray(c_pts, jnp.float32), n_classes)

    # the train->serve handoff object: a fit_chunked_many checkpoint
    chunks = [
        (Xtr[lo : lo + 200], Y[:, lo : lo + 200]) for lo in range(0, 600, 200)
    ]
    result = fit_chunked_many(chunks, cs, b_tile=8)
    assert result.position == 600
    path = str(tmp_path / "bank")
    ckpt.save(
        path, result.ball,
        meta={"position": result.position, "n_classes": n_classes},
    )

    server = BankServer.from_checkpoint(
        path, epilogue="ovr", q_block=64, b_tile=32
    )
    assert server.n_classes == n_classes  # picked up from checkpoint meta
    cls, margin = server.score(Xte)

    bank = result.ball
    rcls, rmargin = predict_c_grid(bank, jnp.asarray(Xte), n_classes)
    np.testing.assert_array_equal(cls, np.asarray(rcls))
    np.testing.assert_array_equal(margin, np.asarray(rmargin))

    # per-C-point accuracy identical to the direct readout, and the grid's
    # best C point actually classifies (the reason the grid is served)
    accs = []
    for g in range(len(c_pts)):
        acc = float(np.mean(cls[:, g] == yte))
        assert acc == float(np.mean(np.asarray(rcls)[:, g] == yte))
        accs.append(acc)
    assert max(accs) > 0.9, accs

    # single-group slice == predict_ovr on the sliced bank
    one = jax.tree.map(lambda v: v[:n_classes], bank)
    np.testing.assert_array_equal(
        cls[:, 0], np.asarray(predict_ovr(one, jnp.asarray(Xte)))
    )


def test_served_scores_bit_exact_with_matmul():
    X, y = _blobs(150, 4, 16, seed=2)
    bank = fit_bank(jnp.asarray(X), ovr_signs(jnp.asarray(y), 4), 10.0)
    server = BankServer(bank, q_block=64)
    out = server.score(X)
    np.testing.assert_array_equal(
        out, np.asarray(jnp.asarray(X) @ bank.w.T)
    )


def test_topk_serving_matches_ref():
    rng = np.random.default_rng(3)
    W = rng.normal(size=(20, 12)).astype(np.float32)
    X = rng.normal(size=(90, 12)).astype(np.float32)
    server = BankServer(W, epilogue="topk", k=3, q_block=32)
    vals, ids = server.score(X)
    rv, ri = jax.lax.top_k(jnp.asarray(X) @ jnp.asarray(W).T, 3)
    np.testing.assert_array_equal(vals, np.asarray(rv))
    np.testing.assert_array_equal(ids, np.asarray(ri).astype(np.int32))


# ---------------------------------------------------------------------------
# Scheduler semantics: packing, admission, utilization
# ---------------------------------------------------------------------------


def test_step_packs_ragged_requests_into_slots():
    """Several small requests share one microbatch; a large one spans
    several. Steps = ceil(total_rows / q_block) regardless of the split."""
    rng = np.random.default_rng(4)
    W = rng.normal(size=(8, 8)).astype(np.float32)
    server = BankServer(W, q_block=16)
    sizes = [5, 3, 16, 9, 40, 1]  # 74 rows -> ceil(74/16) = 5 steps
    reqs = [server.submit(rng.normal(size=(n, 8)).astype(np.float32))
            for n in sizes]
    stats = server.run()
    assert stats.steps == 5
    assert stats.finished == len(sizes)
    assert stats.slot_busy_rows == sum(sizes)
    assert stats.slot_idle_rows == 5 * 16 - sum(sizes)
    assert stats.utilization == sum(sizes) / (5 * 16)
    for r in reqs:
        assert r.done
        np.testing.assert_array_equal(r.result, _jnp_scores(r.queries, W))


def test_admission_under_full_slots():
    """One step scores exactly q_block rows; the overflow stays queued (not
    dropped, not scored early)."""
    rng = np.random.default_rng(5)
    W = rng.normal(size=(8, 8)).astype(np.float32)
    server = BankServer(W, q_block=8)
    big = server.submit(rng.normal(size=(13, 8)).astype(np.float32))
    small = server.submit(rng.normal(size=(4, 8)).astype(np.float32))
    assert server.pending_rows() == 17
    assert server.step() == 8  # the slots fill from the FIFO head only
    assert big.rows_scored == 8 and not big.done
    assert small.rows_scored == 0 and not small.done
    assert server.pending_rows() == 9
    assert server.step() == 8  # big's tail (5) + small fully (4) wait... 5+4=9 -> 8
    assert big.done
    server.run()
    assert small.done
    np.testing.assert_array_equal(big.result, _jnp_scores(big.queries, W))
    np.testing.assert_array_equal(small.result, _jnp_scores(small.queries, W))


def test_run_raises_when_max_steps_cannot_drain():
    """Exhausting max_steps with rows pending must raise — returning would
    hand back requests whose result rows were never written."""
    rng = np.random.default_rng(9)
    W = rng.normal(size=(8, 8)).astype(np.float32)
    server = BankServer(W, q_block=4)
    req = server.submit(rng.normal(size=(12, 8)).astype(np.float32))
    with pytest.raises(RuntimeError, match="max_steps"):
        server.run(max_steps=2)
    assert not req.done
    server.run()  # plenty of steps: drains fine
    assert req.done
    np.testing.assert_array_equal(req.result, _jnp_scores(req.queries, W))


def test_empty_request_finishes_immediately():
    W = np.eye(4, dtype=np.float32)
    server = BankServer(W, q_block=8)
    req = server.submit(np.zeros((0, 4), np.float32))
    assert req.done and server.pending_rows() == 0
    assert req.result.shape == (0, 4)


# ---------------------------------------------------------------------------
# Hot swap: queued requests survive, row provenance is exact, no recompile
# ---------------------------------------------------------------------------


def test_hot_swap_mid_stream_correctness():
    """Rows scored before the swap carry bank A's scores, rows after carry
    bank B's — including the two halves of ONE request split by the swap —
    and nothing queued is dropped."""
    rng = np.random.default_rng(6)
    A = rng.normal(size=(6, 8)).astype(np.float32)
    B = rng.normal(size=(6, 8)).astype(np.float32)
    server = BankServer(A, q_block=8)
    r1 = server.submit(rng.normal(size=(8, 8)).astype(np.float32))
    r2 = server.submit(rng.normal(size=(12, 8)).astype(np.float32))
    server.step()  # r1 fully scored against A
    assert r1.done and not r2.done
    server.step()  # r2 rows [0, 8) against A
    assert r2.rows_scored == 8
    server.swap_bank(B)
    stats = server.run()  # r2 rows [8, 12) against B
    assert r2.done and stats.bank_swaps == 1
    np.testing.assert_array_equal(r1.result, _jnp_scores(r1.queries, A))
    np.testing.assert_array_equal(r2.result[:8], _jnp_scores(r2.queries[:8], A))
    np.testing.assert_array_equal(r2.result[8:], _jnp_scores(r2.queries[8:], B))


def test_hot_swap_same_shape_never_recompiles():
    rng = np.random.default_rng(7)
    server = BankServer(rng.normal(size=(8, 8)).astype(np.float32), q_block=8)
    server.score(rng.normal(size=(3, 8)).astype(np.float32))  # compile once
    start = predict_bank._cache_size()
    for seed in range(3):
        server.swap_bank(
            np.random.default_rng(seed).normal(size=(8, 8)).astype(np.float32)
        )
        server.score(rng.normal(size=(3, 8)).astype(np.float32))
    assert predict_bank._cache_size() == start  # swaps reused the jit entry


def test_swap_and_submit_validate_shapes():
    rng = np.random.default_rng(8)
    server = BankServer(rng.normal(size=(6, 8)).astype(np.float32), q_block=8)
    with pytest.raises(ValueError, match="hot-swap"):
        server.swap_bank(rng.normal(size=(6, 10)).astype(np.float32))
    with pytest.raises(ValueError, match=r"\(n, D=8\)"):
        server.submit(rng.normal(size=(4, 5)).astype(np.float32))
    with pytest.raises(ValueError, match="n_classes"):
        BankServer(rng.normal(size=(6, 8)).astype(np.float32), epilogue="ovr",
                   n_classes=4)
    with pytest.raises(ValueError, match="k="):
        BankServer(rng.normal(size=(6, 8)).astype(np.float32),
                   epilogue="topk", k=9)
    with pytest.raises(ValueError, match="epilogue"):
        BankServer(rng.normal(size=(6, 8)).astype(np.float32),
                   epilogue="softmax")


def test_from_checkpoint_rejects_non_bank_trees(tmp_path):
    path = str(tmp_path / "notabank")
    ckpt.save(path, {"a": jnp.zeros((3,)), "b": jnp.ones((2, 2))})
    with pytest.raises(ValueError, match="4-leaf"):
        BankServer.from_checkpoint(path)


# ---------------------------------------------------------------------------
# Request stamps and the step's phase spans
# ---------------------------------------------------------------------------

PHASES = ("serve.pack", "serve.copy_in", "serve.launch", "serve.readback",
          "serve.scatter")


def test_request_stamps_are_ordered():
    rng = np.random.default_rng(10)
    server = BankServer(rng.normal(size=(8, 8)).astype(np.float32), q_block=8)
    reqs = [server.submit(rng.normal(size=(n, 8)).astype(np.float32))
            for n in (5, 11, 2, 8)]
    assert all(np.isnan(r.t_first) and np.isnan(r.t_done) for r in reqs)
    server.run()
    for r in reqs:
        assert r.t_submit <= r.t_first <= r.t_done
    assert [r.t_submit for r in reqs] == sorted(r.t_submit for r in reqs)


def test_a_request_over_several_steps_is_stamped_by_its_first_and_last():
    """t_first comes from the step that packs the first row, t_done from
    the one that scatters the last; requests packed or finished in one step
    share its stamp."""
    rng = np.random.default_rng(11)
    server = BankServer(rng.normal(size=(8, 8)).astype(np.float32), q_block=8)
    big = server.submit(rng.normal(size=(20, 8)).astype(np.float32))
    small = server.submit(rng.normal(size=(3, 8)).astype(np.float32))
    server.step()  # big's rows 0-7
    first = big.t_first
    assert np.isfinite(first) and np.isnan(big.t_done)
    assert np.isnan(small.t_first)
    server.step()  # big's rows 8-15
    assert big.t_first == first and np.isnan(big.t_done)
    server.step()  # big's rows 16-19 and all of small
    assert big.done and small.done
    assert big.t_first == first < small.t_first
    assert big.t_done == small.t_done > small.t_first


def test_a_zero_row_request_is_stamped_at_submit():
    server = BankServer(np.eye(4, dtype=np.float32), q_block=8)
    req = server.submit(np.zeros((0, 4), np.float32))
    assert np.isfinite(req.t_submit)
    assert req.t_submit == req.t_first == req.t_done


def test_each_step_emits_the_five_phase_spans_in_order(tmp_path):
    """Under a profiler capture every step records the five phase spans
    once each, in order, each starting where the last one ended."""
    import glob

    from jax.profiler import ProfileData

    rng = np.random.default_rng(12)
    server = BankServer(rng.normal(size=(8, 8)).astype(np.float32), q_block=8)
    server.score(rng.normal(size=(8, 8)).astype(np.float32))  # compile
    for n in (20, 3):
        server.submit(rng.normal(size=(n, 8)).astype(np.float32))
    steps = server.stats.steps
    jax.profiler.start_trace(str(tmp_path))
    server.run()
    jax.profiler.stop_trace()
    steps = server.stats.steps - steps
    assert steps == 3
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[-1]
    spans = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("serve.")
    )
    assert [name for _, _, name in spans] == list(PHASES) * steps
    for i in range(steps):
        step = spans[i * len(PHASES):(i + 1) * len(PHASES)]
        gaps = [b[0] - a[1] for a, b in zip(step, step[1:])]
        assert min(gaps) >= 0
        # the spans cover the step: what lies between them is the
        # annotations' own exit and entry, a few microseconds
        assert sum(gaps) < 0.1 * (step[-1][1] - step[0][0])

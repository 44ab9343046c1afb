"""Checkpoint/restart, straggler range re-assignment, elastic remesh."""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import ckpt
from repro.runtime import (
    InjectedFailure,
    RetryPolicy,
    StragglerPolicy,
    rebalance_ranges,
    run_with_restarts,
)


def _toy_step():
    def step(state, batch):
        w = state["w"] + jnp.sum(batch)
        return {"w": w, "n": state["n"] + 1}, {"w_sum": float(jnp.sum(w))}

    return step


def test_restart_bit_equivalent(tmp_path):
    """Crash at steps 3 and 7 -> same final state as the uninterrupted run."""
    batches = [jnp.full((4,), i, jnp.float32) for i in range(10)]
    init = {"w": jnp.zeros((4,)), "n": jnp.zeros((), jnp.int32)}

    clean, _ = run_with_restarts(
        _toy_step(), init, batches, ckpt_dir=str(tmp_path / "a"), ckpt_every=2
    )
    crashy, report = run_with_restarts(
        _toy_step(), init, batches, ckpt_dir=str(tmp_path / "b"), ckpt_every=2,
        fail_at=[3, 7],
    )
    assert report.restarts == 2
    np.testing.assert_array_equal(np.asarray(clean["w"]), np.asarray(crashy["w"]))
    assert int(clean["n"]) == int(crashy["n"]) == 10


def test_restart_metrics_match_uninterrupted(tmp_path):
    """Steps re-run after a crash must not duplicate their metrics entries:
    RunReport.metrics of a crashy run == the uninterrupted run's, entry for
    entry (the resume path truncates the log back to the restored step)."""
    batches = [jnp.full((4,), i, jnp.float32) for i in range(10)]
    init = {"w": jnp.zeros((4,)), "n": jnp.zeros((), jnp.int32)}

    # ckpt_every=4 with crashes at 3 and 7: both crashes land steps past the
    # last durable checkpoint, so their metrics entries are already logged
    # and would duplicate without the resume-path truncation.
    _, clean_report = run_with_restarts(
        _toy_step(), init, batches, ckpt_dir=str(tmp_path / "a"), ckpt_every=4
    )
    _, crashy_report = run_with_restarts(
        _toy_step(), init, batches, ckpt_dir=str(tmp_path / "b"), ckpt_every=4,
        fail_at=[3, 7],
    )
    assert len(clean_report.metrics) == len(batches)
    assert crashy_report.metrics == clean_report.metrics


def test_rebalance_ranges_deterministic():
    """The re-issued work queues must not depend on set iteration order —
    dead shards are processed in sorted order whatever the input order."""
    ranges = [(0, 97), (97, 200), (200, 311), (311, 400), (400, 500)]
    outs = [
        rebalance_ranges(ranges, dead=order)
        for order in ([1, 3], [3, 1], {3, 1}, iter((3, 1)))
    ]
    assert all(o == outs[0] for o in outs[1:])


def test_rebalance_ranges_all_dead_raises():
    with pytest.raises(ValueError, match="no survivors"):
        rebalance_ranges([(0, 10), (10, 20)], dead=[0, 1])


def test_ckpt_roundtrip_dtypes(tmp_path):
    tree = {
        "a": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
        "b": [jnp.ones((2,), jnp.int32), {"c": jnp.zeros((5,), jnp.float32)}],
    }
    ckpt.save(str(tmp_path / "c"), tree, meta={"step": 5})
    out = ckpt.restore(str(tmp_path / "c"), tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
    assert ckpt.load_meta(str(tmp_path / "c"))["step"] == 5


def test_rebalance_ranges_exact_cover():
    ranges = [(0, 100), (100, 200), (200, 300), (300, 400)]
    out = rebalance_ranges(ranges, dead=[1, 3])
    covered = sorted(out)
    # every index in [0,400) covered exactly once
    seen = np.zeros(400, np.int32)
    for lo, hi in covered:
        seen[lo:hi] += 1
    assert (seen == 1).all()


def test_straggler_policy():
    pol = StragglerPolicy(deadline_factor=3.0)
    assert pol.stragglers([1.0, 1.1, 0.9, 10.0]) == [3]
    assert pol.stragglers([1.0, 1.1, 0.9]) == []


def test_straggler_policy_median_rule():
    """The deadline rule pinned down: even length takes the UPPER median
    (sorted[n // 2]), the comparison is strictly greater-than, and
    degenerate inputs (all equal, empty, zero median) behave."""
    pol = StragglerPolicy(deadline_factor=3.0)
    # even length: sorted [1,2,3,10] -> median sorted[2] = 3, deadline 9
    assert pol.stragglers([1.0, 10.0, 2.0, 3.0]) == [1]
    # exactly AT the deadline is not straggling (strict >)
    assert pol.stragglers([1.0, 9.0, 2.0, 3.0]) == []
    assert pol.stragglers([9.001, 1.0, 2.0, 3.0]) == [0]
    # all-equal shards can never straggle, whatever the factor
    assert pol.stragglers([5.0] * 6) == []
    assert StragglerPolicy(deadline_factor=1.0).stragglers([5.0] * 3) == []
    # no shards, no stragglers (and no median to divide by)
    assert pol.stragglers([]) == []
    # zero median: the 1e-9 floor keeps the rule meaningful — any shard
    # doing real work while the median is idle is flagged
    assert pol.stragglers([0.0, 0.0, 1e-6]) == [2]


def test_streamsvm_restart_preserves_one_pass(tmp_path):
    """A preempted one-pass SVM run resumes mid-stream bit-identically."""
    from repro.core import fit, fit_chunked, StreamCheckpoint
    from repro.core.meb import Ball
    from repro.data.stream import chunk_stream

    rng = np.random.default_rng(0)
    X = rng.normal(size=(2000, 16)).astype(np.float32)
    y = np.sign(rng.normal(size=2000) + X[:, 0]).astype(np.float32)
    full = fit(jnp.asarray(X), jnp.asarray(y), 10.0)

    # consume half, checkpoint to disk, "crash", restore, finish
    half = fit_chunked(chunk_stream(X[:1000], y[:1000], 250), 10.0)
    ckpt.save(str(tmp_path / "svm"), half.ball, meta={"position": half.position})
    restored_ball = ckpt.restore(str(tmp_path / "svm"), half.ball)
    pos = ckpt.load_meta(str(tmp_path / "svm"))["position"]
    done = fit_chunked(
        chunk_stream(X, y, 250, start=pos), 10.0,
        resume=StreamCheckpoint(ball=restored_ball, position=pos),
    )
    np.testing.assert_allclose(
        np.asarray(done.ball.w), np.asarray(full.w), rtol=1e-5, atol=1e-6
    )
    assert int(done.ball.m) == int(full.m)


# ---------------------------------------------------------------------------
# Satellite: atomic checkpoint commit — torn payloads refuse loudly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("keep", [0.5, 0.0])
def test_torn_arrays_payload_raises(tmp_path, keep):
    """A truncated arrays file (a torn write that somehow got committed, or
    bit rot) must raise a ValueError naming the file — never restore junk."""
    tree = {"w": jnp.arange(64, dtype=jnp.float32), "n": jnp.ones((3,))}
    d = str(tmp_path / "c")
    ckpt.save(d, tree, meta={"step": 1})
    arrays_file = ckpt.load_manifest(d)["arrays_file"]
    p = os.path.join(d, arrays_file)
    with open(p, "rb") as f:
        raw = f.read()
    with open(p, "wb") as f:
        f.write(raw[: int(len(raw) * keep)])
    with pytest.raises(ValueError, match="torn or corrupt") as ei:
        ckpt.restore(d, tree)
    assert arrays_file in str(ei.value)


def test_crash_mid_save_preserves_previous_checkpoint(tmp_path, monkeypatch):
    """A save that dies while writing its arrays payload must leave the
    previous commit fully restorable — and the next good save sweeps the
    debris."""
    d = str(tmp_path / "c")
    v1 = {"w": jnp.arange(4, dtype=jnp.float32)}
    ckpt.save(d, v1, meta={"step": 1})

    def disk_full(*a, **k):
        raise OSError("No space left on device")

    with monkeypatch.context() as m:
        m.setattr(np, "savez", disk_full)
        with pytest.raises(OSError):
            ckpt.save(d, {"w": jnp.full((4,), 9.0)}, meta={"step": 2})

    # the old commit is untouched: same meta, same bytes
    assert ckpt.exists(d)
    assert ckpt.load_meta(d)["step"] == 1
    np.testing.assert_array_equal(
        np.asarray(ckpt.restore(d, v1)["w"]), np.asarray(v1["w"])
    )
    # a subsequent good save commits and GCs every stale arrays/tmp file
    ckpt.save(d, {"w": jnp.full((4,), 9.0)}, meta={"step": 2})
    assert ckpt.load_meta(d)["step"] == 2
    files = sorted(os.listdir(d))
    assert files == sorted(
        ["manifest.json", ckpt.load_manifest(d)["arrays_file"]]
    )


def test_restore_leaf_count_mismatch_raises(tmp_path):
    """The bare assert became a ValueError carrying both counts + path."""
    d = str(tmp_path / "c")
    ckpt.save(d, {"a": jnp.zeros((3,)), "b": jnp.ones((2,))})
    with pytest.raises(ValueError) as ei:
        ckpt.restore(d, {"a": jnp.zeros((3,))})
    msg = str(ei.value)
    assert "holds 2 leaves" in msg and "target has 1" in msg and d in msg


@pytest.mark.slow
def test_ckpt_guards_survive_python_O(tmp_path):
    """`python -O` strips asserts; the restore guards must be ValueErrors.
    (Extends the PR-6 guard suite in test_kernel_bank.py to checkpointing.)"""
    script = r"""
import sys
import jax.numpy as jnp
from repro.checkpoint import ckpt
from repro.core import fold_banks

d = sys.argv[1]
ckpt.save(d, {"a": jnp.zeros((3,)), "b": jnp.ones((2,))})

try:  # 1) restore-target structure mismatch
    ckpt.restore(d, {"a": jnp.zeros((3,))})
except ValueError as e:
    assert "holds 2 leaves" in str(e) and "target has 1" in str(e), e
    print("LEAVES_OK")

import os
arrays = os.path.join(d, ckpt.load_manifest(d)["arrays_file"])
with open(arrays, "wb") as f:
    f.write(b"\x00not a zip")
try:  # 2) torn arrays payload
    ckpt.restore(d, {"a": jnp.zeros((3,)), "b": jnp.ones((2,))})
except ValueError as e:
    assert "torn or corrupt" in str(e), e
    print("TORN_OK")

try:  # 3) empty fold in the live loop's merge helper
    fold_banks([])
except ValueError as e:
    assert "empty" in str(e), e
    print("FOLD_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-O", "-c", script, str(tmp_path / "c")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, (
        f"stdout:{out.stdout[-2000:]}\nstderr:{out.stderr[-4000:]}"
    )
    for token in ("LEAVES_OK", "TORN_OK", "FOLD_OK"):
        assert token in out.stdout, out.stdout


# ---------------------------------------------------------------------------
# Satellite: run_with_restarts — real failure classification + backoff
# ---------------------------------------------------------------------------


def test_retry_policy_delay_and_classification():
    pol = RetryPolicy(retryable=(OSError,), backoff_base=0.1, backoff_cap=0.5)
    assert [pol.delay(a) for a in range(4)] == [0.1, 0.2, 0.4, 0.5]
    assert pol.is_retryable(OSError("blip"))
    assert pol.is_retryable(FileNotFoundError("subclass counts"))
    assert not pol.is_retryable(ValueError("bug"))
    assert RetryPolicy().is_retryable(InjectedFailure("default"))


def test_run_with_restarts_retries_declared_transients(tmp_path):
    """An exception class named in `retryable` restarts from the checkpoint
    (one backoff slept); the result matches the clean run."""
    batches = [jnp.full((2,), i, jnp.float32) for i in range(6)]
    init = {"w": jnp.zeros((2,)), "n": jnp.zeros((), jnp.int32)}
    calls = {"n": 0}

    def flaky_step(state, batch):
        calls["n"] += 1
        if calls["n"] == 3:
            raise OSError("transient fs blip")
        return _toy_step()(state, batch)

    delays = []
    state, report = run_with_restarts(
        flaky_step, init, batches, ckpt_dir=str(tmp_path / "a"), ckpt_every=2,
        retryable=(InjectedFailure, OSError), sleep=delays.append,
    )
    assert report.restarts == 1 and delays == [0.05]
    clean, _ = run_with_restarts(
        _toy_step(), init, batches, ckpt_dir=str(tmp_path / "b"), ckpt_every=2
    )
    np.testing.assert_array_equal(np.asarray(state["w"]), np.asarray(clean["w"]))
    assert int(state["n"]) == 6


def test_run_with_restarts_programming_error_propagates(tmp_path):
    """A ValueError is a bug: no restart burned, no backoff slept — it
    surfaces on the FIRST occurrence."""
    batches = [jnp.full((2,), i, jnp.float32) for i in range(6)]
    init = {"w": jnp.zeros((2,)), "n": jnp.zeros((), jnp.int32)}

    def bad_step(state, batch):
        raise ValueError("shape mismatch — a bug, not infrastructure")

    delays = []
    with pytest.raises(ValueError, match="a bug"):
        run_with_restarts(
            bad_step, init, batches, ckpt_dir=str(tmp_path / "a"),
            sleep=delays.append,
        )
    assert delays == []


def test_run_with_restarts_backoff_capped_exponential(tmp_path):
    """Consecutive restarts back off base * 2**k up to the cap."""
    batches = [jnp.full((2,), i, jnp.float32) for i in range(10)]
    init = {"w": jnp.zeros((2,)), "n": jnp.zeros((), jnp.int32)}
    delays = []
    _, report = run_with_restarts(
        _toy_step(), init, batches, ckpt_dir=str(tmp_path / "a"),
        ckpt_every=100, fail_at=[2, 4, 6, 8],
        backoff_base=0.05, backoff_cap=0.12, sleep=delays.append,
    )
    assert report.restarts == 4
    assert delays == [0.05, 0.1, 0.12, 0.12]


# ---------------------------------------------------------------------------
# Satellite: straggler mitigation end to end — re-issued ranges through the
# real trainer and the Sec-4.3 fold
# ---------------------------------------------------------------------------

_SD, _SB = 8, 2
_SCS = jnp.asarray([1.0, 4.0], jnp.float32)


def _shard_data(n, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, _SD)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    y = np.sign(rng.normal(size=n) + X[:, 0]).astype(np.float32)
    return X, np.tile(y, (_SB, 1))


def _bank_for_ranges(X, Y, ranges):
    from repro.core import fit_bank

    return [
        fit_bank(jnp.asarray(X[lo:hi]), jnp.asarray(Y[:, lo:hi]), _SCS)
        for lo, hi in ranges
    ]


def test_straggler_reissue_bit_exact(tmp_path):
    """A dead trailing shard's range re-issued to the lone survivor is the
    SAME partition in the SAME order — the folded bank is bit-identical
    (np.array_equal) to the no-straggler run, not merely close."""
    from repro.core import fold_banks

    X, Y = _shard_data(256)
    ranges = [(0, 128), (128, 256)]

    clean = fold_banks(_bank_for_ranges(X, Y, ranges))

    # shard 1 never heartbeats; its whole range (nothing acked) re-issues
    reissued = rebalance_ranges(ranges, dead=[1])
    assert reissued == ranges  # unsplit, order preserved
    recovered = fold_banks(_bank_for_ranges(X, Y, reissued))

    for a, b in zip(clean, recovered):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_detected_reissue_cover_and_enclosure(tmp_path):
    """Policy-detected straggler, partial ack: the un-acked suffix re-issues
    across survivors. The executed ranges cover [0, N) exactly once and the
    folded bank encloses every per-range sub-bank (the Sec-4.3 merge
    invariant), per model lane."""
    from repro.core import center_distance, fold_banks, merge_banks

    N = 256
    X, Y = _shard_data(N, seed=11)
    ranges = [(0, 64), (64, 128), (128, 192), (192, 256)]

    pol = StragglerPolicy(deadline_factor=3.0)
    elapsed = [1.0, 1.1, 0.9, 50.0]
    assert pol.stragglers(elapsed) == [3]

    # shard 3 acked up to 224; [224, 256) re-issues across the survivors
    acked = (192, 224)
    reissued = rebalance_ranges(
        [(0, 64), (64, 128), (128, 192), (224, 256)], dead=[3]
    )
    executed = reissued + [acked]

    # exact cover: every stream index trained exactly once
    seen = np.zeros(N, np.int32)
    for lo, hi in executed:
        seen[lo:hi] += 1
    assert (seen == 1).all()

    banks = _bank_for_ranges(X, Y, executed)
    # Enclosure, checked where the disjoint-slack distance formula is valid:
    # at every fold step the operands hold disjoint example sets, and the
    # merged radius must be exactly the two-ball enclosing radius
    # max(r1, r2, (r1 + r2 + d)/2), per model lane.
    acc = banks[0]
    for bank in banks[1:]:
        d = np.asarray(jax.vmap(center_distance)(acc, bank))
        r1, r2 = np.asarray(acc.r), np.asarray(bank.r)
        acc = merge_banks(acc, bank)
        np.testing.assert_allclose(
            np.asarray(acc.r),
            np.maximum.reduce([r1, r2, 0.5 * (r1 + r2 + d)]),
            rtol=1e-5, atol=1e-6,
        )
    merged = fold_banks(banks)
    np.testing.assert_allclose(
        np.asarray(merged.r), np.asarray(acc.r), rtol=1e-6, atol=1e-7
    )
    assert int(np.asarray(merged.m).sum()) == sum(
        int(m) for b in banks for m in np.asarray(b.m)
    )


# ---------------------------------------------------------------------------
# Satellite: elastic range arithmetic — shard_ranges + grouped re-issue
# ---------------------------------------------------------------------------


def test_shard_ranges_properties():
    """Ceil partition: always n_shards entries, exact disjoint cover of
    [0, n), widths within one ceil step, EMPTY (n, n) tails when shards
    outnumber rows — the logical fold structure every execution substrate
    must agree on."""
    from repro.core import shard_ranges

    for n, k in [(32, 4), (7, 5), (100, 8), (1, 3), (0, 4), (8, 8), (9, 2)]:
        ranges = shard_ranges(n, k)
        assert len(ranges) == k
        seen = np.zeros(max(n, 1), np.int32)
        for lo, hi in ranges:
            assert 0 <= lo <= hi <= n
            seen[lo:hi] += 1
        assert (seen[:n] == 1).all()
        shard_n = -(-n // k) if n else 0
        assert all(hi - lo <= shard_n for lo, hi in ranges)
        # nonempty ranges come first; empties are the trailing shards
        widths = [hi - lo for lo, hi in ranges]
        assert widths == sorted(widths, reverse=True) or n % k == 0
    assert shard_ranges(7, 5) == [(0, 2), (2, 4), (4, 6), (6, 7), (7, 7)]
    assert shard_ranges(0, 3) == [(0, 0), (0, 0), (0, 0)]
    with pytest.raises(ValueError, match="n_shards"):
        shard_ranges(10, 0)
    with pytest.raises(ValueError, match="n"):
        shard_ranges(-1, 2)


def test_rebalance_ranges_grouped_queues():
    """grouped=True keys the re-issued work by SURVIVOR — each survivor's
    own range first, dead ranges split round-robin behind it — and the
    flattened queues cover exactly what the flat form covers."""
    ranges = [(0, 100), (100, 200), (200, 300), (300, 400)]
    queues = rebalance_ranges(ranges, dead=[1, 3], grouped=True)
    assert sorted(queues) == [0, 2]  # only survivors own queues
    assert queues[0][0] == (0, 100) and queues[2][0] == (200, 300)
    seen = np.zeros(400, np.int32)
    for work in queues.values():
        for lo, hi in work:
            seen[lo:hi] += 1
    assert (seen == 1).all()
    # determinism: dead order / container type never changes the queues
    for order in ([3, 1], {3, 1}, iter((3, 1))):
        assert rebalance_ranges(ranges, dead=order, grouped=True) == queues
    with pytest.raises(ValueError, match="no survivors"):
        rebalance_ranges(ranges, dead=[0, 1, 2, 3], grouped=True)


# ---------------------------------------------------------------------------
# Satellite: JAX/XLA runtime device errors are retryable infrastructure
# ---------------------------------------------------------------------------


def test_runtime_device_errors_classification():
    """The default live retry policy treats a device falling over —
    JaxRuntimeError and our DeviceLostError — as retryable infrastructure,
    while programming errors stay fatal."""
    from jax.errors import JaxRuntimeError

    from repro.runtime import (
        DeviceLostError,
        default_live_retryable,
        runtime_device_errors,
    )

    errs = runtime_device_errors()
    assert errs == (JaxRuntimeError,)

    retryable = default_live_retryable()
    assert InjectedFailure in retryable
    assert DeviceLostError in retryable
    assert JaxRuntimeError in retryable
    assert issubclass(DeviceLostError, RuntimeError)

    pol = RetryPolicy(retryable=retryable)
    assert pol.is_retryable(JaxRuntimeError("device lost"))
    assert pol.is_retryable(DeviceLostError("shard 3 gone"))
    assert not pol.is_retryable(ValueError("a bug"))
    assert not pol.is_retryable(TypeError("a bug"))


def test_live_restarts_classify_xla_runtime_error(tmp_path):
    """A source whose fetch dies once with a real JaxRuntimeError (the
    exception XLA raises when a device drops out) burns ONE restart under
    run_live_with_restarts' default policy and completes bit-identically
    to the clean run — satellite contract for device-loss recovery."""
    from jax.errors import JaxRuntimeError

    from repro.live import ArraySource, LiveBank, run_live_with_restarts

    rng = np.random.default_rng(5)
    X = rng.normal(size=(6 * 16, 4)).astype(np.float32)
    y = np.sign(rng.normal(size=X.shape[0]) + X[:, 0]).astype(np.float32)
    y[y == 0] = 1.0
    cs = jnp.asarray([1.0, 4.0])

    def make(ckpt_dir, source):
        return LiveBank(
            source, cs, ckpt_dir=str(ckpt_dir), n_sub_banks=2,
            rotate_every=3, swap_every=2, sleep=lambda s: None,
        )

    clean = make(tmp_path / "a", ArraySource(X, y, 16))
    ref_stats = clean.run()

    inner = ArraySource(X, y, 16)
    state = {"raised": False}

    def dying_device_source(i):
        if i == 3 and not state["raised"]:
            state["raised"] = True
            raise JaxRuntimeError("INTERNAL: device CPU_3 lost")
        return inner(i)

    crashy = make(tmp_path / "b", dying_device_source)
    stats = run_live_with_restarts(crashy, sleep=lambda s: None)
    # the fetch-level RetryPolicy does NOT retry runtime device errors in
    # place (retrying on a dead device spins); they escalate to a restart,
    # which re-enters from the durable checkpoint
    assert stats.restarts == 1 and stats.retries == 0
    assert stats.durable() == ref_stats.durable()
    for a, b in zip(crashy.serving_bank(), clean.serving_bank()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

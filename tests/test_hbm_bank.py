"""HBM-resident double-buffered bank: parity, policy, preflight, recompiles.

The HBM layout re-routes the bank (plus state and lookahead windows) through
ANY-space buffers and a 2-slot VMEM ring, but shares the per-(block x tile)
compute core with the VMEM layout — so it must be BIT-EXACT (f32) with it
across every ring regime (J = 1, 2 resident tiles; J odd/even cycling),
ragged banks, bf16 stream tiles and fused lookahead. The "auto" policy must
flip residency exactly at the VMEM-budget boundary, impossible configs must
die in the ops.py preflight with the byte breakdown (never inside Pallas
lowering — and never silently under ``python -O``), and a residency switch
must recompile while a C sweep must not.
"""
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import fit_bank, fit_ovr, ovr_signs
from repro.kernels import streamsvm_fit_many
from repro.kernels.ops import (
    DEFAULT_VMEM_BUDGET_BYTES,
    engine_vmem_bytes,
    predict_vmem_bytes,
    resolve_bank_resident,
    vmem_budget_bytes,
)
from repro.kernels.ref import streamsvm_scan_many_ref


def _bank_data(b, n, d, seed):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Y = jnp.asarray(np.sign(rng.normal(size=(b, n))).astype(np.float32))
    cs = jnp.asarray(np.exp(rng.uniform(-1, 4, size=b)).astype(np.float32))
    return X, Y, cs


def _assert_banks_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.w), np.asarray(b.w))
    np.testing.assert_array_equal(np.asarray(a.r), np.asarray(b.r))
    np.testing.assert_array_equal(np.asarray(a.xi2), np.asarray(b.xi2))
    np.testing.assert_array_equal(np.asarray(a.m), np.asarray(b.m))


# ---------------------------------------------------------------------------
# Tentpole: hbm == vmem, bit for bit, across every ring regime
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,n,d,block_n,b_tile", [
    (8, 300, 20, 64, 8),       # J=1: nothing cycles (load once / store once)
    (16, 300, 20, 64, 8),      # J=2: slot-pinned tiles, still no cycling
    (24, 384, 24, 128, 8),     # J=3: odd tile count cycling through 2 slots
    (64, 300, 20, 64, 8),      # J=8: steady-state ring over 5 data blocks
    (11, 257, 33, 64, 8),      # ragged B % b_tile != 0 (padded inert lanes)
    (13, 300, 20, 64, 3),      # b_tile not a multiple of 8 (rounded up)
    (40, 128, 40, 256, 8),     # single data block: prefetch chain only
])
def test_hbm_bit_exact_with_vmem(b, n, d, block_n, b_tile):
    """The residency switch must not change a single bit of f32 output."""
    X, Y, cs = _bank_data(b, n, d, seed=b * n + d)
    kw = dict(block_n=block_n, b_tile=b_tile)
    vmem = streamsvm_fit_many(X, Y, cs, bank_resident="vmem", **kw)
    hbm = streamsvm_fit_many(X, Y, cs, bank_resident="hbm", **kw)
    _assert_banks_equal(hbm, vmem)
    assert np.isfinite(np.asarray(hbm.w)).all()


@pytest.mark.parametrize("lookahead", [2, 5, (3, 1, 7, 2) * 6])
def test_hbm_lookahead_bit_exact_with_vmem(lookahead):
    """Fused Algorithm 2: the (B*L, D) windows ride the same ring — per-model
    L, window state crossing block AND tile boundaries, boundary flush."""
    b, n, d = 24, 333, 20
    X, Y, cs = _bank_data(b, n, d, seed=7)
    kw = dict(variant="lookahead", lookahead=lookahead, block_n=64, b_tile=8)
    vmem = streamsvm_fit_many(X, Y, cs, bank_resident="vmem", **kw)
    hbm = streamsvm_fit_many(X, Y, cs, bank_resident="hbm", **kw)
    _assert_banks_equal(hbm, vmem)


def test_hbm_bf16_stream_tiles_bit_exact_with_vmem():
    """bf16 stream tiles: rounding must be identical in both residencies
    (the ring carries the f32 bank; only BlockSpec'd stream tiles are bf16)."""
    b, n, d = 24, 300, 24
    X, Y, cs = _bank_data(b, n, d, seed=11)
    kw = dict(block_n=64, b_tile=8, stream_dtype="bf16")
    vmem = streamsvm_fit_many(X, Y, cs, bank_resident="vmem", **kw)
    hbm = streamsvm_fit_many(X, Y, cs, bank_resident="hbm", **kw)
    _assert_banks_equal(hbm, vmem)


def test_hbm_matches_bank_oracle():
    """Not just self-consistency: the hbm path against the pure-jnp oracle."""
    b, n, d = 32, 400, 24
    X, Y, cs = _bank_data(b, n, d, seed=17)
    bank = streamsvm_fit_many(
        X, Y, cs, block_n=128, b_tile=8, bank_resident="hbm"
    )
    c_inv = 1.0 / cs
    W0 = Y[:, 0:1] * X[0][None, :]
    w, r, xi2, m = streamsvm_scan_many_ref(
        X[1:], Y[:, 1:], W0, 0.0, c_inv, c_inv, 1, gain=c_inv
    )
    np.testing.assert_allclose(
        np.asarray(bank.w), np.asarray(w), rtol=2e-4, atol=2e-5
    )
    np.testing.assert_array_equal(np.asarray(bank.m), np.asarray(m))


def test_hbm_continue_from_bank_and_wrappers():
    """fit_bank continue-from-bank and fit_ovr route residency through."""
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(220, 16)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 6, size=220))
    o_v = fit_ovr(X, labels, 6, 10.0, b_tile=8, bank_resident="vmem")
    o_h = fit_ovr(X, labels, 6, 10.0, b_tile=8, bank_resident="hbm")
    np.testing.assert_array_equal(np.asarray(o_h.w), np.asarray(o_v.w))
    ys = ovr_signs(labels, 6)
    half_h = fit_bank(X[:100], ys[:, :100], 10.0, b_tile=8,
                      bank_resident="hbm")
    cont_h = fit_bank(X[100:], ys[:, 100:], 10.0, half_h, b_tile=8,
                      bank_resident="hbm")
    half_v = fit_bank(X[:100], ys[:, :100], 10.0, b_tile=8,
                      bank_resident="vmem")
    cont_v = fit_bank(X[100:], ys[:, 100:], 10.0, half_v, b_tile=8,
                      bank_resident="vmem")
    _assert_banks_equal(cont_h, cont_v)


# ---------------------------------------------------------------------------
# The "auto" policy: routing at the budget boundary
# ---------------------------------------------------------------------------


def test_auto_routes_at_budget_boundary():
    """auto == vmem exactly AT the vmem working-set total, hbm one byte under.

    B = 8 * b_tile so the full-bank vmem scratch strictly exceeds the 2-slot
    ring and the boundary separates the two regimes."""
    model = lambda res: engine_vmem_bytes(
        64, 64, block_n=128, b_tile=8, bank_resident=res
    )
    total = sum(model("vmem").values())
    res, by = resolve_bank_resident(
        "auto", model, vmem_budget=total, what="t", shapes="s"
    )
    assert res == "vmem" and by == model("vmem")
    res, by = resolve_bank_resident(
        "auto", model, vmem_budget=total - 1, what="t", shapes="s"
    )
    assert res == "hbm" and by == model("hbm")


def test_auto_hbm_routing_is_bit_exact_end_to_end():
    """A budget too small for the vmem working set must silently route auto
    to hbm and produce the identical bank."""
    b, n, d = 24, 300, 20
    X, Y, cs = _bank_data(b, n, d, seed=23)
    vmem = streamsvm_fit_many(X, Y, cs, block_n=64, b_tile=8,
                              bank_resident="vmem")
    model = lambda res: engine_vmem_bytes(
        b, d, block_n=64, b_tile=8, bank_resident=res
    )
    squeeze = sum(model("vmem").values()) - 1
    assert sum(model("hbm").values()) <= squeeze  # hbm fits where vmem won't
    auto = streamsvm_fit_many(X, Y, cs, block_n=64, b_tile=8,
                              bank_resident="auto",
                              vmem_budget_bytes=squeeze)
    _assert_banks_equal(auto, vmem)


def test_auto_derives_ring_tile_when_none_given():
    """With the default b_tile=None, an over-budget bank must still train:
    auto/hbm derive a budget-fitting ring tile instead of trying to ring the
    whole bank (which would be twice the bank per step) — so the ROADMAP's
    "auto picks this for you" holds without hand-picking a tile."""
    b, n, d = 64, 256, 64
    X, Y, cs = _bank_data(b, n, d, seed=29)
    ref = streamsvm_fit_many(X, Y, cs, block_n=64, bank_resident="vmem")
    # budget fits the stream tiles + a small ring but NOT the whole bank:
    model = lambda res, bt: engine_vmem_bytes(
        b, d, block_n=64, b_tile=bt, bank_resident=res
    )
    squeeze = sum(model("hbm", 8).values()) + 1
    assert sum(model("vmem", None).values()) > squeeze
    assert sum(model("hbm", None).values()) > squeeze  # whole-bank ring: no
    for residency in ("auto", "hbm"):
        got = streamsvm_fit_many(X, Y, cs, block_n=64,
                                 bank_resident=residency,
                                 vmem_budget_bytes=squeeze)
        _assert_banks_equal(got, ref)
    # serving twin: same derivation on the predict side
    from repro.kernels import predict_bank

    Xq = X[:40]
    base = predict_bank(Xq, ref.w, q_block=64)
    got = predict_bank(Xq, ref.w, q_block=64, bank_resident="hbm",
                       vmem_budget_bytes=squeeze)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_vmem_budget_resolution_order():
    """Explicit override > REPRO_VMEM_BUDGET_BYTES env > default."""
    assert vmem_budget_bytes(123) == 123
    old = os.environ.get("REPRO_VMEM_BUDGET_BYTES")
    try:
        os.environ["REPRO_VMEM_BUDGET_BYTES"] = "456"
        assert vmem_budget_bytes() == 456
        assert vmem_budget_bytes(123) == 123
        del os.environ["REPRO_VMEM_BUDGET_BYTES"]
        assert vmem_budget_bytes() == DEFAULT_VMEM_BUDGET_BYTES
    finally:
        if old is not None:
            os.environ["REPRO_VMEM_BUDGET_BYTES"] = old
        else:
            os.environ.pop("REPRO_VMEM_BUDGET_BYTES", None)


def test_byte_model_scales_like_the_layouts():
    """vmem's working set grows with B; hbm's is B-independent (ring only)."""
    v64 = sum(engine_vmem_bytes(64, 128, b_tile=8,
                                bank_resident="vmem").values())
    v512 = sum(engine_vmem_bytes(512, 128, b_tile=8,
                                 bank_resident="vmem").values())
    h64 = sum(engine_vmem_bytes(64, 128, b_tile=8,
                                bank_resident="hbm").values())
    h512 = sum(engine_vmem_bytes(512, 128, b_tile=8,
                                 bank_resident="hbm").values())
    assert v512 > v64
    assert h512 == h64
    # lookahead windows dominate both models when L is large
    vl = engine_vmem_bytes(64, 128, b_tile=8, lookahead_max=16,
                           bank_resident="vmem")
    assert vl["lookahead"] > vl["bank"]
    # predict: the serving working set never contains the full bank
    p64 = sum(predict_vmem_bytes(64, 128, b_tile=8).values())
    p4096 = sum(predict_vmem_bytes(4096, 128, b_tile=8).values())
    assert p4096 == p64


# ---------------------------------------------------------------------------
# Preflight: impossible configs die in ops.py with the byte breakdown
# ---------------------------------------------------------------------------


def test_forced_vmem_beyond_budget_raises_with_breakdown():
    b, n, d = 16, 128, 64
    X, Y, cs = _bank_data(b, n, d, seed=1)
    with pytest.raises(ValueError) as ei:
        streamsvm_fit_many(X, Y, cs, block_n=128, b_tile=8,
                           bank_resident="vmem", vmem_budget_bytes=10_000)
    msg = str(ei.value)
    assert "breakdown" in msg and "bank_resident='vmem'" in msg
    assert f"B={b}" in msg and f"D={d}" in msg and "10000" in msg
    assert "hbm" in msg  # the error tells you the way out


def test_no_residency_fits_raises():
    b, n, d = 16, 128, 64
    X, Y, cs = _bank_data(b, n, d, seed=2)
    with pytest.raises(ValueError, match="shrink"):
        streamsvm_fit_many(X, Y, cs, block_n=128, b_tile=8,
                           bank_resident="hbm", vmem_budget_bytes=1_000)
    with pytest.raises(ValueError, match="shrink"):
        streamsvm_fit_many(X, Y, cs, block_n=128, b_tile=8,
                           bank_resident="auto", vmem_budget_bytes=1_000)


def test_unknown_residency_raises():
    X, Y, cs = _bank_data(8, 64, 16, seed=3)
    with pytest.raises(ValueError, match="bank_resident"):
        streamsvm_fit_many(X, Y, cs, bank_resident="sram")


@pytest.mark.slow
def test_vmem_preflight_error_survives_python_O():
    """The preflight must be a ValueError (not a bare assert) so `python -O`
    cannot strip it — a VMEM-overflowing bank must never reach Pallas
    lowering's opaque failure."""
    script = r"""
import numpy as np, jax.numpy as jnp
from repro.kernels import streamsvm_fit_many
X = jnp.zeros((128, 64), jnp.float32)
Y = jnp.ones((16, 128), jnp.float32)
cs = jnp.full((16,), 10.0, jnp.float32)
try:
    streamsvm_fit_many(X, Y, cs, block_n=128, b_tile=8,
                       bank_resident="vmem", vmem_budget_bytes=10_000)
except ValueError as e:
    msg = str(e)
    assert "breakdown" in msg and "B=16" in msg and "D=64" in msg, msg
    print("VALUE_ERROR_OK")
else:
    raise SystemExit("oversized vmem bank was accepted")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-O", "-c", script],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, (
        f"stdout:{out.stdout[-2000:]}\nstderr:{out.stderr[-4000:]}"
    )
    assert "VALUE_ERROR_OK" in out.stdout


# ---------------------------------------------------------------------------
# Compile-cache regression: residency is static, C stays traced
# ---------------------------------------------------------------------------


def test_residency_switch_recompiles_c_sweep_does_not():
    b, n, d = 16, 128, 16
    X, Y, _ = _bank_data(b, n, d, seed=5)
    start = streamsvm_fit_many._cache_size()
    for c in (1.0, 10.0, 100.0):  # C sweep inside hbm: ONE entry
        streamsvm_fit_many(X, Y, jnp.full((b,), c), block_n=64, b_tile=8,
                           bank_resident="hbm")
    assert streamsvm_fit_many._cache_size() == start + 1
    streamsvm_fit_many(X, Y, jnp.full((b,), 1.0), block_n=64, b_tile=8,
                       bank_resident="vmem")  # residency switch: new entry
    assert streamsvm_fit_many._cache_size() == start + 2


def test_quickstart_bank_trains_vmem_resident_by_default():
    """The ROADMAP quickstart bank (200 classes x 3 C = 600 models at
    D = 784) is over the default budget as one tile but fits VMEM-resident
    in derived tiles, while the beyond-VMEM bank (3000 x 4096) needs HBM
    residency with a derived ring tile — the two residencies chip_smoke.py
    drives through "auto" and "hbm"."""
    from repro.kernels.ops import derive_b_tile

    budget = DEFAULT_VMEM_BUDGET_BYTES
    small = lambda bt: engine_vmem_bytes(600, 784, b_tile=bt,
                                         bank_resident="vmem")
    bt = derive_b_tile(600, small, vmem_budget=budget)
    assert bt is not None and sum(small(bt).values()) <= budget
    assert resolve_bank_resident(
        "auto", lambda res: engine_vmem_bytes(600, 784, b_tile=bt,
                                              bank_resident=res),
        vmem_budget=budget, what="t", shapes="s",
    )[0] == "vmem"
    big = lambda bt: engine_vmem_bytes(3000, 4096, b_tile=bt,
                                       bank_resident="hbm")
    bt = derive_b_tile(3000, big, vmem_budget=budget)
    assert bt is not None and sum(big(bt).values()) <= budget
    for bt in (None, 512, 256, 128, 64, 32, 16, 8):
        assert sum(engine_vmem_bytes(3000, 4096, b_tile=bt,
                                     bank_resident="vmem").values()) > budget

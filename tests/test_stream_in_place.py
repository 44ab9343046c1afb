"""The bank engine reads the caller's stream and signs in place.

``kernels.streamsvm_fit_many`` seeds the bank from row 0 and starts the
kernel at row 1 of the caller's X and Y, leaves a ragged last row block to
the live-row mask and a ragged last bank tile to Y's own rows, and pads
only the (B,)- and (B, D)-sized state. Its results must be those of the
zero-padded copy the engine used to make, bit for bit: that copy is built
here by hand (rows 1.. of X and Y, zero rows and zero signs up to a whole
block, zero models up to a whole tile) and continued from the seed state,
which runs the kernel on it from its first row.

``core.fit_bank_sharded`` gives each shard its live row count instead of
padding the stream. On four virtual CPU devices (a child process, which
sets the device count before JAX starts) a ragged stream must give the
bits of the same fit on a stream the caller padded with inert sign-0 rows,
and agree with the plain reference of ``kernels/ref.py`` run per shard
range and folded in order.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.meb import Ball
from repro.kernels import streamsvm_fit_many
from repro.kernels.ops import engine_vmem_bytes, reads_in_place


def _data(b, n, d, seed):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Y = jnp.asarray(np.where(rng.random((b, n)) < 0.3, 1.0, -1.0).astype(np.float32))
    cs = jnp.asarray(np.exp(rng.uniform(-1, 3, size=b)).astype(np.float32))
    return X, Y, cs


def _padded_copy_fit(X, Y, cs, *, block_n, b_tile=None, **kw):
    """The fit of a zero-padded copy of rows 1.. of the stream, continued
    from the seed state row 0 gives (exact slack gain)."""
    n, d = X.shape
    b = Y.shape[0]
    rows = -(-(n - 1) // block_n) * block_n
    Xp = jnp.zeros((rows, -(-d // 128) * 128), X.dtype).at[: n - 1, :d].set(X[1:])
    Yp = jnp.zeros((b, rows), jnp.float32).at[:, : n - 1].set(Y[:, 1:])
    c_inv = 1.0 / cs
    seed = Ball(w=jnp.pad(Y[:, :1] * X[0][None, :], ((0, 0), (0, Xp.shape[1] - d))),
                r=jnp.zeros((b,), jnp.float32), xi2=c_inv,
                m=jnp.ones((b,), jnp.int32))
    bank = streamsvm_fit_many(Xp, Yp, cs, seed, block_n=block_n, b_tile=b_tile, **kw)
    return bank._replace(w=bank.w[:, :d])


def _assert_same_bits(a, b):
    for u, v in zip(a, b):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@pytest.mark.parametrize(
    "b,n,d,block_n,b_tile,kw",
    [
        (21, 300, 54, 64, None, {}),  # covtype's widths: lanes padded, one tile
        (21, 257, 54, 256, None, {}),  # N - 1 a whole block
        (70, 517, 256, 64, 16, dict(bank_resident="hbm")),  # ragged rows and tile
        (70, 517, 256, 64, 16, dict(bank_resident="vmem")),
        (12, 40, 128, 64, None, {}),  # shorter than one block
        (12, 77, 128, 8, 8, {}),  # the smallest block
        (20, 300, 128, 64, 8, dict(variant="lookahead", lookahead=3)),
        # N - 1 a whole number of blocks: the last row comes from the
        # next-row tiles at their clamped index
        (12, 129, 128, 64, None, {}),
        (70, 129, 256, 64, 16, dict(bank_resident="hbm")),
        # the bf16 control's stream: a copy, in bf16, signs too
        (70, 517, 256, 64, 16, dict(bank_resident="hbm", stream_dtype="bf16")),
        (70, 517, 256, 64, 16, dict(stream_dtype="bf16", x_dtype=jnp.bfloat16)),
    ],
)
def test_in_place_fit_equals_the_padded_copy(b, n, d, block_n, b_tile, kw):
    kw = dict(kw)
    X, Y, cs = _data(b, n, d, seed=b + n + d)
    X = X.astype(kw.pop("x_dtype", jnp.float32))
    got = streamsvm_fit_many(X, Y, cs, block_n=block_n, b_tile=b_tile, **kw)
    want = _padded_copy_fit(X, Y, cs, block_n=block_n, b_tile=b_tile, **kw)
    _assert_same_bits(got, want)


def test_only_an_f32_stream_is_read_in_place():
    """One rule decides it for the fit and for the VMEM model: a bf16 stream
    (or a bf16 X) is copied, so the model counts no in-place tiles for it."""
    assert reads_in_place(256, jnp.float32, None)
    assert reads_in_place(4096, jnp.float32, "f32")
    assert not reads_in_place(54, jnp.float32, None)  # lanes to pad
    assert not reads_in_place(256, jnp.float32, "bf16")  # a cast
    assert not reads_in_place(256, jnp.bfloat16, "bf16")
    assert not reads_in_place(256, jnp.bfloat16, None)
    f32 = engine_vmem_bytes(3000, 4096, block_n=64, b_tile=64,
                            bank_resident="hbm")
    bf16 = engine_vmem_bytes(3000, 4096, block_n=64, b_tile=64,
                             bank_resident="hbm", stream_dtype="bf16",
                             x_dtype=jnp.bfloat16)
    assert f32["realign"] > 0 and bf16["realign"] == 0
    assert bf16["stream_tile"] == 2 * 64 * 4096 * 2


def test_rows_past_the_live_count_are_inert_whatever_they_hold():
    """``n_valid`` masks the rows after it: NaN rows and signs there change
    nothing, so a caller's buffer may be longer than its stream."""
    X, Y, cs = _data(24, 200, 256, seed=5)
    want = streamsvm_fit_many(X[:150], Y[:, :150], cs, block_n=64, b_tile=8)
    Xn = X.at[150:].set(jnp.nan)
    Yn = Y.at[:, 150:].set(jnp.nan)
    got = streamsvm_fit_many(Xn, Yn, cs, block_n=64, b_tile=8, n_valid=150)
    _assert_same_bits(got, want)


CHILD = textwrap.dedent('''
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.core import fit_bank, fold_merge
    from repro.core.meb import Ball
    from repro.kernels import ref

    mesh = jax.make_mesh((4,), ("data",))
    rng = np.random.default_rng(11)
    out = {}
    cases = {"hbm": (517, "hbm"), "vmem": (517, "vmem"),
             "one-row-shard": (10, "hbm"), "dead-shard": (9, "hbm")}
    for name, (n, resident) in cases.items():
        b, d = 70, 256
        X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        Y = jnp.asarray(np.where(rng.random((b, n)) < 0.3, 1.0, -1.0).astype(np.float32))
        cs = jnp.asarray(np.exp(rng.uniform(-1, 3, size=b)).astype(np.float32))
        kw = dict(block_n=64, b_tile=16, bank_resident=resident, mesh=mesh)
        got = fit_bank(X, Y, cs, **kw)
        shard_n = -(-n // 4)
        pad = 4 * shard_n - n
        # a padded stream whose last shard is all padding is another stream
        padded = None if n <= 3 * shard_n else fit_bank(
            jnp.pad(X, ((0, pad), (0, 0))), jnp.pad(Y, ((0, 0), (0, pad))),
            cs, **kw)
        banks = []
        for lo in range(0, n, shard_n):
            Xs, Ys = X[lo:lo + shard_n], Y[:, lo:lo + shard_n]
            seed = Ball(Ys[:, :1] * Xs[0][None, :], jnp.zeros((b,)), 1.0 / cs,
                        jnp.ones((b,), jnp.int32))
            banks.append(seed if Xs.shape[0] == 1 else Ball(
                *ref.streamsvm_scan_many_ref(Xs[1:], Ys[:, 1:], *seed[:3],
                                             1.0 / cs, seed.m)))
        oracle = fold_merge(jax.tree.map(lambda *v: jnp.stack(v), *banks))
        same = padded is None or all(
            np.array_equal(np.asarray(u), np.asarray(v))
            for u, v in zip(got, padded))
        gaps = {k: float(np.max(np.abs(np.asarray(u, np.float64) - np.asarray(v))
                                - (tol[0] * np.abs(np.asarray(v)) + tol[1])))
                for k, u, v, tol in zip("w r xi2".split(), got[:3], oracle[:3],
                                        [(1e-5, 1e-6), (1e-5, 1e-6), (1e-4, 1e-6)])}
        out[name] = {"same_bits_as_padded": same, "over_tolerance": gaps,
                     "m_equal": bool(np.array_equal(np.asarray(got.m),
                                                    np.asarray(oracle.m)))}
    print(json.dumps(out))
''')


def test_mesh_fit_reads_each_shard_live_rows():
    """Ragged N over 4 shards (517 rows: shards of 130, the last 127; 10
    rows: the last shard one row; 9 rows: the last shard empty), B 70 in
    tiles of 16, HBM ring and VMEM-resident: the unpadded fit has the bits
    of the fit on a stream the caller padded with inert sign-0 rows (where
    that padding leaves every shard a live row), and matches the per-range
    oracle folded in order within the tolerances of
    test_fit_bank_sharded_matches_manual_ragged_fold."""
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   p for p in (src, os.environ.get("PYTHONPATH")) if p))
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for name, res in out.items():
        assert res["same_bits_as_padded"], (name, res)
        assert all(v <= 0 for v in res["over_tolerance"].values()), (name, res)
        assert res["m_equal"], (name, res)

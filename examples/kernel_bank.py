"""Kernelized one-pass bank: nonlinear data -> RBF core-set bank -> serving.

    PYTHONPATH=src python examples/kernel_bank.py

Two concentric rings are not linearly separable, so the linear one-pass
engine tops out near chance. ``core.fit_kernel_bank`` runs the SAME
Algorithm 1 recursion in kernel space over the SAME single stream pass:
each of the B models keeps a bounded core-set buffer of at most S stream
rows (state O(B * S * D), independent of stream length N — the paper's
constant-storage claim carried to kernel space) and evicts a slot when
full — ``eviction="smallest-coef"`` drops the smallest-|coef| slot,
``eviction="farthest-point"`` drops the slot closest to the center and
keeps the extremes that carry the ball geometry. The C grid AND gamma are
traced, so a whole hyperparameter sweep is one compilation; ``s_tile=``
chunks the core-set Gram launch (bit-exact) when B * S outgrows the VMEM
budget; ``mesh=`` shards the stream over devices and folds the per-shard
banks with the kernelized Sec-4.3 merge (demonstrated below when more
than one device is visible).

The trained bank checkpoints through ``core.save_kernel_bank`` and serves
through the same ``BankServer`` as the linear bank —
``from_checkpoint`` restores the kernel/gamma config from the checkpoint
meta, and served scores are BIT-EXACT with the direct
``core.kernel_bank_decision`` readout (asserted below, not just printed).

Speed on a TPU is measured by the chip benchmark, ``benchmarks/chip/``;
it has no kernel-bank cell yet.
"""
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fit_kernel_bank, kernel_bank_decision, save_kernel_bank
from repro.serve import BankServer


def make_rings(n, d, seed):
    """Inner ring -> +1, outer ring -> -1; extra dims are noise."""
    rng = np.random.default_rng(seed)
    y = np.where(rng.uniform(size=n) < 0.5, 1.0, -1.0).astype(np.float32)
    radius = np.where(y > 0, 1.0, 2.5)
    theta = rng.uniform(0, 2 * np.pi, size=n)
    X = rng.normal(scale=0.1, size=(n, d)).astype(np.float32)
    X[:, 0] += (radius * np.cos(theta)).astype(np.float32)
    X[:, 1] += (radius * np.sin(theta)).astype(np.float32)
    return X, y


def bank_accuracy(bank, Xte, yte, *, kernel, gamma):
    scores = np.asarray(
        kernel_bank_decision(bank, jnp.asarray(Xte), kernel=kernel, gamma=gamma)
    )  # (Q, B)
    return [float(np.mean(np.sign(s) == yte)) for s in scores.T]


def main():
    c_pts, d, s_size, gamma = (0.5, 5.0, 50.0), 8, 64, 2.0
    Xtr, ytr = make_rings(1200, d, seed=0)
    Xte, yte = make_rings(400, d, seed=1)

    Y = jnp.tile(jnp.asarray(ytr)[None, :], (len(c_pts), 1))  # (B, N)
    cs = jnp.asarray(c_pts, jnp.float32)

    # --- one stream pass per kernel; identical API, only the epilogue flips
    banks = {}
    for kernel in ("linear", "rbf"):
        t0 = time.perf_counter()
        banks[kernel] = fit_kernel_bank(
            jnp.asarray(Xtr), Y, cs,
            kernel=kernel, gamma=gamma, coreset_size=s_size, block_n=128,
        )
        t_fit = time.perf_counter() - t0
        accs = bank_accuracy(banks[kernel], Xte, yte, kernel=kernel, gamma=gamma)
        kept = int(np.asarray(banks[kernel].m).max())
        print(
            f"{kernel:>6}: ONE {len(Xtr)}-row pass in {t_fit*1e3:5.0f} ms "
            f"(interpret mode), buffer S={s_size}, {kept} core-set updates; "
            "held-out acc "
            + ", ".join(
                f"C={c:4.1f}: {100*a:5.1f}%" for c, a in zip(c_pts, accs)
            )
        )
    # rings are radially separable only in kernel space: expect the RBF bank
    # far above the ~50% linear ceiling
    best_rbf = max(bank_accuracy(banks["rbf"], Xte, yte, kernel="rbf", gamma=gamma))
    assert best_rbf > 0.9, f"RBF bank should separate the rings, got {best_rbf}"

    # --- eviction + s_tile: same pass, different slot policy / tiling ------
    # farthest-point keeps the slots FARTHEST from the center (the extremes
    # that pin down the enclosing ball) instead of the largest coefficients.
    bank_fp = fit_kernel_bank(
        jnp.asarray(Xtr), Y, cs,
        kernel="rbf", gamma=gamma, coreset_size=s_size, block_n=128,
        eviction="farthest-point",
    )
    best_fp = max(bank_accuracy(bank_fp, Xte, yte, kernel="rbf", gamma=gamma))
    # s_tile chunks the (block_n, B*S) core-set Gram launch into s_tile-slot
    # column strips — smaller VMEM working set, bit-identical bank.
    bank_tiled = fit_kernel_bank(
        jnp.asarray(Xtr), Y, cs,
        kernel="rbf", gamma=gamma, coreset_size=s_size, block_n=128,
        s_tile=16,
    )
    assert all(
        np.array_equal(a, b) for a, b in zip(banks["rbf"], bank_tiled)
    ), "s_tile chunking must be bit-exact"
    print(
        f"eviction sweep: smallest-coef {100*best_rbf:5.1f}% vs "
        f"farthest-point {100*best_fp:5.1f}% held-out acc; s_tile=16 refit "
        "is BIT-EXACT with the unchunked bank (7/7 leaves)"
    )

    # --- mesh-sharded fit: split the stream, merge the banks (Sec 4.3) ----
    # Each device runs the one-pass recursion on its own shard; the
    # per-shard banks fold pairwise with the kernelized ball merge
    # (concatenated core-sets re-compressed to S slots). Run with
    #   XLA_FLAGS=--xla_force_host_platform_device_count=8
    # to see the multi-device path on CPU.
    n_dev = len(jax.devices())
    if n_dev > 1:
        mesh = jax.make_mesh((n_dev,), ("data",))
        t0 = time.perf_counter()
        bank_sh = fit_kernel_bank(
            jnp.asarray(Xtr), Y, cs,
            kernel="rbf", gamma=gamma, coreset_size=s_size, block_n=128,
            mesh=mesh, shard_axis="data",
        )
        t_sh = time.perf_counter() - t0
        best_sh = max(
            bank_accuracy(bank_sh, Xte, yte, kernel="rbf", gamma=gamma)
        )
        assert best_sh > 0.9, f"sharded RBF bank lost the rings: {best_sh}"
        print(
            f"mesh fit over {n_dev} stream shards in {t_sh*1e3:5.0f} ms: "
            f"held-out acc {100*best_sh:5.1f}% (single-pass "
            f"{100*best_rbf:5.1f}%) — merged bank still O(B*S*D)"
        )
    else:
        print(
            "mesh demo skipped (1 device); rerun with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )

    with tempfile.TemporaryDirectory() as td:
        # --- checkpoint -> serve: meta carries bank_kind/kernel/gamma ------
        save_kernel_bank(td, banks["rbf"], kernel="rbf", gamma=gamma)
        server = BankServer.from_checkpoint(td, q_block=128)
        print(
            f"serving core-set bank {server.bank_shape} from checkpoint "
            f"(kernel={server.kernel!r}, gamma={server.gamma} via meta)"
        )
        rng = np.random.default_rng(7)
        reqs, lo = [], 0
        while lo < len(Xte):  # ragged client batches, FIFO-packed into slots
            n = int(rng.integers(1, 100))
            reqs.append(server.submit(Xte[lo : lo + n]))
            lo += n
        t0 = time.perf_counter()
        stats = server.run()
        t_serve = time.perf_counter() - t0

    served = np.concatenate([r.result for r in reqs])  # (Q, B) margins

    # --- served == direct readout, bit for bit ----------------------------
    direct = np.asarray(
        kernel_bank_decision(
            banks["rbf"], jnp.asarray(Xte), kernel="rbf", gamma=gamma
        )
    )
    assert np.array_equal(served, direct), "served kernel scores diverged"
    print(
        f"served {len(Xte)} queries x {len(c_pts)} models in {stats.steps} "
        f"microbatches ({t_serve*1e3:.0f} ms, {len(Xte)/t_serve:.0f} "
        f"queries/s, slot utilization {stats.utilization:.1%}); served f32 "
        "scores BIT-EXACT with core.kernel_bank_decision"
    )

    # --- hot swap: continue the fit on fresh rows, serving keeps running --
    X2, y2 = make_rings(600, d, seed=2)
    X12 = np.concatenate([Xtr, X2])
    Y12 = jnp.tile(jnp.asarray(np.concatenate([ytr, y2]))[None, :],
                   (len(c_pts), 1))
    bank2 = fit_kernel_bank(
        jnp.asarray(X12), Y12, cs,
        kernel="rbf", gamma=gamma, coreset_size=s_size, block_n=128,
    )
    server.submit(Xte[:128])
    server.step()  # scores against the OLD bank
    server.swap_bank(bank2)  # queued requests survive the swap
    server.run()
    print(
        f"hot-swapped to the {len(X12)}-row bank mid-stream "
        f"({server.stats.bank_swaps} swap, {server.stats.finished} requests "
        "finished, none dropped)"
    )


if __name__ == "__main__":
    main()

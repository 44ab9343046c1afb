"""Quickstart: one-pass StreamSVM vs single-pass baselines on Synthetic-A,
a whole C-grid trained in ONE pass via the multi-ball engine, then a
200-class OVR x 3-point C-grid (600 models) in one pass of the TILED engine
— re-trained HBM-resident (``bank_resident="hbm"`` — the double-buffered
ring that lifts the VMEM cap on B*D, bit-exact with VMEM scratch) — and the
trained bank SERVED back through the fused predict engine
(serve.BankServer), bit-exact with the direct readout.

    PYTHONPATH=src python examples/quickstart.py

SHARDED: every bank entry point also takes ``mesh=`` — the stream splits
into contiguous ranges over a device mesh axis, each shard runs the same
tiled engine over its range, and the per-shard banks are folded with the
paper's Sec-4.3 merge (one all_gather). N need not divide the shard count
(ragged remainders are padded with inert sign-0 rows):

    mesh = jax.make_mesh((8,), ("data",))
    bank = fit_bank(X, Y, cs, b_tile=64, stream_dtype="bf16", mesh=mesh)
    # equivalently: fit_ovr(..., mesh=mesh), fit_c_grid(..., mesh=mesh),
    # fit_chunked_many(..., mesh=mesh) — and core.fit_bank_sharded directly.

Run the 8-device version of this flow (simulated host devices):

    PYTHONPATH=src python examples/svm_distributed.py

Training speed on a TPU is measured by the chip benchmark,
``benchmarks/chip/run.py`` (its cells are in ``BENCHMARK.json``).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines import fit_pegasos, fit_perceptron
from repro.core import accuracy, fit, fit_bank, fit_c_grid, fit_lookahead, ovr_signs
from repro.data import load_dataset, preprocess_for


def main():
    Xtr, ytr, Xte, yte = load_dataset("synthetic_a")
    Xtr, Xte = preprocess_for("synthetic_a", Xtr, Xte)
    Xj, yj = jnp.asarray(Xtr), jnp.asarray(ytr)
    Xt, yt = jnp.asarray(Xte), jnp.asarray(yte)

    C = 10.0
    ball = fit(Xj, yj, C)  # Algorithm 1: one pass, O(D) state
    ball2 = fit_lookahead(Xj, yj, C, 10)  # Algorithm 2: lookahead 10

    acc = lambda w: float(np.mean(np.sign(Xte @ np.asarray(w)) == yte)) * 100
    wp, _ = fit_perceptron(Xj, yj)
    wpeg = fit_pegasos(Xj, yj, lam=1.0 / (C * len(ytr)), k=20)

    print(f"StreamSVM Algo-1 : {acc(ball.w):5.1f}%  (core vectors: {int(ball.m)})")
    print(f"StreamSVM Algo-2 : {acc(ball2.w):5.1f}%  (core vectors: {int(ball2.m)})")
    print(f"Perceptron       : {acc(wp):5.1f}%")
    print(f"Pegasos k=20     : {acc(wpeg):5.1f}%")
    print(f"ball radius R={float(ball.r):.3f}  xi2={float(ball.xi2):.4f}  "
          f"state = {ball.w.nbytes + 12} bytes (constant in N)")

    # --- hyper-parameter grid in ONE pass (multi-ball Pallas engine) --------
    # Every C value is a model in the engine's bank: each (block_n, D) tile of
    # the stream is read from HBM once and updates all grid points, so model
    # selection costs one data pass instead of len(grid) passes.
    grid = jnp.asarray([0.1, 1.0, 10.0, 100.0, 1000.0], jnp.float32)
    bank = fit_c_grid(Xj, yj, grid)  # warmup/compile
    t0 = time.perf_counter()
    bank = jax.block_until_ready(fit_c_grid(Xj, yj, grid))
    dt = time.perf_counter() - t0
    accs = [acc(bank.w[i]) for i in range(len(grid))]
    print(f"\nC-grid in one pass ({len(grid)} models, {dt*1e3:.0f} ms):")
    for i, c in enumerate(np.asarray(grid)):
        print(f"  C={c:7.1f}  acc={accs[i]:5.1f}%  "
              f"core vectors={int(bank.m[i])}")
    best = int(np.argmax(accs))
    print(f"selected C* = {float(grid[best]):g} — one stream read for the "
          f"whole grid (state O(B*D) = {bank.w.nbytes} bytes)")

    # --- 200-class OVR x 3-point C-grid: 600 models, ONE pass ---------------
    # Classes x C-grid flatten onto the bank axis of the TILED engine: the
    # 2-D (data-major) grid re-visits each resident stream tile with every
    # b_tile-model bank tile, so the stream is still read once, bf16 tiles
    # halve its HBM bytes, and B is no longer capped by the per-step VMEM
    # working set. Training 600 independent fits here would read the stream
    # 600 times; the bank reads it ONCE. (Scaled-down shapes so the CPU
    # interpret mode stays fast; speed on a TPU is benchmarks/chip/'s job.
    # Note the per-model core-vector budget m stays O(log N) — the paper's
    # sparsity claim — so extreme-imbalance OVR argmax at 200 classes is a
    # stress test of Algorithm 1 itself, not of the engine; the engine is
    # bit-exact with 600 separate single-model fits.)
    n_classes, c_pts = 200, (1.0, 10.0, 100.0)
    rng = np.random.default_rng(0)
    proto = rng.normal(size=(n_classes, 64)).astype(np.float32) * 3
    labels = rng.integers(0, n_classes, size=2000)
    Xm = (rng.normal(size=(2000, 64)) + proto[labels]).astype(np.float32)
    Xm /= np.linalg.norm(Xm, axis=1, keepdims=True)
    signs = ovr_signs(jnp.asarray(labels), n_classes)  # (200, N)
    Y = jnp.tile(signs, (len(c_pts), 1))  # (600, N): class-major per C point
    cs = jnp.repeat(jnp.asarray(c_pts, jnp.float32), n_classes)  # (600,)
    ovr = fit_bank(jnp.asarray(Xm), Y, cs, b_tile=64, stream_dtype="bf16")
    t0 = time.perf_counter()
    ovr = jax.block_until_ready(
        fit_bank(jnp.asarray(Xm), Y, cs, b_tile=64, stream_dtype="bf16")
    )
    dt = time.perf_counter() - t0
    B, N = Y.shape
    print(f"\n200-class OVR x {len(c_pts)}-point C-grid: {B} models, "
          f"ONE {N}-row stream pass in {dt*1e3:.0f} ms "
          f"({B * N / dt / 1e6:.1f}M model-row updates/s, interpret mode)")
    m = np.asarray(ovr.m)
    for ci, cval in enumerate(c_pts):
        mc = m[ci * n_classes : (ci + 1) * n_classes]
        print(f"  C={cval:6.1f}  core vectors/model: "
              f"min={mc.min()} mean={mc.mean():.1f} max={mc.max()}")
    print(f"bank state O(B*D) = {ovr.w.nbytes} bytes vs one stream read "
          f"of {Xm.nbytes} bytes; chip benchmark: benchmarks/chip/")

    # --- the same bank, HBM-resident ----------------------------------------
    # bank_resident="hbm" lifts the VMEM cap on B*D: the bank stays in HBM
    # and (b_tile, D) slices double-buffer through a 2-slot VMEM ring (async
    # prefetch + write-back overlapped with compute) — bit-exact with the
    # VMEM-resident layout, so a 1000-class x C-grid bank at D=4096 (~49 MB,
    # far beyond VMEM scratch) trains with the exact same call. The default
    # "auto" switches over at the VMEM budget (REPRO_VMEM_BUDGET_BYTES).
    ovr_hbm = jax.block_until_ready(
        fit_bank(jnp.asarray(Xm), Y, cs, b_tile=64, stream_dtype="bf16",
                 bank_resident="hbm")
    )
    assert np.array_equal(np.asarray(ovr_hbm.w), np.asarray(ovr.w))
    print('bank_resident="hbm": HBM-resident ring-buffered bank is '
          "bit-exact with VMEM-resident (lifts the VMEM cap on B*D)")

    # --- serve it: the bank through the fused predict engine ----------------
    # The trained bank is tiny and constant-storage, which is exactly the
    # high-QPS deploy shape: serve.BankServer microbatches ragged query
    # batches into fixed (q_block,) row slots and scores each microbatch with
    # ONE fused Pallas kernel launch (per-C-grid-group argmax epilogue).
    # Served f32 results are bit-exact with the direct jnp readout. From a
    # fit_chunked_many checkpoint the same flow is
    # BankServer.from_checkpoint(path, epilogue="ovr").score(queries) — see
    # examples/serve_bank.py.
    from repro.core import predict_c_grid
    from repro.serve import BankServer

    server = BankServer(ovr, epilogue="ovr", n_classes=n_classes,
                        q_block=256, b_tile=200)
    server.score(Xm[:1])  # warmup/compile (the kernel shape is (q_block, D))
    steps0 = server.stats.steps
    t0 = time.perf_counter()
    cls, _ = server.score(Xm)
    dt = time.perf_counter() - t0
    direct_cls, _ = predict_c_grid(ovr, jnp.asarray(Xm), n_classes)
    served = np.mean(cls == np.asarray(labels)[:, None], axis=0)
    direct = np.mean(np.asarray(direct_cls) == np.asarray(labels)[:, None], axis=0)
    print(f"\nserved the bank back over the {len(Xm)} training rows in "
          f"{server.stats.steps - steps0} microbatches ({dt*1e3:.0f} ms, "
          f"{len(Xm)/dt:.0f} queries/s, interpret mode):")
    for ci, cval in enumerate(c_pts):
        print(f"  C={cval:6.1f}  served acc={100*served[ci]:5.1f}%  "
              f"direct acc={100*direct[ci]:5.1f}%")
    exact = np.array_equal(cls, np.asarray(direct_cls))
    print(f"served == direct predict_c_grid readout, bit for bit: {exact}")


if __name__ == "__main__":
    main()

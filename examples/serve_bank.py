"""Train -> checkpoint -> serve: the quickstart's 600-model bank end to end.

    PYTHONPATH=src python examples/serve_bank.py

One pass of the tiled engine fits a 200-class OVR x 3-point C-grid (600
models) through the chunked streaming driver, the checkpoint callback
persists the bank (state O(B * D) — the paper's constant-storage claim),
and ``BankServer.from_checkpoint`` serves it: ragged query batches are
microbatched into fixed (q_block,) row slots and scored by the fused Pallas
predict kernel (per-C-grid-group argmax epilogue). Served f32 results are
BIT-EXACT with the direct jnp readout (core.predict_c_grid) — asserted
below, not just printed.

Serving latency on a TPU is measured by the chip benchmark,
``benchmarks/chip/run.py`` (its cells are in ``BENCHMARK.json``).
"""
import tempfile
import time

import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import fit_chunked_many, ovr_signs, predict_c_grid
from repro.serve import BankServer


def make_blobs(n, n_classes, d, seed, proto_seed=0):
    proto = (
        np.random.default_rng(proto_seed).normal(size=(n_classes, d)) * 3
    ).astype(np.float32)
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, size=n)
    X = (rng.normal(size=(n, d)) + proto[labels]).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    return X, labels


def main():
    n_classes, c_pts, d = 200, (1.0, 10.0, 100.0), 64
    Xtr, ytr = make_blobs(2000, n_classes, d, seed=0)
    Xte, yte = make_blobs(600, n_classes, d, seed=1)

    # --- train: one stream pass over chunks, bank checkpointed ------------
    signs = ovr_signs(jnp.asarray(ytr), n_classes)  # (200, N)
    Y = jnp.tile(signs, (len(c_pts), 1))  # (600, N): class-major per C point
    cs = jnp.repeat(jnp.asarray(c_pts, jnp.float32), n_classes)  # (600,)
    chunks = [
        (Xtr[lo : lo + 500], Y[:, lo : lo + 500])
        for lo in range(0, len(Xtr), 500)
    ]
    t0 = time.perf_counter()
    result = fit_chunked_many(chunks, cs, b_tile=64, stream_dtype="bf16")
    t_fit = time.perf_counter() - t0
    bank = result.ball
    print(
        f"fit: {bank.w.shape[0]} models, ONE {result.position}-row stream "
        f"pass in {t_fit*1e3:.0f} ms (interpret mode); bank state "
        f"O(B*D) = {bank.w.nbytes} bytes"
    )

    with tempfile.TemporaryDirectory() as td:
        ckpt.save(
            td, bank,
            meta={"position": result.position, "n_classes": n_classes},
        )

        # --- serve: checkpoint -> BankServer, ragged batches -> slots -----
        server = BankServer.from_checkpoint(
            td, epilogue="ovr", q_block=256, b_tile=200
        )
        print(
            f"serving bank {server.bank_shape} from checkpoint "
            f"(n_classes={server.n_classes} via checkpoint meta)"
        )
        rng = np.random.default_rng(7)
        reqs, lo = [], 0
        while lo < len(Xte):  # ragged client batches, FIFO-packed into slots
            n = int(rng.integers(1, 200))
            reqs.append(server.submit(Xte[lo : lo + n]))
            lo += n
        t0 = time.perf_counter()
        stats = server.run()
        t_serve = time.perf_counter() - t0

    cls = np.concatenate([r.result[0] for r in reqs])
    margin = np.concatenate([r.result[1] for r in reqs])

    # --- served == direct readout, bit for bit ----------------------------
    rcls, rmargin = predict_c_grid(bank, jnp.asarray(Xte), n_classes)
    assert np.array_equal(cls, np.asarray(rcls)), "served class ids diverged"
    assert np.array_equal(margin, np.asarray(rmargin)), "served margins diverged"
    print(
        f"served {len(Xte)} queries x {bank.w.shape[0]} models in "
        f"{stats.steps} microbatches ({t_serve*1e3:.0f} ms, "
        f"{len(Xte)/t_serve:.0f} queries/s, slot utilization "
        f"{stats.utilization:.1%}); served f32 scores BIT-EXACT with "
        "core.predict_c_grid"
    )
    for g, cval in enumerate(c_pts):
        acc = float(np.mean(cls[:, g] == yte))
        print(f"  C={cval:6.1f}  served held-out acc={100*acc:5.1f}%")
    # (absolute accuracy at 200-way extreme-imbalance OVR is Algorithm 1's
    # known stress case — see the quickstart note; chance is 0.5% — the
    # serving claim is the exact parity asserted above)

    # --- hot swap: re-fit continues, serving never drops a request --------
    more_chunks = [(Xte[:500], jnp.tile(ovr_signs(jnp.asarray(yte[:500]),
                                                  n_classes), (len(c_pts), 1)))]
    result2 = fit_chunked_many(more_chunks, cs, resume=result, b_tile=64,
                               stream_dtype="bf16")
    for lo in range(0, 256, 64):
        server.submit(Xte[lo : lo + 64])
    server.step()  # first 256 rows score against the OLD bank
    server.swap_bank(result2.ball)  # queued requests survive the swap
    server.run()
    print(
        f"hot-swapped to the {result2.position}-row bank mid-stream "
        f"({server.stats.bank_swaps} swap, {server.stats.finished} requests "
        "finished, none dropped)"
    )


if __name__ == "__main__":
    main()

"""Serving throughput harness: sweeps the predict engine, emits BENCH JSON.

Sweeps (Q, D, B, q_block, b_tile, stream_dtype, epilogue, bank_resident)
over the fused bank-inference kernel (kernels.ops.predict_bank) and over the
end-to-end BankServer microbatching path, measures seconds/batch, queries/s
and model-scores/s (Q * B margins evaluated per batch), derives achieved
GB/s from the engine's modeled HBM byte traffic, and compares against the
same bandwidth roofline as the training harness (the device's published HBM
peak from ``peaks.py``, keyed by ``device_kind`` — a device without one is
an error unless ``--hbm-peak-gbps`` or ``REPRO_HBM_PEAK_GBPS`` names a
peak; on the CPU interpret backend no number it prints is a device metric). ``bank_resident="hbm"`` rows serve the bank out of
ANY/HBM space through the kernel's 2-slot async-copy ring instead of the
BlockSpec pipeline — same modeled bytes (the bank is re-read once per
resident query tile either way), so the wall-time ratio against the
equal-shape vmem baseline (``dma_overlap_efficiency`` =
seconds(vmem)/seconds(hbm), which at equal modeled bytes IS the
achieved-GB/s ratio) isolates how well the manual prefetch hides the bank
fetch — 1.0 means it matches the BlockSpec pipeline. Rows record the per-config VMEM
working-set estimate (``vmem_working_set_bytes``).

The modeled bytes encode the serving engine's movement claim, the mirror
image of training's: the QUERY stream is the big term and is read ONCE per
batch (data-major grid — ``query_passes`` stays 1.0 no matter how many bank
tiles revisit each resident tile, and bf16 query tiles halve the term),
while the tiny (B, D) bank is re-read once per resident query tile — the
cheap term, because one-pass training left the model constant-storage.

``path="live"`` rows benchmark the continuous train->serve loop
(repro.live.LiveBank) instead of a predict kernel: steady-state ingest rate
(rows/s through train+fold+swap+checkpoint), hot-swap latency (seconds for
``BankServer.swap_bank`` to publish an already-folded bank — the serving
blackout window), and ``recovery_seconds`` — wall time from relaunching a
killed trainer (crash injected mid-stream, after the last checkpoint) to
the first FRESH bank swapped into the surviving server. Each live row
records its ``bank_kind``: ``"linear"`` Ball loops and ``"kernel"``
core-set loops (train through fit_kernel_bank, Sec-4.3 kernel merges on
retire/fold, RBF serving) share the measurement surface, so their ingest /
blackout / recovery numbers are directly comparable.

Writes ``BENCH_serving.json`` at the repo root (validated by CI's
bench-smoke next to BENCH_engine.json) and prints one ``BENCH`` line per
config. ``--smoke`` runs a seconds-scale sweep in interpret mode for CI and
always includes an ``ovr``-epilogue row, a linear ``live`` row, and a
kernelized ``live`` row (CI asserts all three).

    PYTHONPATH=src python benchmarks/serving_throughput.py [--smoke]
        [--out BENCH_serving.json] [--reps 3]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

try:  # run as a script: benchmarks/ is on sys.path
    from peaks import peaks_for
except ImportError:  # imported as benchmarks.<harness> (run.py)
    from benchmarks.peaks import peaks_for
from repro.kernels import predict_bank, predict_kernel_bank
from repro.kernels.ops import (
    bank_tiling,
    gram_tiling,
    ovr_group_tiling,
    predict_vmem_bytes,
)
from repro.runtime.compile_cache import use_compile_cache
from repro.serve import BankServer

SCHEMA = "streamsvm-bench-serving/v5"
_DTYPE_BYTES = {"f32": 4, "bf16": 2}


def hbm_peak_gbps(override=None) -> float:
    """Roofline peak: --hbm-peak-gbps flag > REPRO_HBM_PEAK_GBPS env > the
    published peak of the device JAX runs on (``peaks.py``, keyed by
    ``device_kind``). A device without a published peak is an error."""
    if override is not None:
        return float(override)
    env = os.environ.get("REPRO_HBM_PEAK_GBPS")
    if env:
        return float(env)
    return peaks_for(jax.devices()[0].device_kind)["hbm_gbps"]


# Keys every result row must carry — CI validates the emitted JSON against
# this (see .github/workflows/ci.yml bench-smoke).
RESULT_KEYS = (
    "name", "Q", "D", "B", "q_block", "b_tile", "n_bank_tiles", "epilogue",
    "n_classes", "k", "stream_dtype", "path", "bank_resident", "kernel",
    "coreset_size", "vmem_working_set_bytes", "seconds_per_batch",
    "queries_per_s", "model_scores_per_s", "bytes", "query_passes",
    "naive_query_bytes", "achieved_gbps", "hbm_peak_gbps",
    "roofline_seconds", "roofline_frac", "dma_overlap_efficiency",
)

# Keys for path="live" rows — the train->serve loop has its own surface
# (ingest rate + swap latency + crash-recovery time, not kernel bytes).
# bank_kind distinguishes linear Ball loops from kernelized core-set loops
# (schema v4). Schema v5 adds the ELASTIC fields: ``n_stream_shards`` (the
# logical shard count each chunk trains across), ``rows_per_s_per_shard``
# (per-shard ingest rate — the weak-scaling denominator), and
# ``remesh_recovery_seconds`` — wall time from relaunching a killed sharded
# trainer on a SMALLER mesh (devices lost for good) to the first fresh bank
# swap; null for unsharded rows. CI's chaos-smoke asserts a sharded live
# row carries all three.
LIVE_RESULT_KEYS = (
    "name", "path", "bank_kind", "B", "D", "chunk_rows", "n_chunks",
    "n_sub_banks", "rotate_every", "swap_every", "n_stream_shards",
    "seconds_per_chunk", "rows_per_s", "rows_per_s_per_shard", "swaps",
    "checkpoints", "swap_latency_s", "recovery_seconds",
    "remesh_recovery_seconds",
)


def out_bytes(Q, B, epilogue, n_classes, k):
    """HBM bytes of the epilogue output per batch (f32 + int32 pairs)."""
    if epilogue == "scores":
        return Q * B * 4
    if epilogue == "ovr":
        return Q * (B // n_classes) * 8  # class ids + margins
    return Q * k * 8  # topk values + ids


def modeled_bytes(Q, D, B, q_block, epilogue, n_classes, k, stream_dtype,
                  kernel=None, coreset_size=None):
    """HBM bytes per batch under the predict engine's movement model.

    queries: each (q_block, D) tile DMA'd once (data-major grid) — Q*D at
    the stream dtype, NOT multiplied by the B/b_tile bank tiles revisiting
    it. bank: (B, D) f32 re-read once per resident query tile — the paper's
    constant-storage model makes this the small term. out: the epilogue's
    emitted rows.
    """
    sz = _DTYPE_BYTES[stream_dtype]
    n_q_blocks = -(-Q // q_block)
    if kernel is not None:
        # Kernelized bank: the (B*S, D) core-set operand replaces the (B, D)
        # weight rows in the Gram launch (re-fetched once per resident query
        # tile, like the linear bank), the (Q, B*S) kernel block round-trips
        # once between the Gram launch and the coefficient contraction, and
        # the (B, S) coefficients are read once per query tile.
        return {
            "queries": Q * D * sz,
            "bank": n_q_blocks * B * coreset_size * D * 4,
            "kernel_block": 2 * Q * B * coreset_size * 4,
            "coef": n_q_blocks * B * coreset_size * 4,
            "out": out_bytes(Q, B, epilogue, n_classes, k),
        }
    return {
        "queries": Q * D * sz,
        "bank": n_q_blocks * B * D * 4,
        "out": out_bytes(Q, B, epilogue, n_classes, k),
    }


def bench_one(cfg, reps, interpret, peak_gbps):
    Q, D, B = cfg["Q"], cfg["D"], cfg["B"]
    epilogue = cfg.get("epilogue", "scores")
    n_classes = cfg.get("n_classes")
    k = cfg.get("k")
    path = cfg.get("path", "ops")
    bank_resident = cfg.get("bank_resident", "vmem")
    kernel = cfg.get("kernel")
    coreset_size = cfg.get("coreset_size")
    sdt = cfg["stream_dtype"] if cfg["stream_dtype"] != "f32" else None
    rng = np.random.default_rng(0)
    X = rng.normal(size=(Q, D)).astype(np.float32)
    W = rng.normal(size=(B, D)).astype(np.float32)
    if kernel is not None:
        # Kernelized bank: a synthetic core-set buffer of the benchmarked
        # shape (serving cost depends only on (B, S, D), not the fit).
        from repro.core import KernelBank

        S = coreset_size
        points = jnp.asarray(rng.normal(size=(B, S, D)).astype(np.float32))
        coef = jnp.asarray(
            rng.normal(size=(B, S)).astype(np.float32) / np.sqrt(S)
        )
        kkw = dict(
            kernel=kernel, gamma=0.5, epilogue=epilogue, n_classes=n_classes,
            k=k, q_block=cfg["q_block"], stream_dtype=sdt,
            interpret=interpret,
        )
        if path == "server":
            kb = KernelBank(
                idx=jnp.zeros((B, S), jnp.int32), coef=coef, points=points,
                q=jnp.ones((B,)), r=jnp.ones((B,)), xi2=jnp.ones((B,)),
                m=jnp.full((B,), S, jnp.int32),
            )
            sizes = _ragged_sizes(Q)
            skw = dict(kkw)
            skw.pop("kernel"), skw.pop("gamma")

            def run():
                server = BankServer(kb, kernel=kernel, gamma=0.5, **skw)
                reqs = [server.submit(X[lo:hi]) for lo, hi in sizes]
                server.run()
                return reqs[-1].result
        else:
            run = lambda: jax.block_until_ready(
                predict_kernel_bank(jnp.asarray(X), points, coef, **kkw)
            )
    else:
        kw = dict(
            epilogue=epilogue,
            n_classes=n_classes,
            k=k,
            q_block=cfg["q_block"],
            b_tile=cfg["b_tile"],
            stream_dtype=sdt,
            bank_resident=bank_resident,
            interpret=interpret,
        )
        if path == "server":
            # end-to-end: FIFO packing of ragged requests + the kernel — a
            # new server per rep so admission/packing overhead is inside the
            # clock
            sizes = _ragged_sizes(Q)

            def run():
                server = BankServer(W, **kw)
                reqs = [server.submit(X[lo:hi]) for lo, hi in sizes]
                server.run()
                return reqs[-1].result
        else:
            run = lambda: jax.block_until_ready(
                predict_bank(jnp.asarray(X), jnp.asarray(W), **kw)
            )
    run()  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        run()
    sec = (time.perf_counter() - t0) / reps

    if kernel is not None:
        b_tile_eff, n_btiles = None, 1
        bank_resident = "vmem"
        # Working-set estimate: the Gram launch's operand tiles + f32
        # accumulator, plus the coefficient contraction's inputs.
        bm_, bn_ = gram_tiling(Q, B * coreset_size, cfg["q_block"], 256)
        bk = 512
        working_set = (
            (bm_ * bk + bn_ * bk + bm_ * bn_) * 4
            + B * coreset_size * 4
        )
    elif epilogue == "ovr":
        nc_pad, g_tile, gp = ovr_group_tiling(B, n_classes, cfg["b_tile"])
        b_tile_eff, n_btiles = g_tile * nc_pad, gp // g_tile
    else:
        b_tile_eff, n_btiles = bank_tiling(B, cfg["b_tile"])
    by = modeled_bytes(
        Q, D, B, cfg["q_block"], epilogue, n_classes, k, cfg["stream_dtype"],
        kernel=kernel, coreset_size=coreset_size,
    )
    total = sum(by.values())
    roofline_sec = total / (peak_gbps * 1e9)
    if kernel is None:
        working_set = sum(
            predict_vmem_bytes(
                B, D, q_block=cfg["q_block"], b_tile=cfg["b_tile"],
                stream_dtype=(
                    cfg["stream_dtype"] if cfg["stream_dtype"] != "f32"
                    else None
                ),
                epilogue=epilogue, n_classes=n_classes, k=k,
                bank_resident=bank_resident,
            ).values()
        )
    return {
        "name": cfg["name"],
        "Q": Q,
        "D": D,
        "B": B,
        "q_block": cfg["q_block"],
        "b_tile": b_tile_eff,
        "n_bank_tiles": n_btiles,
        "epilogue": epilogue,
        "n_classes": n_classes,
        "k": k,
        "stream_dtype": cfg["stream_dtype"],
        "path": path,
        "bank_resident": bank_resident,
        "kernel": kernel,
        "coreset_size": coreset_size,
        "vmem_working_set_bytes": working_set,
        "seconds_per_batch": sec,
        "queries_per_s": Q / sec,
        "model_scores_per_s": Q * B / sec,  # margins evaluated / s
        "bytes": {**by, "total": total},
        "query_passes": 1.0,  # data-major grid: NOT B/b_tile
        "naive_query_bytes": n_btiles * by["queries"],  # bank-major cost
        "achieved_gbps": total / sec / 1e9,
        "hbm_peak_gbps": peak_gbps,
        "roofline_seconds": roofline_sec,
        "roofline_frac": roofline_sec / sec,
        # filled in post-sweep for hbm rows with a named vmem baseline
        "dma_overlap_efficiency": None,
    }


class _TimingServer:
    """Hot-swap target that timestamps every published bank (both kinds)."""

    def __init__(self):
        self.times = []

    def swap_bank(self, bank):
        jax.block_until_ready(
            bank.points if hasattr(bank, "points") else bank.w
        )
        self.times.append(time.perf_counter())


def bench_live(cfg, reps, interpret):
    """The train->serve loop end to end: steady-state ingest, hot-swap
    latency, and recovery-to-fresh-bank after an injected mid-stream kill.
    ``bank_kind="kernel"`` runs the same loop through fit_kernel_bank +
    the Sec-4.3 kernel merge, so the linear/kernel rows are comparable."""
    import tempfile

    from repro.live import ArraySource, LiveBank
    from repro.runtime import InjectedFailure

    B, D = cfg["B"], cfg["D"]
    bank_kind = cfg.get("bank_kind", "linear")
    n_shards = int(cfg.get("n_stream_shards", 1))
    mesh = None
    if n_shards > 1:
        if len(jax.devices()) < n_shards:
            print(
                f'SKIP {cfg["name"]}: needs {n_shards} devices for the '
                f"sharded live row, have {len(jax.devices())} (run under "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n_shards} with --filter sharded --append)"
            )
            return None
        mesh = jax.make_mesh((n_shards,), ("data",))
    chunk, n_chunks = cfg["chunk_rows"], cfg["n_chunks"]
    n_rows = chunk * n_chunks
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n_rows, D)).astype(np.float32)
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    y = np.sign(rng.normal(size=n_rows) + X[:, 0]).astype(np.float32)
    Y = np.tile(y, (B, 1))
    cs = jnp.asarray(np.linspace(1.0, 8.0, B, dtype=np.float32))
    kernel_kw = (
        dict(
            kernel=cfg.get("kernel", "rbf"), gamma=cfg.get("gamma", 0.5),
            coreset_size=cfg.get("coreset_size", 32),
        )
        if bank_kind == "kernel"
        else {}
    )

    def make(td, srv, failpoints=None, run_mesh=None):
        return LiveBank(
            ArraySource(X, Y, chunk), cs, ckpt_dir=os.path.join(td, "ck"),
            bank_kind=bank_kind, n_sub_banks=cfg["n_sub_banks"],
            rotate_every=cfg["rotate_every"], swap_every=cfg["swap_every"],
            mesh=run_mesh if run_mesh is not None else mesh,
            n_stream_shards=n_shards,
            server=srv, failpoints=failpoints, sleep=lambda s: None,
            interpret=interpret, **kernel_kw,
        )

    with tempfile.TemporaryDirectory() as td:
        make(td, _TimingServer()).run()  # compile warm-up
    with tempfile.TemporaryDirectory() as td:
        live = make(td, _TimingServer())
        t0 = time.perf_counter()
        stats = live.run()
        total = time.perf_counter() - t0
        bank = live.serving_bank()

    # Hot-swap latency: publishing an already-folded bank into a warm
    # server (same shape — never recompiles). This is the serving blackout.
    if bank_kind == "kernel":
        server = BankServer(
            bank, kernel=kernel_kw["kernel"], gamma=kernel_kw["gamma"],
            interpret=interpret,
        )
    else:
        server = BankServer(bank, interpret=interpret)
    server.swap_bank(bank)  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        server.swap_bank(bank)
    swap_latency = (time.perf_counter() - t0) / reps

    # Recovery: kill the trainer after it trains a chunk PAST its last
    # checkpoint, relaunch, and clock the window until the surviving server
    # receives its first fresh bank (replay + fold + swap).
    crash_at = (n_chunks // 2) + 1
    with tempfile.TemporaryDirectory() as td:
        srv = _TimingServer()
        live = make(td, srv, failpoints=[("post_train", crash_at)])
        try:
            live.run()
        except InjectedFailure:
            pass
        swaps_before = len(srv.times)
        t0 = time.perf_counter()
        live.run()
        recovery = srv.times[swaps_before] - t0

    # Remesh recovery (sharded rows only): the kill takes its devices with
    # it — the relaunch restores the checkpoint onto a HALF-SIZE mesh
    # (same logical shards, re-placed slots, degraded per-range training)
    # and the clock runs until the surviving server gets a fresh bank.
    remesh_recovery = None
    if n_shards > 1:
        small = jax.make_mesh((max(1, n_shards // 2),), ("data",))
        fps = {("post_train", crash_at)}  # shared: the kill fires ONCE
        with tempfile.TemporaryDirectory() as td:
            srv = _TimingServer()
            live = make(td, srv, failpoints=fps)
            try:
                live.run()
            except InjectedFailure:
                pass
            swaps_before = len(srv.times)
            t0 = time.perf_counter()
            relaunched = make(td, srv, failpoints=fps, run_mesh=small)
            relaunched.run()
            remesh_recovery = srv.times[swaps_before] - t0
            assert relaunched.stats.remeshes >= 1

    return {
        "name": cfg["name"],
        "path": "live",
        "bank_kind": bank_kind,
        "B": B,
        "D": D,
        "chunk_rows": chunk,
        "n_chunks": n_chunks,
        "n_sub_banks": cfg["n_sub_banks"],
        "rotate_every": cfg["rotate_every"],
        "swap_every": cfg["swap_every"],
        "n_stream_shards": n_shards,
        "seconds_per_chunk": total / n_chunks,
        "rows_per_s": n_rows / total,
        "rows_per_s_per_shard": n_rows / total / n_shards,
        "swaps": stats.swaps,
        "checkpoints": stats.checkpoints,
        "swap_latency_s": swap_latency,
        "recovery_seconds": recovery,
        "remesh_recovery_seconds": remesh_recovery,
    }


def _ragged_sizes(Q):
    """Deterministic ragged request spans covering Q rows (server path)."""
    spans, lo, step = [], 0, 0
    while lo < Q:
        n = [7, 33, 128, 15, 64][step % 5]
        spans.append((lo, min(lo + n, Q)))
        lo += n
        step += 1
    return spans


def sweep(smoke: bool):
    if smoke:
        base = dict(Q=512, D=64, q_block=128)
        return [
            dict(name="smoke_scores_single_tile", **base, B=48, b_tile=None,
                 stream_dtype="f32"),
            dict(name="smoke_scores_tiled", **base, B=48, b_tile=8,
                 stream_dtype="f32"),
            dict(name="smoke_bf16", **base, B=48, b_tile=8,
                 stream_dtype="bf16"),
            # the acceptance row: fused per-C-grid-group argmax epilogue
            dict(name="smoke_ovr", **base, B=48, b_tile=16, stream_dtype="f32",
                 epilogue="ovr", n_classes=16),
            dict(name="smoke_topk", **base, B=48, b_tile=8, stream_dtype="f32",
                 epilogue="topk", k=4),
            # HBM-resident bank served through the async-copy ring (CI
            # asserts this row + its fields)
            dict(name="smoke_hbm", **base, B=48, b_tile=8,
                 stream_dtype="f32", bank_resident="hbm",
                 overlap_baseline="smoke_scores_tiled"),
            # end-to-end microbatching server (ragged FIFO packing included)
            dict(name="smoke_server_ovr", **base, B=48, b_tile=16,
                 stream_dtype="f32", epilogue="ovr", n_classes=16,
                 path="server"),
            # kernelized bank served through the fused Gram epilogue (CI
            # asserts this row + its fields)
            dict(name="smoke_kernel_rbf", **base, B=48, b_tile=None,
                 stream_dtype="f32", kernel="rbf", coreset_size=16),
            dict(name="smoke_server_kernel_rbf", **base, B=48, b_tile=None,
                 stream_dtype="f32", kernel="rbf", coreset_size=16,
                 path="server"),
            # continuous train->serve loop with an injected kill (CI asserts
            # this row + its swap-latency/recovery fields)
            dict(name="smoke_live", path="live", B=16, D=32, chunk_rows=128,
                 n_chunks=8, n_sub_banks=2, rotate_every=3, swap_every=2),
            # the kernelized live loop: same measurement surface, core-set
            # train/merge/fold + RBF serving (CI asserts this row too)
            dict(name="smoke_live_kernel", path="live", bank_kind="kernel",
                 B=8, D=16, chunk_rows=64, n_chunks=6, n_sub_banks=2,
                 rotate_every=3, swap_every=2, coreset_size=16),
            # the ELASTIC live loop: 8 logical shards on an 8-device mesh,
            # measured only in the forced-device second pass
            # (--filter sharded --append); CI's chaos-smoke asserts this
            # row's per-shard rate and remesh-recovery fields
            dict(name="smoke_live_sharded", path="live", B=16, D=32,
                 chunk_rows=128, n_chunks=8, n_sub_banks=2, rotate_every=3,
                 swap_every=2, n_stream_shards=8),
        ]
    base = dict(D=128, q_block=256)
    return [
        # query-stream scaling at the quickstart bank shape (600 models)
        dict(name="serve_q4096_b600", Q=4096, **base, B=600, b_tile=64,
             stream_dtype="f32"),
        dict(name="serve_q16384_b600", Q=16384, **base, B=600, b_tile=64,
             stream_dtype="f32"),
        # dtype policy: same shape, half the query bytes
        dict(name="serve_q16384_b600_bf16", Q=16384, **base, B=600, b_tile=64,
             stream_dtype="bf16"),
        # bank scaling: one query pass for 1x..8x the bank
        dict(name="serve_q4096_b64", Q=4096, **base, B=64, b_tile=64,
             stream_dtype="f32"),
        dict(name="serve_q4096_b512", Q=4096, **base, B=512, b_tile=64,
             stream_dtype="f32"),
        # fused epilogues at the quickstart layout (200 classes x 3 C points)
        dict(name="serve_ovr_200c_x3", Q=4096, **base, B=600, b_tile=200,
             stream_dtype="f32", epilogue="ovr", n_classes=200),
        dict(name="serve_topk8_b600", Q=4096, **base, B=600, b_tile=64,
             stream_dtype="f32", epilogue="topk", k=8),
        # HBM-resident bank: equal-shape pair isolates the manual ring's
        # prefetch overlap vs the BlockSpec pipeline
        dict(name="serve_q4096_b512_hbm", Q=4096, **base, B=512, b_tile=64,
             stream_dtype="f32", bank_resident="hbm",
             overlap_baseline="serve_q4096_b512"),
        # a bank beyond the default 16 MiB VMEM budget, served from HBM
        dict(name="serve_b1536_d4096_hbm_beyond_vmem", Q=512, D=4096,
             q_block=256, B=1536, b_tile=64, stream_dtype="f32",
             bank_resident="hbm"),
        # end-to-end server (packing overhead included)
        dict(name="serve_server_ovr_200c_x3", Q=4096, **base, B=600,
             b_tile=200, stream_dtype="f32", epilogue="ovr", n_classes=200,
             path="server"),
        # kernelized core-set bank through the fused Gram epilogues
        dict(name="serve_kernel_rbf_b64_s64", Q=4096, **base, B=64,
             b_tile=None, stream_dtype="f32", kernel="rbf", coreset_size=64),
        dict(name="serve_kernel_linear_b64_s64", Q=4096, **base, B=64,
             b_tile=None, stream_dtype="f32", kernel="linear",
             coreset_size=64),
        # coreset-size sweep: S is the serve-side state/latency knob the
        # training evictions trade accuracy against — (Q, B*S) kernel block
        # and (B, S, D) gather scale linearly in S
        dict(name="serve_kernel_rbf_b64_s16", Q=4096, **base, B=64,
             b_tile=None, stream_dtype="f32", kernel="rbf", coreset_size=16),
        dict(name="serve_kernel_rbf_b64_s128", Q=4096, **base, B=64,
             b_tile=None, stream_dtype="f32", kernel="rbf",
             coreset_size=128),
        dict(name="serve_server_kernel_rbf_b64_s64", Q=4096, **base, B=64,
             b_tile=None, stream_dtype="f32", kernel="rbf", coreset_size=64,
             path="server"),
        # the live loop at a production-ish shape: ingest rate, hot-swap
        # blackout, and recovery time after a mid-stream kill
        dict(name="live_b64_d128", path="live", B=64, D=128, chunk_rows=2048,
             n_chunks=16, n_sub_banks=4, rotate_every=4, swap_every=2),
        # its kernelized twin: core-set S=64 train/merge/fold + RBF serving,
        # same cadences — the rows pair up for linear-vs-kernel comparison
        dict(name="live_kernel_b16_d64_s64", path="live", bank_kind="kernel",
             B=16, D=64, chunk_rows=512, n_chunks=12, n_sub_banks=4,
             rotate_every=4, swap_every=2, coreset_size=64),
        # the elastic sharded live loop: 8 logical shards on an 8-device
        # mesh, plus the remesh-recovery clock (kill, relaunch on 4
        # devices) — skipped loudly without devices, measured in the
        # --filter sharded --append pass
        dict(name="live_sharded_b64_d128", path="live", B=64, D=128,
             chunk_rows=2048, n_chunks=16, n_sub_banks=4, rotate_every=4,
             swap_every=2, n_stream_shards=8),
    ]


def run(smoke: bool, reps: int, interpret, name_filter: str | None = None,
        peak_gbps: float | None = None):
    peak = hbm_peak_gbps(peak_gbps)
    results = []
    baselines = {}
    for cfg in sweep(smoke):
        if name_filter is not None and name_filter not in cfg["name"]:
            continue
        if cfg.get("path") == "live":
            row = bench_live(cfg, reps, interpret)
            if row is not None:  # sharded rows skip loudly sans devices
                results.append(row)
            continue
        row = bench_one(cfg, reps, interpret, peak)
        base = baselines.get(cfg.get("overlap_baseline"))
        if base is not None:
            # DMA-overlap efficiency: wall time vs the equal-shape vmem
            # baseline (equal modeled bytes, so this is also the
            # achieved-GB/s ratio); 1.0 = the ring matches the BlockSpec
            # pipeline
            row["dma_overlap_efficiency"] = (
                base["seconds_per_batch"] / row["seconds_per_batch"]
            )
        elif cfg.get("overlap_baseline") is not None:
            print(
                f'NOTE {cfg["name"]}: overlap baseline '
                f'{cfg["overlap_baseline"]!r} not measured in this run — '
                "dma_overlap_efficiency stays null"
            )
        baselines[cfg["name"]] = row
        results.append(row)
    return {
        "schema": SCHEMA,
        "generated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": jax.default_backend(),
        "interpret": (
            jax.default_backend() != "tpu" if interpret is None else interpret
        ),
        "jax_version": jax.__version__,
        "hbm_peak_gbps": peak,
        "smoke": smoke,
        "reps": reps,
        "results": results,
    }


def validate(report: dict):
    """Schema check (used by the CI bench-smoke job).

    Validates the report's SHAPE and that the measurements are sane numbers.
    The one-pass query-movement property (query_passes == 1.0) is a design
    invariant of the data-major grid, enforced by the kernel parity suite
    (tests/test_predict_engine.py bit-exactness across b_tile); the field is
    reported so downstream readers model bytes correctly.
    """
    for key in ("schema", "generated", "backend", "hbm_peak_gbps", "results"):
        if key not in report:
            raise ValueError(f"BENCH report missing key {key!r}")
    if report["schema"] != SCHEMA:
        raise ValueError(f"unexpected schema {report['schema']!r}")
    if not report["results"]:
        raise ValueError("BENCH report has no results")
    for row in report["results"]:
        if row.get("path") == "live":
            missing = [k for k in LIVE_RESULT_KEYS if k not in row]
            if missing:
                raise ValueError(
                    f"live result {row.get('name')!r} missing {missing}"
                )
            for key in ("seconds_per_chunk", "rows_per_s", "swap_latency_s",
                        "recovery_seconds"):
                if not row[key] > 0:
                    raise ValueError(
                        f"{row['name']}: non-positive {key} ({row[key]!r})"
                    )
            if not (row["swaps"] >= 1 and row["checkpoints"] >= 1):
                raise ValueError(
                    f"{row['name']}: a live run must swap and checkpoint at "
                    f"least once (swaps={row['swaps']}, "
                    f"checkpoints={row['checkpoints']})"
                )
            if row["bank_kind"] not in ("linear", "kernel"):
                raise ValueError(
                    f"{row['name']}: unknown bank_kind {row['bank_kind']!r}"
                )
            shards = row["n_stream_shards"]
            if not (isinstance(shards, int) and shards >= 1):
                raise ValueError(
                    f"{row['name']}: n_stream_shards must be an int >= 1, "
                    f"got {shards!r}"
                )
            pps = row["rows_per_s_per_shard"]
            if not (pps > 0 and abs(pps * shards - row["rows_per_s"])
                    <= 1e-6 * row["rows_per_s"]):
                raise ValueError(
                    f"{row['name']}: rows_per_s_per_shard ({pps!r}) must "
                    "be rows_per_s / n_stream_shards"
                )
            rr = row["remesh_recovery_seconds"]
            if shards > 1:
                if not (rr is not None and rr > 0):
                    raise ValueError(
                        f"{row['name']}: sharded live rows must clock a "
                        f"positive remesh_recovery_seconds, got {rr!r}"
                    )
            elif rr is not None:
                raise ValueError(
                    f"{row['name']}: remesh_recovery_seconds={rr!r} on an "
                    "unsharded row (must be null)"
                )
            continue
        missing = [k for k in RESULT_KEYS if k not in row]
        if missing:
            raise ValueError(f"result {row.get('name')!r} missing {missing}")
        if not (row["seconds_per_batch"] > 0 and row["achieved_gbps"] > 0):
            raise ValueError(f"{row['name']}: non-positive measurement")
        if row["epilogue"] not in ("scores", "ovr", "topk"):
            raise ValueError(
                f"{row['name']}: unknown epilogue {row['epilogue']!r}"
            )
        if row["path"] not in ("ops", "server"):
            raise ValueError(f"{row['name']}: unknown path {row['path']!r}")
        if row["bank_resident"] not in ("vmem", "hbm"):
            raise ValueError(
                f"{row['name']}: unknown bank_resident "
                f"{row['bank_resident']!r}"
            )
        if row["kernel"] not in (None, "linear", "rbf"):
            raise ValueError(
                f"{row['name']}: unknown kernel {row['kernel']!r}"
            )
        if row["kernel"] is not None and not (
            isinstance(row["coreset_size"], int) and row["coreset_size"] >= 1
        ):
            raise ValueError(
                f"{row['name']}: kernelized rows need coreset_size >= 1, "
                f"got {row['coreset_size']!r}"
            )
        if row["kernel"] is None and row["coreset_size"] is not None:
            raise ValueError(
                f"{row['name']}: coreset_size={row['coreset_size']!r} "
                "without a kernel"
            )
        if not (
            isinstance(row["vmem_working_set_bytes"], int)
            and row["vmem_working_set_bytes"] > 0
        ):
            raise ValueError(
                f"{row['name']}: vmem_working_set_bytes must be a positive "
                f"int, got {row['vmem_working_set_bytes']!r}"
            )
        if not row["hbm_peak_gbps"] > 0:
            raise ValueError(
                f"{row['name']}: hbm_peak_gbps must be positive, got "
                f"{row['hbm_peak_gbps']!r}"
            )
        eff = row["dma_overlap_efficiency"]
        if eff is not None and not eff > 0:
            raise ValueError(
                f"{row['name']}: dma_overlap_efficiency must be null or "
                f"positive, got {eff!r}"
            )
    return True


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny CI sweep")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument(
        "--out",
        default=str(Path(__file__).resolve().parent.parent / "BENCH_serving.json"),
    )
    ap.add_argument(
        "--interpret", default=None, choices=["true", "false"],
        help="force interpret mode (default: auto — interpret off-TPU)",
    )
    ap.add_argument(
        "--hbm-peak-gbps", type=float, default=None, metavar="GBPS",
        help="HBM roofline peak in GB/s (default: REPRO_HBM_PEAK_GBPS env "
        "var, else the device's published peak from benchmarks/peaks.py)",
    )
    ap.add_argument(
        "--filter", default=None, metavar="SUBSTR",
        help="bench only configs whose name contains SUBSTR",
    )
    ap.add_argument(
        "--append", action="store_true",
        help="merge results into an existing --out report (rows with the "
        "same name are replaced)",
    )
    args = ap.parse_args(argv)
    interpret = None if args.interpret is None else args.interpret == "true"
    use_compile_cache(Path(__file__).resolve().parent.parent)

    report = run(args.smoke, args.reps, interpret, name_filter=args.filter,
                 peak_gbps=args.hbm_peak_gbps)
    out_path = Path(args.out)
    if args.append and out_path.exists():
        prev = json.loads(out_path.read_text())
        new_names = {r["name"] for r in report["results"]}
        report["results"] = [
            r for r in prev.get("results", []) if r["name"] not in new_names
        ] + report["results"]
    validate(report)
    out_path.write_text(json.dumps(report, indent=2) + "\n")

    hdr = ("name", "epilogue", "path", "resident", "queries/s",
           "model-scores/s", "GB/s", "roofline%", "overlap-eff", "s/batch")
    print(",".join(hdr))
    for r in report["results"]:
        if r["path"] == "live":
            print(
                f'{r["name"]},{r["bank_kind"]},live,-,'
                f'{r["rows_per_s"]:.0f} rows/s,'
                f'swap={r["swap_latency_s"] * 1e3:.2f}ms,'
                f'recovery={r["recovery_seconds"]:.3f}s,-,-,'
                f'{r["seconds_per_chunk"]:.4f}/chunk'
            )
            continue
        eff = r["dma_overlap_efficiency"]
        print(
            f'{r["name"]},{r["epilogue"]},{r["path"]},{r["bank_resident"]},'
            f'{r["queries_per_s"]:.0f},{r["model_scores_per_s"]:.0f},'
            f'{r["achieved_gbps"]:.3f},{100 * r["roofline_frac"]:.2f},'
            f'{"-" if eff is None else f"{eff:.3f}"},'
            f'{r["seconds_per_batch"]:.4f}'
        )
    print(f"BENCH written: {args.out}")


if __name__ == "__main__":
    main()

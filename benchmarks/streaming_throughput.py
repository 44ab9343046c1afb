"""Engine throughput harness: sweeps the tiled bank engine, emits BENCH JSON.

Sweeps (B, D, N, block_n, b_tile, stream_dtype, variant, n_shards,
bank_resident) over the tiled multi-ball engine, measures seconds/pass,
rows/s and model-rows/s, derives achieved GB/s from the engine's modeled HBM
byte traffic, and compares against a bandwidth-roofline estimate (the
device's published HBM peak from ``peaks.py``, keyed by ``device_kind`` — a
device without one is an error unless ``--hbm-peak-gbps`` or the
``REPRO_HBM_PEAK_GBPS`` env var names a peak; on the CPU interpret backend
no number it prints is a device metric).

The modeled bytes encode the engine's central claim: the stream is read ONCE
per fit regardless of how many bank tiles revisit it (``stream_passes`` stays
1.0 while ``naive_stream_bytes`` shows what B/b_tile passes would cost), and
bf16 stream tiles halve the stream term. Under ``bank_resident="vmem"`` the
bank round-trips HBM twice (in + out), independent of N; under "hbm" it
round-trips once per DATA BLOCK (the 2-slot ring re-fetches and writes back
every (b_tile, D) slice each time a stream block revisits it) — the traffic
the ring's async prefetch/write-back is there to hide. Rows carry the
per-config VMEM working-set estimate (``vmem_working_set_bytes``, from
kernels.ops's residency byte model) and hbm rows carry
``dma_overlap_efficiency`` — seconds(vmem baseline) / seconds(hbm) at equal
shape. The two rows do the SAME fit, so this is the achieved-GB/s ratio at
equal (the baseline's) modeled bytes: 1.0 = the added bank round-trips are
fully hidden behind compute, below 1.0 = they cost wall time. (Each row's
own ``achieved_gbps`` uses its own residency's byte model — the hbm row
genuinely moves more HBM bytes — so the efficiency is NOT the ratio of the
two ``achieved_gbps`` fields.)

``n_shards > 1`` rows run ``core.fit_bank_sharded`` over a ``(n_shards,)``
device mesh — each shard reads 1/n_shards of the stream, so the per-device
byte model divides the stream/sign terms by the shard count and the ideal
scaling efficiency is ``seconds(1 shard) / (n_shards * seconds(n))``.
Configs needing more devices than the process has are SKIPPED (printed, not
silent); CI's bench-smoke forces 8 host devices so the sharded smoke row is
always measured there.

Kernelized rows (schema v3) sweep ``coreset_size`` x ``eviction`` x
``n_shards``: ``eviction`` picks the core-set compression policy
("smallest-coef" or "farthest-point" — the latter maintains an extra (S, S)
core-set Gram carry per model), and ``n_shards > 1`` routes through
``fit_kernel_bank(..., mesh=)`` — per-shard one-pass fits folded with the
kernelized Sec-4.3 merge. Their ``vmem_working_set_bytes`` comes from
``kernels.ops.kernel_engine_vmem_bytes``, the same byte model the fit's
preflight budgets against (``s_tile=`` caps its core-set operand terms).

Writes ``BENCH_engine.json`` at the repo root (schema below) so the perf
trajectory is tracked from this PR onward, and prints one ``BENCH`` line per
config. ``--smoke`` runs a seconds-scale sweep in interpret mode for CI,
which validates the same schema.

    PYTHONPATH=src python benchmarks/streaming_throughput.py [--smoke]
        [--out BENCH_engine.json] [--reps 3]
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
from repro.kernels import streamsvm_fit_many
from repro.kernels.ops import bank_tiling, engine_vmem_bytes
from repro.runtime.compile_cache import use_compile_cache

SCHEMA = "streamsvm-bench-engine/v4"
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
    "name", "B", "D", "N", "block_n", "b_tile", "n_bank_tiles", "n_shards",
    "stream_dtype", "variant", "lookahead", "bank_resident", "kernel",
    "coreset_size", "eviction", "vmem_working_set_bytes", "seconds_per_pass",
    "rows_per_s", "rows_per_s_per_shard", "model_rows_per_s", "bytes",
    "stream_passes",
    "naive_stream_bytes", "achieved_gbps", "hbm_peak_gbps",
    "roofline_seconds", "roofline_frac", "dma_overlap_efficiency",
)


def modeled_bytes(B, D, N, stream_dtype, n_shards=1, *, block_n=256,
                  b_tile=None, bank_resident="vmem", lookahead=None,
                  kernel=None, coreset_size=None):
    """PER-DEVICE HBM bytes per pass under the tiled engine's movement model.

    stream: each (block_n, D) tile DMA'd once (data-major grid) — N*D at the
    stream dtype, NOT multiplied by the B/b_tile bank tiles that revisit it.
    Sharding splits the stream over devices: N/n_shards rows per device.
    signs:  each (b_tile, block_n) tile read once over the whole grid —
    B*N/n_shards per device.
    bank:   under bank_resident="vmem" the (B, D) f32 bank enters and leaves
    HBM once per device (it persists in VMEM across the grid); under "hbm"
    every (b_tile, D) slice round-trips once per DATA BLOCK — the ring
    re-fetches and writes back the whole bank (and the B*L*D lookahead
    windows) each of the ceil(N_shard/block_n) times the stream revisits it —
    EXCEPT when the bank spans <= 2 tiles, where the kernel degenerates to
    load-once/store-once (each tile owns a ring slot) and the traffic equals
    the vmem layout's. The fold's all_gather moves another
    (n_shards-1)*B*(D+3) floats over ICI (not HBM — excluded).
    """
    sz = _DTYPE_BYTES[stream_dtype]
    shard_n = -(-N // n_shards)
    if kernel is not None:
        # Kernelized bank: the stream is still read once (data-major tiles);
        # every tile additionally gathers each model's (S, D) core set back
        # from HBM (the buffer indices change as slots fill/evict, so the
        # gather cannot persist across tiles) and writes the two Gram blocks
        # the recursion reads. State out is the (B, S, D) core-set buffer.
        n_tiles = -(-shard_n // block_n)
        return {
            "stream": shard_n * D * sz,
            "signs": B * shard_n * sz,
            "coreset_gather": n_tiles * B * coreset_size * D * 4,
            "gram_blocks": n_tiles
            * (block_n * B * coreset_size + block_n * block_n) * 4,
            "bank": B * coreset_size * (D + 1) * 4,
        }
    _, n_btiles = bank_tiling(B, b_tile)
    trips = (
        -(-shard_n // block_n)
        if bank_resident == "hbm" and n_btiles > 2
        else 1
    )
    by = {
        "stream": shard_n * D * sz,
        "signs": B * shard_n * sz,
        "bank": 2 * B * D * 4 * trips,
    }
    if bank_resident == "hbm" and lookahead:
        l_max = max(lookahead) if isinstance(lookahead, (tuple, list)) else lookahead
        by["lookahead_windows"] = 2 * B * l_max * D * 4 * trips
    return by


def bench_one(cfg, reps, interpret, peak_gbps):
    B, D, N = cfg["B"], cfg["D"], cfg["N"]
    n_shards = cfg.get("n_shards", 1)
    bank_resident = cfg.get("bank_resident", "vmem")
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    Y = jnp.asarray(np.sign(rng.normal(size=(B, N))).astype(np.float32))
    cs = jnp.asarray(np.full(B, 10.0, np.float32))
    variant = cfg.get("variant", "exact")
    lookahead = cfg.get("lookahead")
    kernel = cfg.get("kernel")
    coreset_size = cfg.get("coreset_size")
    sdt = cfg["stream_dtype"] if cfg["stream_dtype"] != "f32" else None
    if kernel is not None:
        from repro.core import fit_kernel_bank
        from repro.kernels.ops import kernel_engine_vmem_bytes

        eviction = cfg.get("eviction", "smallest-coef")
        s_tile = cfg.get("s_tile")
        mesh = (
            jax.make_mesh((n_shards,), ("data",)) if n_shards > 1 else None
        )
        fit = lambda X_, Y_, cs_: fit_kernel_bank(
            X_, Y_, cs_, kernel=kernel, gamma=0.5,
            coreset_size=coreset_size, eviction=eviction, variant=variant,
            block_n=cfg["block_n"], s_tile=s_tile, stream_dtype=sdt,
            mesh=mesh, interpret=interpret,
        )
        run = lambda: jax.block_until_ready(fit(X, Y, cs))
        run()  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            run()
        sec = (time.perf_counter() - t0) / reps
        by = modeled_bytes(
            B, D, N, cfg["stream_dtype"], n_shards, block_n=cfg["block_n"],
            kernel=kernel, coreset_size=coreset_size,
        )
        total = sum(by.values())
        roofline_sec = total / (peak_gbps * 1e9)
        # Per-step VMEM working set from the engine's own preflight byte
        # model (Gram tiles + the s_tile-capped K_cs block / core-set
        # operand + the stream tile) — the same numbers fit_kernel_bank
        # budgets against.
        working_set = sum(
            kernel_engine_vmem_bytes(
                B, D, coreset_size=coreset_size, block_n=cfg["block_n"],
                s_tile=s_tile, stream_dtype=sdt,
            ).values()
        )
        return {
            "name": cfg["name"],
            "B": B,
            "D": D,
            "N": N,
            "block_n": cfg["block_n"],
            "b_tile": None,
            "n_bank_tiles": 1,
            "n_shards": n_shards,
            "stream_dtype": cfg["stream_dtype"],
            "variant": variant,
            "lookahead": None,
            "bank_resident": "vmem",
            "kernel": kernel,
            "coreset_size": coreset_size,
            "eviction": eviction,
            "vmem_working_set_bytes": working_set,
            "seconds_per_pass": sec,
            "rows_per_s": N / sec,
            # v4: per-device ingest rate — the elastic live loop's scaling
            # denominator (kernelized fits here are single-device)
            "rows_per_s_per_shard": N / sec / n_shards,
            "model_rows_per_s": B * N / sec,
            "bytes": {**by, "total": total},
            "stream_passes": 1.0,
            # a per-model dense kernelized fit would re-read the stream B
            # times (and carry O(N) coefficients); the bank reads it once
            "naive_stream_bytes": B * by["stream"],
            "achieved_gbps": total / sec / 1e9,
            "hbm_peak_gbps": peak_gbps,
            "roofline_seconds": roofline_sec,
            "roofline_frac": roofline_sec / sec,
            "dma_overlap_efficiency": None,
        }
    kw = dict(
        variant=variant,
        lookahead=lookahead,
        block_n=cfg["block_n"],
        b_tile=cfg["b_tile"],
        stream_dtype=sdt,
        bank_resident=bank_resident,
        interpret=interpret,
    )
    if n_shards > 1:
        from repro.core import fit_bank_sharded

        mesh = jax.make_mesh((n_shards,), ("data",))
        fit = jax.jit(
            lambda X_, Y_, cs_: fit_bank_sharded(X_, Y_, cs_, mesh, **kw)
        )
    else:
        fit = lambda X_, Y_, cs_: streamsvm_fit_many(X_, Y_, cs_, **kw)
    run = lambda: jax.block_until_ready(fit(X, Y, cs))
    run()  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        run()
    sec = (time.perf_counter() - t0) / reps

    b_tile_eff, n_btiles = bank_tiling(B, cfg["b_tile"])
    by = modeled_bytes(
        B, D, N, cfg["stream_dtype"], n_shards, block_n=cfg["block_n"],
        b_tile=cfg["b_tile"], bank_resident=bank_resident,
        lookahead=lookahead,
    )
    total = sum(by.values())
    roofline_sec = total / (peak_gbps * 1e9)
    l_max = (
        max(lookahead) if isinstance(lookahead, (tuple, list)) else lookahead
    )
    working_set = sum(
        engine_vmem_bytes(
            B, D, block_n=cfg["block_n"], b_tile=cfg["b_tile"],
            stream_dtype=(
                cfg["stream_dtype"] if cfg["stream_dtype"] != "f32" else None
            ),
            lookahead_max=l_max, bank_resident=bank_resident,
        ).values()
    )
    return {
        "name": cfg["name"],
        "B": B,
        "D": D,
        "N": N,
        "block_n": cfg["block_n"],
        "b_tile": b_tile_eff,
        "n_bank_tiles": n_btiles,
        "n_shards": n_shards,
        "stream_dtype": cfg["stream_dtype"],
        "variant": variant,
        "lookahead": lookahead,
        "bank_resident": bank_resident,
        "kernel": None,
        "coreset_size": None,
        "eviction": None,
        "vmem_working_set_bytes": working_set,
        "seconds_per_pass": sec,
        "rows_per_s": N / sec,
        # v4: ingest rate per mesh device — flat rows_per_s across shard
        # counts means linear weak scaling of the sharded engine
        "rows_per_s_per_shard": N / sec / n_shards,
        "model_rows_per_s": B * N / sec,  # conditional updates applied / s
        "bytes": {**by, "total": total},
        "stream_passes": 1.0,  # data-major grid: NOT B/b_tile
        "naive_stream_bytes": n_btiles * by["stream"],  # bank-major would pay this
        "achieved_gbps": total / sec / 1e9,
        "hbm_peak_gbps": peak_gbps,
        "roofline_seconds": roofline_sec,
        "roofline_frac": roofline_sec / sec,
        # filled in post-sweep for hbm rows with a named vmem baseline
        "dma_overlap_efficiency": None,
    }


def sweep(smoke: bool):
    if smoke:
        base = dict(B=16, D=64, N=512, block_n=128)
        return [
            dict(name="smoke_single_tile", **base, b_tile=None, stream_dtype="f32"),
            dict(name="smoke_tiled", **base, b_tile=8, stream_dtype="f32"),
            dict(name="smoke_bf16", **base, b_tile=8, stream_dtype="bf16"),
            dict(name="smoke_lookahead", **base, b_tile=8, stream_dtype="f32",
                 variant="lookahead", lookahead=4),
            # HBM-resident bank: same shape as smoke_tiled, bank double-
            # buffered through the ring — the ratio of achieved GB/s is the
            # DMA-overlap efficiency (CI asserts this row + its fields)
            dict(name="smoke_hbm", **base, b_tile=8, stream_dtype="f32",
                 bank_resident="hbm", overlap_baseline="smoke_tiled"),
            # sharded bank engine (needs >= 8 devices; CI's bench-smoke job
            # forces 8 host devices via XLA_FLAGS so this row is measured)
            dict(name="smoke_sharded_s8", **base, b_tile=8, stream_dtype="f32",
                 n_shards=8),
            # kernelized core-set bank: same one-pass read, RBF Gram blocks
            # through the fused epilogue (CI asserts this row + its fields)
            dict(name="smoke_kernel_rbf", **base, b_tile=None,
                 stream_dtype="f32", kernel="rbf", coreset_size=32),
            # eviction-policy variant of the same kernelized fit
            dict(name="smoke_kernel_rbf_fp", **base, b_tile=None,
                 stream_dtype="f32", kernel="rbf", coreset_size=32,
                 eviction="farthest-point"),
            # mesh-sharded kernelized bank (8 host devices in CI's second
            # bench-smoke pass; CI asserts this row carries n_shards == 8
            # and an eviction field)
            dict(name="smoke_sharded_kernel_rbf_s8", **base, b_tile=None,
                 stream_dtype="f32", kernel="rbf", coreset_size=32,
                 n_shards=8),
        ]
    base = dict(D=128, N=4096, block_n=256)
    cfgs = [
        # bank scaling at fixed tile: one stream pass for 1x..8x the tile
        dict(name="bank_b64_single_tile", B=64, **base, b_tile=None,
             stream_dtype="f32"),
        dict(name="bank_b64_t8", B=64, **base, b_tile=8, stream_dtype="f32"),
        dict(name="bank_b128_t8", B=128, **base, b_tile=8, stream_dtype="f32"),
        dict(name="bank_b256_t32", B=256, **base, b_tile=32, stream_dtype="f32"),
        # dtype policy: same shape, half the stream bytes
        dict(name="bank_b64_t8_bf16", B=64, **base, b_tile=8,
             stream_dtype="bf16"),
        dict(name="bank_b256_t32_bf16", B=256, **base, b_tile=32,
             stream_dtype="bf16"),
        # fused Algorithm-2 lookahead in the same single pass
        dict(name="lookahead_b64_t8_L8", B=64, **base, b_tile=8,
             stream_dtype="f32", variant="lookahead", lookahead=8),
        # HBM-resident bank: equal-shape pair measures the DMA-overlap
        # efficiency (how much of the per-block bank round-trip the ring's
        # async prefetch/write-back hides behind the MXU work)
        dict(name="bank_b256_t32_hbm", B=256, **base, b_tile=32,
             stream_dtype="f32", bank_resident="hbm",
             overlap_baseline="bank_b256_t32"),
        # a bank whose (B, D) f32 footprint (25.2 MB) exceeds the default
        # 16 MiB VMEM budget — impossible to hold VMEM-resident at all
        dict(name="bank_b1536_d4096_hbm_beyond_vmem", B=1536, D=4096, N=1024,
             block_n=256, b_tile=64, stream_dtype="f32",
             bank_resident="hbm"),
        # block_n sensitivity
        dict(name="bank_b64_t8_n512", B=64, D=128, N=4096, block_n=512,
             b_tile=8, stream_dtype="f32"),
        # stream sharding: same fit spread over a device mesh — scaling
        # efficiency is seconds(bank_b64_t8) / (n_shards * seconds(row))
        dict(name="sharded_b64_t8_s2", B=64, **base, b_tile=8,
             stream_dtype="f32", n_shards=2),
        dict(name="sharded_b64_t8_s4", B=64, **base, b_tile=8,
             stream_dtype="f32", n_shards=4),
        dict(name="sharded_b64_t8_s8", B=64, **base, b_tile=8,
             stream_dtype="f32", n_shards=8),
        dict(name="sharded_b256_t32_s8_bf16", B=256, **base, b_tile=32,
             stream_dtype="bf16", n_shards=8),
        # kernelized core-set bank: bounded O(B*S*D) state, per-tile RBF /
        # linear Gram blocks through the fused epilogue, one stream pass
        dict(name="kernel_rbf_b16_s64", B=16, **base, b_tile=None,
             stream_dtype="f32", kernel="rbf", coreset_size=64),
        dict(name="kernel_rbf_b64_s64", B=64, **base, b_tile=None,
             stream_dtype="f32", kernel="rbf", coreset_size=64),
        dict(name="kernel_linear_b16_s64", B=16, **base, b_tile=None,
             stream_dtype="f32", kernel="linear", coreset_size=64),
        dict(name="kernel_rbf_b16_s64_bf16", B=16, **base, b_tile=None,
             stream_dtype="bf16", kernel="rbf", coreset_size=64),
        # core-set size sweep: S is the state/accuracy knob — smaller S
        # means less Gram work and gather traffic per tile
        dict(name="kernel_rbf_b16_s16", B=16, **base, b_tile=None,
             stream_dtype="f32", kernel="rbf", coreset_size=16),
        dict(name="kernel_rbf_b16_s128", B=16, **base, b_tile=None,
             stream_dtype="f32", kernel="rbf", coreset_size=128),
        # eviction-policy sweep at fixed shape: farthest-point maintains a
        # per-model (S, S) core-set Gram carry on top of smallest-coef
        dict(name="kernel_rbf_b16_s64_fp", B=16, **base, b_tile=None,
             stream_dtype="f32", kernel="rbf", coreset_size=64,
             eviction="farthest-point"),
        # mesh-sharded kernelized bank: per-shard one-pass fits folded with
        # the kernelized Sec-4.3 merge (measured in the forced-8-device
        # second pass, like the linear sharded rows)
        dict(name="sharded_kernel_rbf_b16_s64_s8", B=16, **base, b_tile=None,
             stream_dtype="f32", kernel="rbf", coreset_size=64, n_shards=8),
        dict(name="sharded_kernel_rbf_b16_s64_fp_s8", B=16, **base,
             b_tile=None, stream_dtype="f32", kernel="rbf", coreset_size=64,
             eviction="farthest-point", n_shards=8),
    ]
    return cfgs


def run(smoke: bool, reps: int, interpret, name_filter: str | None = None,
        peak_gbps: float | None = None):
    peak = hbm_peak_gbps(peak_gbps)
    n_dev = len(jax.devices())
    results = []
    baselines = {}
    for cfg in sweep(smoke):
        if name_filter is not None and name_filter not in cfg["name"]:
            continue
        if cfg.get("n_shards", 1) > n_dev:
            # no silent caps: say what was dropped and how to get it
            print(
                f'SKIP {cfg["name"]}: n_shards={cfg["n_shards"]} > '
                f"{n_dev} visible device(s) (set XLA_FLAGS="
                f'--xla_force_host_platform_device_count={cfg["n_shards"]} '
                "and re-run with --filter sharded --append, or use a real "
                "mesh)"
            )
            continue
        row = bench_one(cfg, reps, interpret, peak)
        base = baselines.get(cfg.get("overlap_baseline"))
        if base is not None:
            # DMA-overlap efficiency: wall time vs the equal-shape
            # VMEM-resident baseline — same fit, so 1.0 = the hbm bank
            # round-trips fully hidden behind compute (see module docstring;
            # deliberately NOT the ratio of the rows' achieved_gbps, whose
            # byte models differ)
            row["dma_overlap_efficiency"] = (
                base["seconds_per_pass"] / row["seconds_per_pass"]
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

    This validates the report's SHAPE and that the measurements are sane
    numbers. The one-pass property itself (stream_passes == 1.0) is a design
    invariant of the data-major grid, enforced by the kernel parity suites
    (tests/test_tiled_engine.py bit-exactness across b_tile), not something
    this harness can measure from wall time in interpret mode — the field is
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
        missing = [k for k in RESULT_KEYS if k not in row]
        if missing:
            raise ValueError(f"result {row.get('name')!r} missing {missing}")
        if not (row["seconds_per_pass"] > 0 and row["achieved_gbps"] > 0):
            raise ValueError(f"{row['name']}: non-positive measurement")
        if not (isinstance(row["n_shards"], int) and row["n_shards"] >= 1):
            raise ValueError(
                f"{row['name']}: n_shards must be an int >= 1, got "
                f"{row['n_shards']!r}"
            )
        pps = row["rows_per_s_per_shard"]
        if not (pps > 0 and abs(pps * row["n_shards"] - row["rows_per_s"])
                <= 1e-6 * row["rows_per_s"]):
            raise ValueError(
                f"{row['name']}: rows_per_s_per_shard ({pps!r}) must be "
                f"rows_per_s / n_shards"
            )
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
        if row["kernel"] is not None:
            if row["eviction"] not in ("smallest-coef", "farthest-point"):
                raise ValueError(
                    f"{row['name']}: kernelized rows need eviction in "
                    "('smallest-coef', 'farthest-point'), got "
                    f"{row['eviction']!r}"
                )
        elif row["eviction"] is not None:
            raise ValueError(
                f"{row['name']}: eviction={row['eviction']!r} without a "
                "kernel"
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
        default=str(Path(__file__).resolve().parent.parent / "BENCH_engine.json"),
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
        "same name are replaced). Lets sharded rows — which need forced "
        "host devices — be measured in a separate process from the "
        "single-device rows, which must see the real device count "
        "(conftest rule); CI's bench-smoke runs the harness twice this way",
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

    hdr = ("name", "shards", "resident", "rows/s", "model-rows/s", "GB/s",
           "roofline%", "overlap-eff", "s/pass")
    print(",".join(hdr))
    for r in report["results"]:
        eff = r["dma_overlap_efficiency"]
        print(
            f'{r["name"]},{r["n_shards"]},{r["bank_resident"]},'
            f'{r["rows_per_s"]:.0f},'
            f'{r["model_rows_per_s"]:.0f},'
            f'{r["achieved_gbps"]:.3f},{100 * r["roofline_frac"]:.2f},'
            f'{"-" if eff is None else f"{eff:.3f}"},'
            f'{r["seconds_per_pass"]:.4f}'
        )
    print(f"BENCH written: {args.out}")


if __name__ == "__main__":
    main()

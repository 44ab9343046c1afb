#!/usr/bin/env python3
"""Smoke test of the one-pass bank trainer and server on a TPU.

    python chip_smoke.py             # phases (a)-(e) on one chip
    python chip_smoke.py --chips 4   # the stream-sharded fit, on four chips

Drives the main path through its public entry points at real widths, with
data generated from ``--seed``:

  (a) ``core.fit_bank``: a 200-class one-vs-rest x 3-point C-grid bank
      (B = 600) over N = 65,536 rows at D = 784, default residency, f32;
  (b) the same fit HBM-resident: 1000 classes x 3 C (B = 3000) at D = 4096
      over N = 16,384 rows, ``bank_resident="hbm"``;
  (c) ``ckpt.save`` -> ``BankServer.from_checkpoint(epilogue="ovr")``
      answering ragged query batches against the bank of (a);
  (d) ``core.fit_kernel_bank`` (RBF, S = 64) served through
      ``kernels.predict_kernel_bank``;
  (e) ``live.LiveBank``: a few chunks with one injected kill and a resume,
      which must end bit-identical to the same run without the kill.

``--chips 4`` runs only ``core.fit_bank(..., mesh=)`` at the shapes of (a)
against its referent: per-range single-device fits over
``core.shard_ranges`` folded in order, which must be bit-identical.

Each phase checks its result against a plain numpy reference computed on the
host (never an XLA expression on the chip, whose default f32 matmul
precision is not Mosaic's) and prints one line: its shapes, its host
wall-clock time (compilation included — not a device metric) and the
measured error beside each tolerance. The last line of standard output is
one JSON object naming the device. The script exits non-zero, and prints no
JSON line, when JAX finds no TPU, when the ``repro`` package is not next to
it, or when any phase fails. It runs in one process: a chip belongs to one
process at a time.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says; when that
is unset, to ``.jax_cache/`` next to this script.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# --- tolerances (each with its reason) --------------------------------------
#: Relative error of a trained center against the float64 host reference,
#: and of a radius. The engine runs the recursion in f32 in another order
#: (block Gram + rank-1 updates vs. direct distances), so rounding differs
#: by a few ulps per row and accumulates over the absorbed rows: about 5e-7
#: on a v5e. 1e-4 leaves room for data-dependent drift and still fails a
#: dot rounded through one bf16 pass (about 2e-3).
FIT_RTOL = 1e-4
#: Core-vector count slack. An update at d ~ r has step s = (1 - r/d)/2 ~ 0,
#: so a decision that rounding flips changes m by one and the center by
#: almost nothing: allow 1% of m, plus 2.
M_SLACK = (0.01, 2)
#: Sign agreement of a trained model with its reference on held-out rows.
SIGN_AGREE = 0.995
#: Served margin error, relative to ||x|| * max_b ||w_b||. An f32 dot of
#: D <= 4096 terms rounds within ~sqrt(D) * 2**-24 of that scale (~4e-6 at
#: D = 784); 1e-5 bounds it and would catch a single bf16 pass (~4e-3).
MARGIN_RTOL = 1e-5
#: Kernel-bank fits: share of models whose core-set indices must equal the
#: reference's (a rounding-flipped absorb or eviction changes one model's
#: set), and the coefficient tolerance where they do (products of up to m
#: f32 step factors).
KB_IDX_AGREE, KB_COEF_RTOL, KB_COEF_ATOL = 0.9, 1e-3, 1e-5
#: Kernel-bank served scores, relative to sum_s |coef|: each score is
#: sum_s coef_s * k_s with k in [0, 1], and each RBF value carries the f32
#: rounding of its exponent.
KB_SCORE_RTOL = 1e-5


#: Phase shapes. (a)/(e)/sharded: the ROADMAP quickstart bank; (b): its
#: beyond-VMEM bank at smaller N.
SIZES = {
    "linear": dict(n=65536, d=784, n_classes=200, n_query=2048),
    "hbm": dict(n=16384, d=4096, n_classes=1000, n_query=512),
    "kernel": dict(n=2048, d=784, n_classes=10, n_query=512, S=64),
    "live": dict(chunk=4096, n_chunks=6),
}
C_POINTS = (1.0, 10.0, 100.0)


class PhaseFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


# --- data ---------------------------------------------------------------------


def blobs(rng, n, d, n_classes, proto_scale=3.0):
    """Unit-norm rows around per-class prototypes, labels uniform."""
    proto = (rng.standard_normal((n_classes, d)) * proto_scale).astype(np.float32)
    labels = rng.integers(0, n_classes, size=n)
    X = rng.standard_normal((n, d), dtype=np.float32)
    X += proto[labels]
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    return X, labels


def ovr_grid(labels, n_classes, c_points):
    """(B, N) class-major OVR sign rows per C point, and the (B,) Cs."""
    signs = np.where(
        labels[None, :] == np.arange(n_classes)[:, None], 1.0, -1.0
    ).astype(np.float32)
    Y = np.tile(signs, (len(c_points), 1))
    cs = np.repeat(np.asarray(c_points, np.float32), n_classes)
    return Y, cs


# --- host references ------------------------------------------------------------


def alg1_ref(X, Y, cs):
    """Algorithm 1 (exact slack gain) in float64 numpy, models vectorized,
    rows in stream order; row 0 seeds every model (the engine's init)."""
    X = np.asarray(X, np.float64)
    Y = np.asarray(Y, np.float64)
    c_inv = 1.0 / np.asarray(cs, np.float64)
    w = Y[:, :1] * X[0][None, :]
    r = np.zeros(len(c_inv))
    xi2 = c_inv.copy()
    m = np.ones(len(c_inv), np.int64)
    for i in range(1, X.shape[0]):
        row = Y[:, i : i + 1] * X[i][None, :]
        d = np.sqrt(np.maximum(
            np.sum((w - row) ** 2, axis=1) + xi2 + c_inv, 1e-12
        ))
        upd = (d >= r) & (Y[:, i] != 0)
        if not upd.any():
            continue
        s = np.where(upd, 0.5 * (1.0 - r / d), 0.0)
        w = (1.0 - s)[:, None] * w + s[:, None] * row
        r = np.where(upd, r + 0.5 * (d - r), r)
        xi2 = xi2 * (1.0 - s) ** 2 + s**2 * c_inv
        m += upd
    return w, r, m


def check_fit_against_ref(bank, X, Y, cs, sample, Xq):
    """Compare the sampled models of a trained bank with alg1_ref."""
    w_ref, r_ref, m_ref = alg1_ref(X, Y[sample], cs[sample])
    w = np.asarray(bank.w)[sample].astype(np.float64)
    r = np.asarray(bank.r)[sample].astype(np.float64)
    m = np.asarray(bank.m)[sample]
    w_err = np.linalg.norm(w - w_ref, axis=1) / np.linalg.norm(w_ref, axis=1)
    r_err = np.abs(r - r_ref) / np.abs(r_ref)
    m_err = np.abs(m - m_ref)
    agree = np.mean(np.sign(Xq @ w.T) == np.sign(Xq @ w_ref.T), axis=0)
    check(np.isfinite(np.asarray(bank.w)).all(), "non-finite centers")
    check(w_err.max() <= FIT_RTOL, f"center rel err {w_err.max():.3g}")
    check(r_err.max() <= FIT_RTOL, f"radius rel err {r_err.max():.3g}")
    check(
        np.all(m_err <= M_SLACK[0] * m_ref + M_SLACK[1]),
        f"core-vector count off by {m_err.max()}",
    )
    check(agree.min() >= SIGN_AGREE, f"sign agreement {agree.min():.4f}")
    return (
        f"ref {len(sample)} models: center rel err {w_err.max():.2e} "
        f"(tol {FIT_RTOL:g}), radius {r_err.max():.2e}, m off by "
        f"<= {m_err.max()} (m up to {m_ref.max()}), held-out sign "
        f"agreement >= {agree.min():.4f} (tol {SIGN_AGREE})"
    )


def check_ovr_against_ref(cls, margin, W, Xq, n_classes):
    """Served ovr (class, margin) vs the float64 readout of the same bank:
    margins within MARGIN_RTOL of ||x|| max||w||, classes equal wherever
    the reference's winner leads its runner-up by more than that bound."""
    S = np.asarray(Xq, np.float64) @ np.asarray(W, np.float64).T
    q, b = S.shape
    G = S.reshape(q, b // n_classes, n_classes)
    rcls = np.argmax(G, axis=-1)
    top2 = np.sort(G, axis=-1)[..., -2:]
    scale = (
        np.linalg.norm(Xq, axis=1)[:, None]
        * np.linalg.norm(np.asarray(W, np.float64), axis=1).max()
    )
    tol = MARGIN_RTOL * scale
    m_err = np.abs(np.asarray(margin, np.float64) - top2[..., 1])
    decided = (top2[..., 1] - top2[..., 0]) > 2 * tol
    wrong = (np.asarray(cls) != rcls) & decided
    check(np.all(m_err <= tol), f"margin err {(m_err / scale).max():.3g}")
    check(not wrong.any(), f"{int(wrong.sum())} decided classes differ")
    return (
        f"margin err {(m_err / scale).max():.2e} of ||x||max||w|| (tol "
        f"{MARGIN_RTOL:g}), classes equal on {int(decided.sum())}/"
        f"{decided.size} decided answers ({int((~decided).sum())} near-ties "
        "exempt)"
    )


def rbf_scores_ref(Xq, points, coef, gamma):
    """sum_s coef[b, s] exp(-gamma |x - p_bs|^2) in float64 numpy."""
    Xq = np.asarray(Xq, np.float64)
    P = np.asarray(points, np.float64)
    d2 = (
        np.sum(Xq**2, 1)[:, None, None]
        + np.sum(P**2, -1)[None]
        - 2.0 * np.einsum("qd,bsd->qbs", Xq, P)
    )
    K = np.exp(-gamma * np.maximum(d2, 0.0))
    return np.einsum("qbs,bs->qb", K, np.asarray(coef, np.float64))


# --- phases ---------------------------------------------------------------------


def lowered_has_mosaic(fn, *args, **kw) -> bool:
    return "tpu_custom_call" in fn.lower(*args, **kw).as_text()


def phase_linear(ctx, rng):
    import jax
    import jax.numpy as jnp
    from repro.core import fit_bank
    from repro.kernels import ops

    n, d, n_classes, n_query = SIZES["linear"].values()
    X, labels = blobs(rng, n, d, n_classes)
    Y, cs = ovr_grid(labels, n_classes, C_POINTS)
    Xq, _ = blobs(rng, n_query, d, n_classes)
    b = Y.shape[0]
    residency, b_tile = ops.plan_bank_engine(b, d)
    Xd, Yd, csd = jnp.asarray(X), jnp.asarray(Y), jnp.asarray(cs)
    check(
        lowered_has_mosaic(ops.streamsvm_fit_many, Xd, Yd, csd),
        "lowered training step holds no tpu_custom_call",
    )
    t0 = time.perf_counter()
    bank = jax.block_until_ready(fit_bank(Xd, Yd, csd))
    wall = time.perf_counter() - t0
    sample = np.array([c * n_classes + k for c in range(len(C_POINTS))
                       for k in range(0, n_classes, max(1, n_classes // 8))])
    res = check_fit_against_ref(bank, X, Y, cs, sample, Xq)
    ctx.update(bank=bank, X=X, labels=labels, Y=Y, cs=cs, Xq=Xq,
               n_classes=n_classes)
    return (f"N={n} D={d} B={b} residency={residency} b_tile={b_tile} "
            f"stream=f32 wall_s={wall:.3f}", res)


def phase_hbm(ctx, rng):
    import jax
    import jax.numpy as jnp
    from repro.core import fit_bank
    from repro.kernels import ops

    n, d, n_classes, n_query = SIZES["hbm"].values()
    X, labels = blobs(rng, n, d, n_classes)
    Y, cs = ovr_grid(labels, n_classes, C_POINTS)
    Xq, _ = blobs(rng, n_query, d, n_classes)
    b = Y.shape[0]
    _, b_tile = ops.plan_bank_engine(b, d, bank_resident="hbm")
    Xd, Yd, csd = jnp.asarray(X), jnp.asarray(Y), jnp.asarray(cs)
    check(
        lowered_has_mosaic(ops.streamsvm_fit_many, Xd, Yd, csd,
                           bank_resident="hbm"),
        "lowered HBM training step holds no tpu_custom_call",
    )
    t0 = time.perf_counter()
    bank = jax.block_until_ready(fit_bank(Xd, Yd, csd, bank_resident="hbm"))
    wall = time.perf_counter() - t0
    sample = np.array([c * n_classes + k for c in range(len(C_POINTS))
                       for k in range(0, n_classes, max(1, n_classes // 4))])
    res = check_fit_against_ref(bank, X, Y, cs, sample, Xq)
    del Xd, Yd
    return (f"N={n} D={d} B={b} residency=hbm b_tile={b_tile} "
            f"bank_bytes={b * d * 4} wall_s={wall:.3f}", res)


def phase_serve(ctx, rng):
    import jax.numpy as jnp
    from repro.checkpoint import ckpt
    from repro.kernels import predict_bank
    from repro.serve import BankServer

    bank, n_classes = ctx["bank"], ctx["n_classes"]
    Xq = ctx["Xq"]
    with tempfile.TemporaryDirectory() as td:
        ckpt.save(td, bank, meta={"n_classes": n_classes})
        server = BankServer.from_checkpoint(
            td, epilogue="ovr", n_classes=n_classes
        )
    check(
        lowered_has_mosaic(
            predict_bank, jnp.asarray(Xq[: server.q_block]), bank.w,
            epilogue="ovr", n_classes=n_classes, q_block=server.q_block,
        ),
        "lowered serving step holds no tpu_custom_call",
    )
    sizes, lo, reqs = [], 0, []
    t0 = time.perf_counter()
    while lo < len(Xq):
        k = int(rng.integers(1, 400))
        reqs.append(server.submit(Xq[lo : lo + k]))
        sizes.append(min(k, len(Xq) - lo))
        lo += k
    stats = server.run()
    wall = time.perf_counter() - t0
    cls = np.concatenate([r.result[0] for r in reqs])
    margin = np.concatenate([r.result[1] for r in reqs])
    check(cls.shape == (len(Xq), len(C_POINTS)), f"served shape {cls.shape}")
    res = check_ovr_against_ref(cls, margin, np.asarray(bank.w), Xq, n_classes)
    return (f"Q={len(Xq)} in {len(sizes)} ragged requests ({min(sizes)}.."
            f"{max(sizes)} rows), {stats.steps} microbatches of "
            f"{server.q_block}, B={server.bank_shape[0]} D="
            f"{server.bank_shape[1]} wall_s={wall:.3f}", res)


def phase_kernel(ctx, rng):
    import jax
    import jax.numpy as jnp
    from repro.core import fit_kernel_bank
    from repro.kernels import predict_kernel_bank
    from repro.kernels.ref import fit_kernel_bank_ref

    n, d, n_classes, n_query, S = SIZES["kernel"].values()
    c_points, gamma = C_POINTS[:2], 1.0
    X, labels = blobs(rng, n, d, n_classes)
    Y, cs = ovr_grid(labels, n_classes, c_points)
    Xq, _ = blobs(rng, n_query, d, n_classes)
    t0 = time.perf_counter()
    bank = fit_kernel_bank(jnp.asarray(X), jnp.asarray(Y), jnp.asarray(cs),
                           kernel="rbf", gamma=gamma, coreset_size=S)
    scores = jax.block_until_ready(predict_kernel_bank(
        jnp.asarray(Xq), bank.points, bank.coef, kernel="rbf", gamma=gamma
    ))
    wall = time.perf_counter() - t0
    idx_r, coef_r, *_ = fit_kernel_bank_ref(
        X, Y, cs, kernel="rbf", gamma=gamma, coreset_size=S
    )
    idx, coef = np.asarray(bank.idx), np.asarray(bank.coef)
    order = lambda i: np.argsort(np.where(i < 0, n, i), axis=1)
    same = np.all(np.take_along_axis(idx, order(idx), 1)
                  == np.take_along_axis(idx_r, order(idx_r), 1), axis=1)
    c_a = np.take_along_axis(coef, order(idx), 1)[same]
    c_b = np.take_along_axis(coef_r, order(idx_r), 1)[same]
    check(same.mean() >= KB_IDX_AGREE, f"core sets equal on {same.mean():.2f}")
    c_err = np.abs(c_a - c_b) - KB_COEF_RTOL * np.abs(c_b)
    check(np.all(c_err <= KB_COEF_ATOL), "core-set coefficients differ")
    ref = rbf_scores_ref(Xq, bank.points, bank.coef, gamma)
    scale = np.sum(np.abs(coef), axis=1)[None, :]
    s_err = (np.abs(np.asarray(scores, np.float64) - ref) / scale).max()
    check(s_err <= KB_SCORE_RTOL, f"served kernel score err {s_err:.3g}")
    grouped = (len(Xq), len(c_points), n_classes)
    got_cls = np.argmax(np.asarray(scores).reshape(grouped), -1)
    ref_g = np.sort(ref.reshape(grouped), -1)
    decided = ref_g[..., -1] - ref_g[..., -2] > 2 * KB_SCORE_RTOL * scale.max()
    ref_cls = np.argmax(ref.reshape(grouped), -1)
    check(not ((got_cls != ref_cls) & decided).any(), "kernel classes differ")
    return (f"RBF N={n} D={d} B={len(cs)} S={S} Q={len(Xq)} wall_s={wall:.3f}",
            f"core sets equal on {same.mean():.2f} of models (tol "
            f"{KB_IDX_AGREE}), served score err {s_err:.2e} of sum|coef| "
            f"(tol {KB_SCORE_RTOL:g}), classes equal on {int(decided.sum())}/"
            f"{decided.size} decided answers")


def phase_live(ctx, rng):
    import jax.numpy as jnp
    from repro.live import ArraySource, LiveBank
    from repro.runtime import InjectedFailure
    from repro.serve import BankServer

    n_classes = ctx["n_classes"]
    chunk, n_chunks = SIZES["live"].values()
    X = ctx["X"][: chunk * n_chunks]
    Y = ctx["Y"][:, : chunk * n_chunks]
    cs, Xq = ctx["cs"], ctx["Xq"][:512]

    def make(ckpt_dir, failpoints=None):
        return LiveBank(
            ArraySource(X, Y, chunk), jnp.asarray(cs), ckpt_dir=ckpt_dir,
            n_sub_banks=2, rotate_every=3, swap_every=2,
            failpoints=failpoints,
            server_factory=lambda bank: BankServer(
                bank, epilogue="ovr", n_classes=n_classes
            ),
        )

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as clean_dir, \
            tempfile.TemporaryDirectory() as crash_dir:
        clean = make(clean_dir)
        clean_stats = clean.run()
        crashy = make(crash_dir, failpoints={("post_train", 3)})
        try:
            crashy.run()
        except InjectedFailure:
            killed = True
        else:
            killed = False
        check(killed, "the injected kill did not fire")
        resumed = make(crash_dir)  # a fresh trainer: resume from disk
        stats = resumed.run()
        want = clean.serving_bank()
        got = resumed.serving_bank()
        same_bank = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(got, want)
        )
        s_clean = clean.server.score(Xq)
        s_got = resumed.server.score(Xq)
    wall = time.perf_counter() - t0
    check(same_bank, "recovered bank differs from the kill-free run")
    check(all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(s_got, s_clean)), "served answers differ")
    check(stats.durable() == clean_stats.durable(), "durable stats differ")
    res = check_ovr_against_ref(
        np.asarray(s_got[0]), np.asarray(s_got[1]), np.asarray(got.w), Xq,
        n_classes,
    )
    return (f"{n_chunks} chunks of {chunk} rows, B={len(cs)} D={X.shape[1]}, "
            f"kill at post_train of chunk 3 then resume; wall_s={wall:.3f}",
            "recovered bank, served answers and durable stats bit-identical "
            f"to the kill-free run; {res}")


def phase_sharded(ctx, rng):
    import jax
    import jax.numpy as jnp
    from repro.core import fit_bank, fold_merge, shard_ranges, stack_banks

    n, d, n_classes, _ = SIZES["linear"].values()
    X, labels = blobs(rng, n, d, n_classes)
    Y, cs = ovr_grid(labels, n_classes, C_POINTS)
    devices = jax.devices()
    mesh = jax.make_mesh((len(devices),), ("data",))
    Xd, Yd, csd = jnp.asarray(X), jnp.asarray(Y), jnp.asarray(cs)
    step = jax.jit(lambda X, Y, c: fit_bank(X, Y, c, mesh=mesh))
    check(lowered_has_mosaic(step, Xd, Yd, csd),
          "sharded step holds no tpu_custom_call")
    check(f"num_partitions = {len(devices)}" in step.lower(Xd, Yd, csd).as_text(),
          "the sharded step is not partitioned over every device")
    t0 = time.perf_counter()
    out = jax.block_until_ready(fit_bank(Xd, Yd, csd, mesh=mesh))
    wall = time.perf_counter() - t0
    # every device holds its own contiguous quarter of the stream and ends
    # with the folded bank
    placed = jax.device_put(
        Xd, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    )
    homes = {s.device: s.index[0].start for s in placed.addressable_shards}
    check(len(homes) == len(devices) == len(set(homes.values())),
          f"stream shards on {len(homes)} devices")
    check(out.w.sharding.device_set == set(devices),
          "the folded bank is not on every device")
    banks = [fit_bank(Xd[lo:hi], Yd[:, lo:hi], csd)
             for lo, hi in shard_ranges(n, len(devices)) if hi > lo]
    ref = fold_merge(stack_banks(banks))
    diffs = {f: float(np.max(np.abs(np.asarray(getattr(out, f), np.float64)
                                    - np.asarray(getattr(ref, f), np.float64))))
             for f in ("w", "r", "xi2", "m")}
    check(all(v == 0.0 for v in diffs.values()),
          f"mesh fit differs from the per-range fold: {diffs}")
    return (f"N={n} D={d} B={len(cs)} over {len(devices)} devices "
            f"({n // len(devices)} rows each) wall_s={wall:.3f}",
            "bit-identical to the per-range single-device fits folded in "
            "order; one stream shard per device, folded bank on every device")


PHASES_ONE_CHIP = (
    ("a linear-bank-fit", phase_linear),
    ("b hbm-bank-fit", phase_hbm),
    ("c serve-from-checkpoint", phase_serve),
    ("d kernel-bank", phase_kernel),
    ("e live-kill-resume", phase_live),
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.kernels import ops
        from repro.runtime.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = use_compile_cache(ROOT)
    if ops.resolve_interpret(None):
        print("chip_smoke: kernels would run in interpret mode on a TPU",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)

    phases = (
        (("sharded-fit", phase_sharded),) if args.chips == 4
        else PHASES_ONE_CHIP
    )
    rng = np.random.default_rng(args.seed)
    ctx: dict = {}
    for name, fn in phases:
        try:
            shapes, result = fn(ctx, rng)
        except Exception:
            import traceback

            traceback.print_exc()
            print(f"phase {name}: FAILED", flush=True)
            return 1
        print(f"phase {name}: {shapes} | {result} | ok", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""StreamSVM — one-pass l2-SVM via streaming MEB (paper Algorithms 1 & 2).

Entry points
------------
fit(X, y, c)                    Algorithm 1 (closed-form updates), lax.scan:
                                the reference the bank engine is tested
                                against (``fit_ball`` continues a ball).
fit_lookahead(X, y, c, L)       Algorithm 2 for one model through the fused
                                bank engine (kernels.ops.streamsvm_fit_many).
fit_lookahead_ball(...)         Algorithm 2 as a lax.scan that solves each
                                window of L violators (BC solve).
fit_chunked(...)                python-level streaming driver over an
                                iterator of chunks, with checkpoint hooks —
                                the "real" one-pass entry point.
fit_chunked_many(...)           same driver for a BANK of B models (classes x
                                C-grid x variants) via the multi-ball Pallas
                                engine: one data pass total, O(B*D) state.
decision_function / predict     linear classifier readout.

All core math lives in meb.py / qp.py; this module provides the streaming
control flow. Banks of classes x hyper-parameter grids train through
``fit_bank`` (multiball.py, multiclass.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from .meb import Ball, enclose_point, make_ball, point_distance
from .qp import solve_meb_ball_points


def init_ball(x1: jax.Array, y1: jax.Array, c: float, *, variant: str = "exact") -> Ball:
    """Paper line 3: w = y1 x1, R = 0, xi2 = 1/C (exact) or 1 (paper-listing)."""
    w = y1 * x1
    xi2 = (1.0 / c) if variant == "exact" else 1.0
    return make_ball(w, r=0.0, xi2=xi2, m=1)


def _step(ball: Ball, yx: jax.Array, c_inv, variant: str) -> Tuple[Ball, jax.Array]:
    d = point_distance(ball, yx, c_inv)
    update = d >= ball.r
    new = enclose_point(ball, yx, c_inv, variant=variant)
    out = jax.tree.map(lambda a, b: jnp.where(update, a, b), new, ball)
    return out, update


def fit_ball(ball: Ball, X: jax.Array, y: jax.Array, c: float, *, variant: str = "exact") -> Ball:
    """Continue Algorithm 1 from an existing ball over (X, y)."""
    c_inv = jnp.asarray(1.0 / c, X.dtype)
    yx = y[:, None] * X

    def body(b, row):
        return _step(b, row, c_inv, variant)

    ball, _ = jax.lax.scan(body, ball, yx)
    return ball


def fit(X: jax.Array, y: jax.Array, c: float, *, variant: str = "exact") -> Ball:
    """Algorithm 1 over a full (in-memory) stream. X: (N, D), y: (N,) in ±1."""
    ball = init_ball(X[0], y[0], c, variant=variant)
    return fit_ball(ball, X[1:], y[1:], c, variant=variant)


# ---------------------------------------------------------------------------
# Algorithm 2 — lookahead
# ---------------------------------------------------------------------------


def fit_lookahead_ball(
    ball: Ball,
    X: jax.Array,
    y: jax.Array,
    c: float,
    lookahead: int,
    *,
    qp_iters: int = 128,
) -> Ball:
    """Continue Algorithm 2 from an existing ball."""
    L = int(lookahead)
    c_inv = jnp.asarray(1.0 / c, X.dtype)
    yx = y[:, None] * X
    D = X.shape[-1]

    buf0 = jnp.zeros((L, D), X.dtype)
    cnt0 = jnp.asarray(0, jnp.int32)

    def body(carry, row):
        b, buf, cnt = carry
        d = point_distance(b, row, c_inv)
        take = d >= b.r
        buf = jnp.where(take, buf.at[cnt].set(row), buf)
        cnt = cnt + take.astype(jnp.int32)

        def flush(args):
            b_, buf_, cnt_ = args
            valid = jnp.arange(L) < cnt_
            b_ = solve_meb_ball_points(b_, buf_, valid, c_inv, iters=qp_iters)
            return b_, jnp.zeros_like(buf_), jnp.zeros_like(cnt_)

        b, buf, cnt = jax.lax.cond(
            cnt >= L, flush, lambda a: a, (b, buf, cnt)
        )
        return (b, buf, cnt), take

    (ball, buf, cnt), _ = jax.lax.scan(body, (ball, buf0, cnt0), yx)
    # Final partial flush (paper lines 12-14).
    valid = jnp.arange(L) < cnt
    return solve_meb_ball_points(ball, buf, valid, c_inv, iters=qp_iters)


def fit_lookahead(
    X: jax.Array,
    y: jax.Array,
    c: float,
    lookahead: int,
    *,
    variant: str = "exact",
    block_n: int = 256,
    stream_dtype=None,
    bank_resident: str = "auto",
) -> Ball:
    """Algorithm 2 for one model. lookahead=1 is Algorithm 1 exactly.

    Runs the fused lookahead path of the bank engine as a bank of one: the
    L-row window lives in VMEM next to the ball and is flushed
    farthest-point-first inside the kernel (greedy Badoiu-Clarkson
    insertion over the window), so Algorithm 2 costs the same single stream
    read as Algorithm 1. ``fit_lookahead_ball`` (what ``fit_chunked`` uses,
    chunk by chunk) solves each buffered window with the iterative BC
    solver in qp.py instead; the two accept slightly different core-vector
    sets (greedy insertion vs window solve), and both satisfy the paper's
    enclosure guarantee.
    """
    if variant not in ("exact", "paper-listing"):
        raise ValueError(
            f"unknown variant {variant!r}; expected 'exact' or 'paper-listing'"
        )
    from .multiball import fit_bank

    bank = fit_bank(
        X, y[None, :].astype(X.dtype), c,
        variant="lookahead" if variant == "exact" else "lookahead-paper",
        lookahead=int(lookahead),
        block_n=block_n, stream_dtype=stream_dtype,
        bank_resident=bank_resident,
    )
    return jax.tree.map(lambda v: v[0], bank)


# ---------------------------------------------------------------------------
# Streaming driver (true one-pass over an iterator, constant memory)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StreamCheckpoint:
    ball: Ball
    position: int  # number of examples consumed


def fit_chunked(
    chunks: Iterable[Tuple[jax.Array, jax.Array]],
    c: float,
    *,
    lookahead: int = 1,
    variant: str = "exact",
    qp_iters: int = 128,
    resume: Optional[StreamCheckpoint] = None,
    checkpoint_every: int = 0,
    checkpoint_cb: Optional[Callable[[StreamCheckpoint], None]] = None,
) -> StreamCheckpoint:
    """One pass over an iterator of (X_chunk, y_chunk) with constant memory.

    The jit'd per-chunk update keeps state O(D); ``checkpoint_cb`` receives a
    StreamCheckpoint every ``checkpoint_every`` consumed examples, enabling
    preemption-safe resume *without a second pass* (resume at .position).
    NOTE: lookahead buffers are flushed at chunk boundaries when
    lookahead > 1; with the default chunk sizes (>= 4096) this matches the
    paper's final-flush semantics per chunk and keeps resume state O(D).
    """
    ball = resume.ball if resume is not None else None
    pos = resume.position if resume is not None else 0
    since_ckpt = 0

    if lookahead <= 1:
        step = jax.jit(fit_ball, static_argnames=("c", "variant"))
    else:
        step = jax.jit(
            fit_lookahead_ball, static_argnames=("c", "lookahead", "qp_iters")
        )

    it: Iterator = iter(chunks)
    for Xc, yc in it:
        Xc = jnp.asarray(Xc)
        yc = jnp.asarray(yc)
        n_chunk = int(Xc.shape[0])
        if ball is None:
            ball = init_ball(Xc[0], yc[0], c, variant=variant)
            Xc, yc = Xc[1:], yc[1:]
        if Xc.shape[0]:
            if lookahead <= 1:
                ball = step(ball, Xc, yc, c=c, variant=variant)
            else:
                ball = step(ball, Xc, yc, c=c, lookahead=lookahead, qp_iters=qp_iters)
        pos += n_chunk
        since_ckpt += n_chunk
        if checkpoint_every and checkpoint_cb and since_ckpt >= checkpoint_every:
            checkpoint_cb(StreamCheckpoint(ball=jax.tree.map(jnp.asarray, ball), position=pos))
            since_ckpt = 0
    if ball is None:
        raise ValueError(
            "fit_chunked got an empty stream: the chunk iterator yielded no "
            f"examples (resume={resume!r}) — at least one (X, y) chunk with "
            "one row is required to initialize the ball"
        )
    return StreamCheckpoint(ball=ball, position=pos)


def fit_chunked_many(
    chunks: Iterable[Tuple[jax.Array, jax.Array]],
    cs,
    *,
    variant: str = "exact",
    block_n: int = 256,
    b_tile: Optional[int] = None,
    stream_dtype=None,
    bank_resident: str = "auto",
    mesh=None,
    shard_axis="data",
    resume: Optional[StreamCheckpoint] = None,
    checkpoint_every: int = 0,
    checkpoint_cb: Optional[Callable[[StreamCheckpoint], None]] = None,
) -> StreamCheckpoint:
    """One pass of the multi-ball engine over an iterator of chunks.

    Bank analogue of ``fit_chunked``: ``cs`` is a (B,) array of per-model C
    values and each chunk is ``(X_chunk, y_chunk)`` with ``y_chunk`` either
    (n,) shared +-1 labels (broadcast to every model — the C-grid case) or
    (B, n) per-model sign rows (the one-vs-rest case). The checkpoint carries
    the whole bank — state stays O(B * D) — so preemption/resume keeps the
    stream single-pass for all B models at once. ``bank_resident`` passes
    through to the engine per chunk ("hbm" double-buffers banks beyond VMEM
    scratch through HBM; checkpoints are residency-agnostic — a run may
    resume under a different residency, bit-exact in f32).

    ``mesh=`` shards every chunk over the ``shard_axis`` axes of a device
    mesh (distributed.fit_bank_sharded): each shard fits its contiguous
    slice of the chunk fresh and the per-shard banks are folded with the
    Sec-4.3 merge, the prior bank folding in as one more disjoint summand.
    Because the checkpoint still carries ONE folded bank, a run may resume
    on a DIFFERENT shard count (elastic reshard) — chunk sizes need not
    divide the shard count (inert-row padding).
    """
    from repro.core.multiball import fit_bank

    cs = jnp.atleast_1d(jnp.asarray(cs, jnp.float32))
    n_models = int(cs.shape[0])
    bank = resume.ball if resume is not None else None
    pos = resume.position if resume is not None else 0
    since_ckpt = 0

    for Xc, yc in iter(chunks):
        Xc = jnp.asarray(Xc)
        yc = jnp.asarray(yc)
        if yc.ndim == 1:
            yc = jnp.broadcast_to(yc[None, :], (n_models, yc.shape[0]))
        n_chunk = int(Xc.shape[0])
        bank = fit_bank(
            Xc, yc, cs, bank, variant=variant, block_n=block_n,
            b_tile=b_tile, stream_dtype=stream_dtype,
            bank_resident=bank_resident,
            mesh=mesh, shard_axis=shard_axis,
        )
        pos += n_chunk
        since_ckpt += n_chunk
        if checkpoint_every and checkpoint_cb and since_ckpt >= checkpoint_every:
            checkpoint_cb(
                StreamCheckpoint(ball=jax.tree.map(jnp.asarray, bank), position=pos)
            )
            since_ckpt = 0
    if bank is None:
        raise ValueError(
            "fit_chunked_many got an empty stream: the chunk iterator "
            f"yielded no examples for the {n_models}-model bank "
            f"(resume={resume!r}) — at least one (X, Y) chunk with one row "
            "is required to initialize the bank"
        )
    return StreamCheckpoint(ball=bank, position=pos)


# ---------------------------------------------------------------------------
# Readout
# ---------------------------------------------------------------------------


def decision_function(ball: Ball, X: jax.Array) -> jax.Array:
    return X @ ball.w


def predict(ball: Ball, X: jax.Array) -> jax.Array:
    return jnp.sign(decision_function(ball, X))


def accuracy(ball: Ball, X: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.mean((decision_function(ball, X) * y) > 0)

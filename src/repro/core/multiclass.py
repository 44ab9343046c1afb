"""One-vs-rest multiclass StreamSVM and hyper-parameter-grid fitting.

Classes and C-grid points are embarrassingly parallel *in math* but share the
same stream, so both wrappers flatten them onto the model axis of the tiled
bank engine (``fit_bank`` -> kernels.ops.streamsvm_fit_many): every
(block_n, D) tile is read from HBM once and updates all B models — bank
tiling (``b_tile``) keeps that true for hundreds of classes x a C-grid, and
``lookahead > 1`` runs the fused in-kernel Algorithm 2. ``mesh=`` shards the
stream over a device mesh (distributed.fit_bank_sharded).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .meb import Ball
from .multiball import fit_bank


def _cast_ball(ball: Ball, dtype) -> Ball:
    """Cast the bank to the stream's dtype (the kernel accumulates in f32)."""
    return Ball(
        w=ball.w.astype(dtype), r=ball.r.astype(dtype),
        xi2=ball.xi2.astype(dtype), m=ball.m,
    )


def ovr_signs(labels: jax.Array, n_classes: int, dtype=jnp.float32) -> jax.Array:
    """(N,) int labels -> (n_classes, N) one-vs-rest sign rows in {-1, +1}."""
    return jnp.where(
        labels[None, :] == jnp.arange(n_classes)[:, None], 1.0, -1.0
    ).astype(dtype)


@partial(
    jax.jit,
    static_argnames=(
        "n_classes", "lookahead", "variant", "b_tile", "stream_dtype",
        "bank_resident", "mesh", "shard_axis",
    ),
)
def fit_ovr(
    X: jax.Array,
    labels: jax.Array,
    n_classes: int,
    c,
    *,
    lookahead: int = 1,
    variant: str = "exact",
    b_tile: int | None = None,
    stream_dtype=None,
    bank_resident: str = "auto",
    mesh=None,
    shard_axis="data",
) -> Ball:
    """labels: (N,) int in [0, n_classes). Returns Ball stacked over classes.

    ``c`` is traced (sweeping C reuses one compilation). All classes flatten
    onto the bank axis of the tiled Pallas engine — including
    ``lookahead > 1``, which runs the fused in-kernel Algorithm 2 — so
    hundreds of classes train in ONE stream pass; ``b_tile`` bounds the
    per-step VMEM working set, ``stream_dtype="bf16"`` halves stream HBM
    traffic, and ``bank_resident="hbm"`` lifts the VMEM cap on the bank
    (classes x C-grid banks beyond VMEM scratch double-buffer through HBM —
    see kernels.ops).

    ``mesh=`` shards the stream over ``shard_axis`` of
    a device mesh and folds the per-shard banks with the Sec-4.3 merge:
    classes x shards in one pass of each shard's range (fit_bank_sharded).
    """
    if variant not in ("exact", "paper-listing"):
        raise ValueError(
            f"unknown variant {variant!r}; expected 'exact' or 'paper-listing'"
        )
    ys = ovr_signs(labels, n_classes, X.dtype)
    if lookahead <= 1:
        bank = fit_bank(
            X, ys, c, variant=variant, b_tile=b_tile,
            stream_dtype=stream_dtype, bank_resident=bank_resident,
            mesh=mesh, shard_axis=shard_axis,
        )
    else:
        bank = fit_bank(
            X, ys, c,
            variant="lookahead" if variant == "exact" else "lookahead-paper",
            lookahead=int(lookahead),
            b_tile=b_tile, stream_dtype=stream_dtype,
            bank_resident=bank_resident,
            mesh=mesh, shard_axis=shard_axis,
        )
    return _cast_ball(bank, X.dtype)


def predict_ovr(balls: Ball, X: jax.Array) -> jax.Array:
    """Direct jnp OVR readout: argmax margin over the bank's model axis.

    The serving fast path for this readout is kernels.ops.predict_bank /
    serve.BankServer (fused tiled kernel, bit-exact with this matmul in
    f32); this stays the one-liner oracle.
    """
    scores = X @ balls.w.T  # (N, K)
    return jnp.argmax(scores, axis=-1)


def predict_c_grid(balls: Ball, X: jax.Array, n_classes: int):
    """Per-C-grid-group OVR readout of a (G * n_classes)-model bank.

    ``balls`` is a stacked bank laid out class-major within each
    hyper-parameter group (model = g * n_classes + class — exactly what
    ``fit_ovr``/``fit_c_grid``/the quickstart's ``jnp.tile(signs, (G, 1))``
    produce). Returns ``((N, G) int32 predicted class, (N, G) f32 margin)``:
    each C-grid point's classifier answers independently, so one readout
    scores the whole grid. Direct jnp path — the fused serving twin is
    ``kernels.ops.predict_bank(..., epilogue="ovr")``, bit-exact in f32.
    """
    scores = X @ balls.w.T  # (N, B)
    b = scores.shape[1]
    if n_classes < 1 or b % n_classes:
        raise ValueError(
            f"n_classes must be >= 1 and divide the bank size: got "
            f"n_classes={n_classes}, B={b}"
        )
    grouped = scores.reshape(X.shape[0], b // n_classes, n_classes)
    return (
        jnp.argmax(grouped, axis=-1).astype(jnp.int32),
        jnp.max(grouped, axis=-1),
    )


@partial(
    jax.jit,
    static_argnames=(
        "variant", "b_tile", "stream_dtype", "bank_resident", "mesh",
        "shard_axis",
    ),
)
def fit_c_grid(
    X: jax.Array,
    y: jax.Array,
    c_grid: jax.Array,
    *,
    variant: str = "exact",
    b_tile: int | None = None,
    stream_dtype=None,
    bank_resident: str = "auto",
    mesh=None,
    shard_axis="data",
) -> Ball:
    """Model-selection sweep over a grid of C values in ONE stream pass.

    Every grid point is a model in the engine's bank (c enters only through
    1/C, so the grid can be traced). Returns Ball stacked over the grid.
    ``mesh=`` shards the stream over ``shard_axis`` and folds the per-shard
    grid banks with the Sec-4.3 merge.
    """
    c_grid = jnp.asarray(c_grid)
    b = c_grid.shape[0]
    Y = jnp.broadcast_to(y[None, :], (b, y.shape[0])).astype(X.dtype)
    return _cast_ball(
        fit_bank(
            X, Y, c_grid, variant=variant, b_tile=b_tile,
            stream_dtype=stream_dtype, bank_resident=bank_resident,
            mesh=mesh, shard_axis=shard_axis,
        ),
        X.dtype,
    )

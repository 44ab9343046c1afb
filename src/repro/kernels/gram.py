"""Pallas TPU kernel: tiled Gram / kernel-matrix blocks with epilogues.

K = A B^T tiled (bm, bn, bk) with an f32 VMEM accumulator; on the last
k-step an epilogue maps the accumulator to the kernel value:

  linear: K_ij = <a_i, b_j>
  rbf:    K_ij = exp(-gamma (|a_i|^2 + |b_j|^2 - 2 <a_i, b_j>))

Row norms are passed in (computed once by ops.py) so the RBF epilogue is a
fused elementwise transform. ``gamma`` is a TRACED (1, 1) operand staged
with a constant-index BlockSpec — a gamma sweep reuses one compilation
(the scalar-operand idiom of streamsvm_scan.py). Serves the kernelized
StreamSVM (Sec 4.2) and the lookahead QP; it is the MXU-shaped replacement
for the paper's per-element kernel evaluations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mosaic's default f32 matmul rounds its operands to bf16 (a relative error
# near 2**-9, seen on a v5e against the f32 host reference); every dot in
# this kernel asks for full f32, the precision its references compute in.
_F32 = jax.lax.Precision.HIGHEST


def _kernel(a_ref, b_ref, an_ref, bn_ref, g_ref, o_ref, acc_ref, *, epilogue: str):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (1,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32,
    )

    @pl.when(k_step == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[...]
        if epilogue == "rbf":
            gamma = g_ref[0, 0]
            d2 = an_ref[...] + bn_ref[...].T - 2.0 * acc
            o_ref[...] = jnp.exp(-gamma * jnp.maximum(d2, 0.0)).astype(o_ref.dtype)
        else:
            o_ref[...] = acc.astype(o_ref.dtype)


def gram_pallas(
    A: jax.Array,
    B: jax.Array,
    *,
    epilogue: str = "linear",
    gamma=1.0,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
):
    """K = epilogue(A B^T). A: (M, D), B: (N, D) — pre-padded by ops.py.

    ``gamma`` may be a python float or a traced scalar: it enters the grid
    as a (1, 1) f32 operand, so it never forces a recompile.
    """
    m, d = A.shape
    n, d2 = B.shape
    if d != d2 or m % bm or n % bn or d % bk:
        raise ValueError(
            f"gram_pallas needs pre-padded operands sharing the feature "
            f"axis with M % bm == 0, N % bn == 0, D % bk == 0: got "
            f"A.shape={A.shape}, B.shape={B.shape}, bm={bm}, bn={bn}, "
            f"bk={bk} (use kernels.ops.gram for arbitrary shapes)"
        )

    an = jnp.sum(A.astype(jnp.float32) ** 2, axis=1, keepdims=True)  # (M,1)
    bn_ = jnp.sum(B.astype(jnp.float32) ** 2, axis=1, keepdims=True)  # (N,1)
    g = jnp.reshape(jnp.asarray(gamma, jnp.float32), (1, 1))

    grid = (m // bm, n // bn, d // bk)
    return pl.pallas_call(
        functools.partial(_kernel, epilogue=epilogue),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="gram",
    )(A, B, an, bn_, g)

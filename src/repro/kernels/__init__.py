"""Pallas TPU kernels for the paper's compute hot-spots.

streamsvm_scan — the one-pass training engine: Algorithm 1 for a bank of
                 B models (one model is B = 1) on a 2-D data-major grid,
                 one stream pass for arbitrary B, with fused
                 Algorithm-2 lookahead windows, a bf16 stream-tile policy,
                 and two bank residencies sharing one compute core: VMEM
                 scratch, or HBM/ANY double-buffered through a 2-slot
                 async-copy ring (lifts the VMEM cap on B*D, bit-exact f32)
predict        — the serving twin: (Q, D) query tiles x (B, D) bank tiles on
                 the same data-major grid, with fused scores / per-C-grid-
                 group ovr-argmax / topk epilogues and the same HBM-resident
                 ring option for the bank
gram           — tiled kernel-matrix blocks (linear / RBF epilogues)

ops.py carries the jit'd public wrappers (padding, bank tiling, dtype
policy); ref.py the pure-jnp/numpy oracles. Kernels validate in
interpret=True mode on CPU and target TPU BlockSpec tiling (128-aligned
lanes, f32 VMEM accumulators).
"""
from .ops import (
    gram,
    predict_bank,
    predict_kernel_bank,
    streamsvm_fit_many,
)

__all__ = [
    "gram",
    "predict_bank",
    "predict_kernel_bank",
    "streamsvm_fit_many",
]

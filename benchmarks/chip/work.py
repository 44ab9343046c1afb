"""The work each kernel's algorithm needs, counted from unpadded shapes.

These counts are the algorithm's own, not the implementation's: lane and
row padding, the dense (B, N) sign matrix and the passes a full-f32 dot
takes on the MXU are all overhead, so a change that removes them shows up
as a higher roofline share and never as a changed count.
"""
from __future__ import annotations

F32 = 4
LABEL_BYTES = 4  # one int32 class id per stream row


def train_pass(n_rows: int, n_models: int, n_features: int,
               stream_bytes: int = F32) -> tuple[float, float]:
    """(ops, bytes) of one Algorithm-1 pass of a bank over ``n_rows`` rows.

    ops   = 2·N·B·D for the row-to-center products <w_b, y x_i>
          + 2·N·B·D for the center updates w_b <- (1 - s) w_b + s y x_i
    bytes = N·D·stream_bytes (the stream, read once at its dtype)
          + N·4            (one label per row)
          + 2·B·D·4        (the bank in and out once)
    """
    n, b, d = n_rows, n_models, n_features
    ops = 2.0 * n * b * d + 2.0 * n * b * d
    nbytes = float(n * d * stream_bytes + n * LABEL_BYTES + 2 * b * d * F32)
    return ops, nbytes


def serve(rows: int, steps: int, n_models: int, n_features: int,
          out_bytes: int, query_bytes: int = F32) -> tuple[float, float]:
    """(ops, bytes) of ``steps`` serving steps that answered ``rows`` rows.

    ops   = 2·rows·B·D
    bytes = rows·D·query_bytes (the answered rows, not the slot padding)
          + steps·B·D·4        (the bank, once per step)
          + rows·out_bytes     (each row's answer: k scores and k ids for a
                                top-k readout, 8·k bytes)
    """
    ops = 2.0 * rows * n_models * n_features
    nbytes = float(rows * n_features * query_bytes
                   + steps * n_models * n_features * F32 + rows * out_bytes)
    return ops, nbytes


def roofline_s(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it.

    The bf16 peak is the MXU's; an f32 dot can reach it at best.
    """
    t_ops = ops / peaks["bf16_flops"]
    t_bytes = nbytes / (peaks["hbm_gbps"] * 1e9)
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")

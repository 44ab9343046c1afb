"""Pallas TPU kernel: fused bank inference — (Q, D) queries x (B, D) bank.

The serving-side twin of the training engine (streamsvm_scan.py): the same
data-major 2-D grid ``(q_block, bank_tile)`` with the QUERY axis outer, so
each (q_block, D) query tile is DMA'd from HBM exactly once (its BlockSpec
index ignores the bank axis and Pallas elides the re-copy) and is revisited
by every (b_tile, D) slice of the bank. The trained bank is tiny — O(B * D),
the paper's constant-storage claim — so re-reading a bank tile per resident
query tile is the cheap term; the query firehose is the expensive one and it
is read ONCE per batch.

One MXU matmul per (i, j) step — (q_block, D) x (D, b_tile) margins — feeds a
fused epilogue selected statically:

  scores  raw margin matrix S[q, b] = <x_q, w_b>, written tile by tile
          (bit-exact with the jnp ``X @ W.T`` readout: same full-D
          contraction per element, no accumulation across grid steps).
  ovr     per-C-grid-group argmax: the bank is laid out class-major within
          each hyper-parameter group (model = g * n_classes + class, the
          fit_ovr/fit_c_grid flattening), groups are padded to whole bank
          tiles by ops.py, and each grid step emits the winning class id and
          its margin for the g_tile groups resident in the tile — the
          argmax never crosses a tile boundary.
  topk    running top-k (score, model-id) per query across bank tiles, kept
          in VMEM scratch like the training engine's ball state: each step
          merges the resident tile's b_tile candidates into the running k
          (static k selection steps of max + first-argmax + mask), and the
          last bank tile writes the sorted result.

Padded bank lanes (B -> b_tile multiple, classes -> nc_pad) are masked with a
large negative additive bias so no epilogue can select them; padded query
rows are sliced off by ops.py. Query tiles may be bf16 (ops.py's
``stream_dtype`` policy — halves the dominant HBM term); the bank, bias and
every epilogue accumulator stay f32.

Bank residency (``bank_resident``) mirrors the training engine's knob:

  "vmem"  bank tiles are BlockSpec-delivered — Pallas's automatic pipeline
          stages each (b_tile, D) slice into VMEM (the PR 4 layout).
  "hbm"   the bank stays in an HBM ref and the kernel streams
          (b_tile, D) slices through a 2-slot VMEM ring with
          ``pltpu.make_async_copy`` — the prefetch of grid step t+1's tile
          issued before compute on step t's slot, DMA semaphores in scratch.
          Read-only, so there is no write-back leg; the epilogue compute is
          shared op-for-op with "vmem" (bit-exact f32). This is the serving
          twin of the training engine's HBM-resident mode: a bank whose
          (B, D) footprint exceeds the VMEM budget serves without ever
          claiming VMEM residency for it, and ops.py's ``auto`` policy keeps
          train/serve residency decisions consistent.
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

# Large-but-finite lane mask: padded bank lanes carry this additive bias so
# every real margin beats them (finite so bias + margin never becomes NaN).
NEG_MASK = -3.0e38


def _first_argmax(vals: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(rows, lanes) -> per-row (max, first index achieving it).

    max/min/where/iota only — the Mosaic-friendly spelling of jnp.argmax
    (ties resolve to the lowest lane, matching jnp.argmax / lax.top_k).
    """
    best = jnp.max(vals, axis=1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    arg = jnp.min(
        jnp.where(vals == best[:, None], lanes, vals.shape[1]), axis=1
    )
    return best, arg


def _kernel(
    q_ref,  # (q_block, D) query tile (f32 or bf16)
    w_ref,  # (b_tile, D) bank tile (f32) — or the full HBM bank (hbm)
    bias_ref,  # (b_tile, 1) additive lane bias: 0 live, NEG_MASK padded
    *refs,  # epilogue outputs, then scratch (topk adds 2; hbm adds ring+sem)
    epilogue: str,
    b_tile: int,
    nc_pad: int | None,
    k: int | None,
    hbm: bool = False,
    n_q_blocks: int | None = None,
):
    j = pl.program_id(1)  # bank tile (inner — revisits the resident queries)
    n_btiles = pl.num_programs(1)

    if hbm:
        # HBM-resident bank: stream (b_tile, D) slices through a 2-slot VMEM
        # ring — prefetch of step t+1's tile issued before compute on step
        # t's slot. Read-only, so no write-back leg; with <= 2 bank tiles
        # each tile owns a slot and loads once, on the first query tile.
        ring, sem = refs[-2], refs[-1]
        refs = refs[:-2]
        i = pl.program_id(0)
        J = n_btiles
        t = i * J + j
        T = n_q_blocks * J

        def din(tt):
            tile = jax.lax.rem(tt, J)
            slot = jax.lax.rem(tt, 2) if J > 2 else tile
            return pltpu.make_async_copy(
                w_ref.at[pl.ds(tile * b_tile, b_tile), :],
                ring.at[slot],
                sem.at[slot],
            )

        if J <= 2:
            @pl.when(i == 0)
            def _load():
                d = din(t)
                d.start()
                d.wait()

            slot = j
        else:
            @pl.when(t == 0)
            def _warmup():
                din(0).start()

            @pl.when(t + 1 < T)
            def _prefetch():  # overlaps the matmul + epilogue below
                din(t + 1).start()

            din(t).wait()
            slot = jax.lax.rem(t, 2)
        w_tile = ring[slot]
    else:
        w_tile = w_ref[...]

    q = q_ref[...].astype(jnp.float32)  # bf16 query tiles upcast here
    s = jax.lax.dot_general(
        q, w_tile, (((1,), (1,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32,
    )  # (q_block, b_tile) margins

    if epilogue == "scores":
        # No bias: live lanes must stay bit-identical to X @ W.T (padded
        # lanes are sliced off by ops.py, so masking them is pointless).
        (out_ref,) = refs
        out_ref[...] = s
        return

    s = s + bias_ref[...][:, 0][None, :]

    if epilogue == "ovr":
        cls_ref, margin_ref = refs
        g_tile = b_tile // nc_pad
        cls_cols, margin_cols = [], []
        for g in range(g_tile):  # static: groups resident in this tile
            seg = s[:, g * nc_pad : (g + 1) * nc_pad]
            best, arg = _first_argmax(seg)
            cls_cols.append(arg)  # class lane == class id (padded lanes lose)
            margin_cols.append(best)
        cls_ref[...] = jnp.stack(cls_cols, axis=1)
        margin_ref[...] = jnp.stack(margin_cols, axis=1)
        return

    # ----- topk: running (score, model-id) top-k across bank tiles --------
    vals_out, ids_out, vals_ref, ids_ref = refs

    @pl.when(j == 0)
    def _reset():  # fresh query tile: forget the previous tile's ranking
        vals_ref[...] = jnp.full(vals_ref.shape, NEG_MASK, jnp.float32)
        ids_ref[...] = jnp.zeros(ids_ref.shape, jnp.int32)

    lane_ids = j * b_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    cand_v = jnp.concatenate([vals_ref[...], s], axis=1)  # (q_block, k+b_tile)
    cand_i = jnp.concatenate([ids_ref[...], lane_ids], axis=1)
    vals, ids = [], []
    for _ in range(k):  # static selection: max + first-argmax + mask
        best, pos = _first_argmax(cand_v)
        sel = (
            jax.lax.broadcasted_iota(jnp.int32, cand_v.shape, 1)
            == pos[:, None]
        )
        vals.append(best)
        ids.append(jnp.sum(jnp.where(sel, cand_i, 0), axis=1))  # one-hot pick
        cand_v = jnp.where(sel, NEG_MASK, cand_v)
    vals_ref[...] = jnp.stack(vals, axis=1)  # descending by construction
    ids_ref[...] = jnp.stack(ids, axis=1)

    @pl.when(j == n_btiles - 1)
    def _write():
        vals_out[...] = vals_ref[...]
        ids_out[...] = ids_ref[...]


def predict_bank_pallas(
    Q: jax.Array,
    W: jax.Array,
    bias: jax.Array,
    *,
    epilogue: str = "scores",
    q_block: int = 256,
    b_tile: int | None = None,
    nc_pad: int | None = None,
    k: int | None = None,
    bank_resident: str = "vmem",
    interpret: bool = False,
):
    """Score padded queries against a padded bank with a fused epilogue.

    Q: (Qn, D) query rows (f32 or bf16) — D padded to a multiple of 128 and
    Qn to a multiple of ``q_block`` by ops.py. W: (Bp, D) f32 bank, Bp a
    multiple of ``b_tile``. bias: (Bp, 1) f32 additive lane mask (0 for live
    model lanes, NEG_MASK for padding). Epilogues:

      "scores" -> (Qn, Bp) f32 margins
      "ovr"    -> ((Qn, Gp) int32 class ids, (Qn, Gp) f32 margins) where the
                  bank is packed as Gp groups of ``nc_pad`` class lanes and
                  ``b_tile`` is a whole number of groups (ops.py arranges
                  both), so every group's argmax completes inside one step
      "topk"   -> ((Qn, k) f32, (Qn, k) int32) per-query top-k model scores
                  and ids, descending (running VMEM scratch across tiles)

    ``bank_resident="hbm"`` keeps W in HBM and double-buffers
    (b_tile, D) slices through a 2-slot VMEM ring (see module docstring);
    bit-exact with the default BlockSpec-delivered layout.
    """
    if bank_resident not in ("vmem", "hbm"):
        raise ValueError(
            f"unknown bank_resident {bank_resident!r}; expected 'vmem' or "
            "'hbm' (ops.predict_bank resolves 'auto' before calling the "
            "kernel)"
        )
    qn, d = Q.shape
    bp, dw = W.shape
    if dw != d:
        raise ValueError(
            f"queries and bank must share the feature axis: got Q.shape="
            f"{Q.shape}, W.shape={W.shape}"
        )
    if bias.shape != (bp, 1):
        raise ValueError(
            f"bias must be (B, 1) matching the bank: got bias.shape="
            f"{bias.shape}, W.shape={W.shape}"
        )
    if qn % q_block != 0:
        raise ValueError(
            f"Q={qn} must be a multiple of q_block={q_block} (pad the "
            "queries; ops.predict_bank does this)"
        )
    if b_tile is None:
        b_tile = bp
    if bp % b_tile != 0:
        raise ValueError(
            f"B={bp} must be a multiple of b_tile={b_tile} (pad the bank; "
            "ops.predict_bank does this)"
        )
    if epilogue == "ovr":
        if nc_pad is None or b_tile % nc_pad != 0:
            raise ValueError(
                f"epilogue='ovr' needs nc_pad dividing b_tile: got "
                f"nc_pad={nc_pad}, b_tile={b_tile}"
            )
    elif epilogue == "topk":
        if k is None or k < 1:
            raise ValueError(f"epilogue='topk' needs k >= 1, got {k}")
    elif epilogue != "scores":
        raise ValueError(
            f"unknown epilogue {epilogue!r}; expected 'scores', 'ovr' or "
            "'topk'"
        )

    grid = (qn // q_block, bp // b_tile)
    hbm = bank_resident == "hbm"
    in_specs = [
        # query tile index ignores j -> DMA'd once, resident across the bank
        pl.BlockSpec((q_block, d), lambda i, j: (i, 0)),
        # hbm: the bank never enters the BlockSpec pipeline — the kernel
        # rings (b_tile, D) slices out of HBM itself
        pl.BlockSpec(memory_space=pltpu.HBM)
        if hbm
        else pl.BlockSpec((b_tile, d), lambda i, j: (j, 0)),
        pl.BlockSpec((b_tile, 1), lambda i, j: (j, 0)),
    ]
    scratch = []
    # A (q_block, cols) output tile that is a lane window of the full
    # (Qn, bp) result must be 128 lanes wide for Mosaic. Narrower tiles get
    # one (Qn, cols) output plane per bank tile instead, whose whole last
    # dim the block then spans (legal for any width); the planes are
    # interleaved back into (Qn, bp) order below.
    n_planes = grid[1]
    planes = n_planes > 1 and epilogue != "topk" and (
        epilogue == "ovr" or b_tile % 128 != 0
    )
    cols = b_tile // nc_pad if epilogue == "ovr" else b_tile

    def out_tile(dtype):
        if planes:
            return (
                pl.BlockSpec((pl.Squeezed(), q_block, cols),
                             lambda i, j: (j, i, 0)),
                jax.ShapeDtypeStruct((n_planes, qn, cols), dtype),
            )
        return (
            pl.BlockSpec((q_block, cols), lambda i, j: (i, j)),
            jax.ShapeDtypeStruct((qn, n_planes * cols), dtype),
        )

    if epilogue == "scores":
        out_specs, out_shape = zip(out_tile(jnp.float32))
    elif epilogue == "ovr":
        out_specs, out_shape = zip(out_tile(jnp.int32), out_tile(jnp.float32))
    else:  # topk: outputs parked at tile 0, written on the last bank tile
        out_specs = [
            pl.BlockSpec((q_block, k), lambda i, j: (i, 0)),
            pl.BlockSpec((q_block, k), lambda i, j: (i, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((qn, k), jnp.float32),
            jax.ShapeDtypeStruct((qn, k), jnp.int32),
        ]
        scratch = [
            pltpu.VMEM((q_block, k), jnp.float32),
            pltpu.VMEM((q_block, k), jnp.int32),
        ]

    if hbm:
        scratch = scratch + [
            pltpu.VMEM((2, b_tile, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    outs = pl.pallas_call(
        functools.partial(
            _kernel, epilogue=epilogue, b_tile=b_tile, nc_pad=nc_pad, k=k,
            hbm=hbm, n_q_blocks=grid[0],
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="predict_bank",
    )(Q, W.astype(jnp.float32), bias.astype(jnp.float32))
    if planes:
        outs = [o.transpose(1, 0, 2).reshape(qn, n_planes * cols) for o in outs]
    return outs[0] if epilogue == "scores" else tuple(outs)

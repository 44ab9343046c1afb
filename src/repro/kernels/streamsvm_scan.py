"""Pallas TPU kernel: the one-pass StreamSVM bank engine.

TPU adaptation of Algorithm 1 (DESIGN.md §3) for a BANK of B independent
models (``_kernel_many`` / ``streamsvm_scan_many_pallas``; a single model
is a bank of one). The grid is 2-D, ``(n_block, bank_tile)``, in DATA-MAJOR
order: the data-block axis is outer and the bank-tile axis inner, so each
(block_n, D) stream tile is fetched from HBM exactly once (its BlockSpec
index ignores the bank axis, so Pallas elides the re-copy across the inner
iterations) and is revisited by every (b_tile, D) tile of the bank. Per
(i, j) step:

  1. the unsigned block Gram G = X X^T (and Algorithm 1's band of it)
     depends on the block alone: the block's first step (j = 0) fills it
     into scratch on the MXU and the other bank tiles of the block read it
     there; the tile's inner products g = <w, x_k> are one matmul;
  2. a fori_loop over the block's rows runs the inherently-sequential
     conditional updates, vectorized across the b_tile models (one model
     per sublane row; per-model label signs re-applied as rank-1 factors),
     maintaining g_k for k > j with rank-1 corrections from G (O(block_n)
     per row);
  3. the bank tile is updated once per block via accumulated (decay,
     alpha) coefficients — a single (b_tile, block_n) x (block_n, D)
     matmul.

B models cost ONE pass of data movement, for arbitrary B, and the result
equals the reference scan's (tests sweep shapes/dtypes against ref.py).

Mosaic lowers no dynamic slice of a value, so the row loop never indexes a
value at the traced row: the Gram row is a sublane read of a VMEM scratch
ref, a stream row a sublane read of the stream tile's ref, and the per-model
columns g[:, j] / y[:, j] are one-hot lane sums (exact in f32). Per-model
scalars live in (rows, 128) slabs, one model per sublane row, so a bank tile
of any multiple of 8 models is one aligned slab in VMEM and one aligned DMA.

A lane sum is a round trip through the cross-lane unit, about 100 cycles
on a v5e, against a chain of about 50 per row (d^2, an exact sqrt and
divide, s). So the Algorithm-1 loop keeps those sums off the chain:
it runs ``_rows_per_step`` rows a step, lane-sums the NEXT step's columns
of g and y at the start of a step, and brings them up to date row by row
in (b_tile, 128) arithmetic with the very operations the full-width update
of g applies to that column — so every value, and the result, is the same
bit for bit. The Gram entries those updates need, G[j, j + t] for t below
two steps, are laid out once a block as a lane-replicated band
(``band_ref``), read by sublane. The per-model columns are lane-replicated
(b_tile, 128) values, so no value needs a lane shift or a broadcast inside
the loop.

The fused Algorithm-2 variant (``lookahead`` is not None) defers acceptance:
violating rows are pushed into a per-model L-row window (slot-major
(L, b_tile, D), staged with the bank tile) and only when a model's window
fills is it flushed — repeatedly absorbing the FARTHEST buffered point (the
paper's farthest-point lookahead; greedy Badoiu-Clarkson insertion over the
window) and dropping buffered points the grown ball now encloses. Per-model
L rides the parameter tile; windows persist across block AND tile
boundaries, with a final partial flush on the last grid step (same
boundary-flush semantics as fit_chunked).

Stream tiles may be bf16 (``X``/``Y`` dtype is whatever the caller DMAs in —
see ops.py's ``stream_dtype`` policy; a stream read in place is f32); the
bank, scalar state, and every accumulator stay f32.

The bank engine reads the caller's stream and signs in place: its blocks
start at row 1 of an array whose row 0 seeded the state, its last row block
and last bank tile may be ragged, and rows past the live count and models
past Y's rows read 0.0 — the values a zero-padded copy would hold, so the
results are that copy's, bit for bit (``_kernel_many``; ops.py copies a
stream it must pad or cast anyway, and drops the seed row in that copy).

Bank residency (``bank_resident``): the bank, its state slabs and the
lookahead windows live in HBM buffers (aliased pallas_call inputs->outputs,
so the update is in place; pinned to HBM, since in ANY space the chip's
compiler may place small ones in VMEM, outside the kernel's own VMEM
accounting) and ONE kernel stages bank tiles
in VMEM slots with ``pltpu.make_async_copy`` — the two layouts differ only
in the slot count, so they are bit-exact in f32 by construction:

  "vmem"  one slot per bank tile: each tile loads on the first data block,
          stays in VMEM for the whole pass and writes back after the last.
          Fast, but B*D is capped by VMEM.
  "hbm"   two slots: the prefetch of grid step t+1's tile into slot
          (t+1) % 2 is issued BEFORE compute on step t's slot t % 2, and the
          updated tile is written back async — its wait deferred to step
          t+1 — so both DMA directions overlap the MXU work of the step.
          Correctness of the ring: every step t >= 1 first waits the
          writeback issued at t-1, so by the time step t prefetches tile
          (t+1) % J, the last writeback of that tile (issued at step
          t+1-J <= t-1) has already been waited — no RAW through HBM, and
          the slot being prefetched into is never still draining (WAR).
          With J = B/b_tile <= 2 tiles there is nothing to cycle, and the
          data movement is the VMEM-resident one.

ops.py's ``auto`` policy picks the residency from a per-step VMEM byte
model; the per-step VMEM working set in "hbm" mode is O(2 slots + stream
tile) no matter how large B*D grows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mosaic's default f32 matmul rounds its operands to bf16 (a relative error
# near 2**-9, seen on a v5e against the f32 host reference); every dot in
# this kernel asks for full f32, the precision its references compute in.
_F32 = jax.lax.Precision.HIGHEST

#: Lanes of D per dot. Mosaic's full-f32 matmul splits both operands into
#: bf16 parts held in VMEM temporaries (on v5e about 3x a D = 4096 stream
#: tile); contracting or producing D in chunks bounds those by a
#: (rows, D_CHUNK) operand whatever D is.
D_CHUNK = 512


def _d_chunks(d: int):
    return [slice(c, min(c + D_CHUNK, d)) for c in range(0, d, D_CHUNK)]


def _dot_nt(a_at, b_at, d: int):
    """``a @ b.T`` in f32, contracted over D in chunks: ``a_at(c)`` and
    ``b_at(c)`` give the operands' columns ``c`` (ref reads or static value
    slices)."""
    acc = None
    for c in _d_chunks(d):
        p = jax.lax.dot_general(
            a_at(c), b_at(c), (((1,), (1,)), ((), ())),
            precision=_F32, preferred_element_type=jnp.float32,
        )
        acc = p if acc is None else acc + p
    return acc


def _rows_per_step(block_n: int) -> int:
    """Rows of a block the Algorithm-1 loop runs per step: two (one for an
    odd block_n). A step reads the next step's g columns by cross-lane sums
    at its start (module docstring), so their round trip overlaps the
    step's own rows. Each further row a step carries two more columns per
    model, which spill: on a v5e four rows a step ran slower than two both
    for a one-tile bank of 24 models and for tiles of 256 (PERF.md).
    """
    return math.gcd(2, block_n)


# Per-model state arrays are (rows, 128) slabs: one model per sublane row,
# its scalars in the first lanes. A whole-lane-tile row is what lets a tile of
# models be one 8-aligned sublane slab in VMEM and one aligned DMA from HBM
# (Mosaic refuses lane slices narrower than the 128-lane tiling).
STATE_LANES = 128


def _state_slab(r, xi2, wsq):
    """Pack three (b_tile, 1) f32 columns into the (b_tile, 128) state slab
    [r, xi2, wsq, 0, ...] by lane selects (no lane-offset stores)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (r.shape[0], STATE_LANES), 1)
    out = jnp.where(lane == 0, r, 0.0)
    out = jnp.where(lane == 1, xi2, out)
    return jnp.where(lane == 2, wsq, out)


def _count_slab(c):
    """A (b_tile, 1) int32 count column as its (b_tile, 128) state slab."""
    return jnp.broadcast_to(c, (c.shape[0], STATE_LANES))


def _bank_flush(w_ref, r, xi2, g, cnt, buf, fmask, x_at, ys, c_inv, gain):
    """Farthest-first flush of the lookahead buffers of the masked models.

    Vectorized over the b_tile model rows: up to L_max greedy steps, each
    absorbing the farthest still-buffered point of every flushing model (the
    Algorithm-1 update), dropping the whole remaining window as soon as its
    farthest point is already enclosed. ``buf`` is the (L_max, b_tile, D)
    window slab (slot-major, so every per-slot reduction is across whole
    vregs); the centers are updated in place in ``w_ref``. ``g`` (the
    maintained <w, y x_k> for the rest of the current block) picks up a
    rank-1 correction per absorb via one (b_tile, D) x (D, block_n) matmul
    against the stream tile (``x_at(c)``: its columns ``c`` in f32).
    Returns the updated (r, xi2, g, cnt) (m is counted at buffer-push time,
    not here).
    """
    l_max, bt, _ = buf.shape
    slot = jax.lax.broadcasted_iota(jnp.int32, (l_max, bt, 1), 0)
    # The still-buffered mask rides the loop as 0/1 f32: Mosaic loops carry
    # no boolean vectors.
    remain = jnp.where(
        jnp.logical_and(slot < cnt[None], fmask[None]), 1.0, 0.0
    )

    def fstep(_, carry):
        r, xi2, g, remain_f = carry
        remain = remain_f > 0.0
        w = w_ref[...]
        bd2 = (
            jnp.sum((w[None] - buf) ** 2, axis=-1, keepdims=True)
            + xi2[None]
            + c_inv[None]
        )  # (L, bt, 1)
        bd = jnp.sqrt(jnp.maximum(bd2, 1e-12))
        bdm = jnp.where(remain, bd, -jnp.inf)
        dfar = jnp.max(bdm, axis=0)  # (bt, 1)
        # first slot achieving the max (jnp.argmax's tie rule)
        far = jnp.min(jnp.where(bdm == dfar[None], slot, l_max), axis=0)
        has = jnp.max(jnp.where(remain, 1.0, 0.0), axis=0) > 0.0
        act = jnp.logical_and(has, dfar >= r)  # absorb only live violators
        s = jnp.where(act, 0.5 * (1.0 - r / jnp.where(act, dfar, 1.0)), 0.0)
        one_s = 1.0 - s
        sel = slot == far[None]
        pfar = jnp.sum(jnp.where(jnp.logical_and(sel, remain), buf, 0.0), axis=0)
        w_ref[...] = one_s * w + s * pfar
        r = jnp.where(act, r + 0.5 * (dfar - r), r)
        xi2 = xi2 * one_s**2 + s**2 * gain
        # <w', y_bk x_k> = (1-s) g + s y_bk <pfar, x_k>
        pg = _dot_nt(lambda c: pfar[:, c], x_at, pfar.shape[1])  # (bt, bn)
        g = one_s * g + s * (ys * pg)
        # remove the absorbed slot; if the farthest point was enclosed, every
        # remaining buffered point is too — drop the whole window.
        drop_all = jnp.logical_and(has, jnp.logical_not(act))
        remain = jnp.logical_and(
            remain, jnp.logical_not(jnp.logical_and(sel, act[None]))
        )
        remain = jnp.logical_and(remain, jnp.logical_not(drop_all[None]))
        return r, xi2, g, jnp.where(remain, 1.0, 0.0)

    r, xi2, g, _ = jax.lax.fori_loop(0, l_max, fstep, (r, xi2, g, remain))
    cnt = jnp.where(fmask, 0, cnt)
    return r, xi2, g, cnt


def _block_update(
    x_at,  # c -> (block_n, |c|) f32: the block's stream rows, columns c
    x_row,  # jr -> (1, D) f32: stream row jr of the block (Algorithm 2)
    ys_ref,  # (b_tile, block_n) f32 per-model label-sign tile
    w_ref,  # (b_tile, D) f32 ref view: the resident bank tile, updated here
    gram_ref,  # (block_n, block_n) f32 VMEM scratch for the block Gram
    band_ref,  # (2 * _rows_per_step(block_n), block_n, 128) f32 (or None)
    fill_gram,  # traced bool (or True): the block's first visit fills both
    r, xi2, wsq,  # (b_tile, 1) f32 per-model scalars
    m,  # (b_tile, 1) int32 core-vector counts
    cnt,  # (b_tile, 1) int32 lookahead fill counts (None for Algorithm 1)
    buf_ref,  # (L_max, b_tile, D) f32 ref view of the windows (or None)
    c_inv,  # (b_tile, 1) f32
    gain,  # (b_tile, 1) f32 slack gain
    l_arr,  # (b_tile, 1) int32 per-model L (None for Algorithm 1)
    row0,  # traced int: stream index of the block's first row
    n_valid,  # traced int: rows >= n_valid are past the live stream
    is_last_block,  # traced bool: final data block (lookahead boundary flush)
    *,
    block_n: int,
    b_tile: int,
    lookahead_max: int | None,
):
    """One (stream block x bank tile) update — the residency-agnostic core.

    Both residencies run it on a bank tile staged in a VMEM slot, which is
    what makes them bit-exact in f32: only how long the tile stays in its
    slot differs, never the arithmetic applied to it. Per-model scalars
    come in as (b_tile, 1) columns, one model per sublane row like the bank
    tile (Algorithm 1 widens them to lane-replicated (b_tile, 128)).
    The row loop reads row jr without dynamic value slicing (which Mosaic
    does not lower): the Gram row is a sublane read of ``gram_ref``, stream
    row jr a sublane read (``x_row``), and the (b_tile,) columns ``g[:, jr]``
    and ``ys[:, jr]`` are one-hot lane sums against the column iota — exact
    in f32, since every other term is 0.0. Writes the new centers into
    ``w_ref`` and returns ``(r, xi2, wsq, m, cnt)`` (cnt None for
    Algorithm 1). The stream tile and the bank tile are re-read from their
    refs at each use (``x_at`` reads a D chunk) rather than held as values
    across the row loop, which would make Mosaic keep a (block_n, D) and a
    (b_tile, D) copy in VMEM; so are the signs, from ``ys_ref``.

    The Gram (and Algorithm 1's band of it) is the block's alone, the same
    for every bank tile: it is computed into ``gram_ref`` (and ``band_ref``)
    only where ``fill_gram`` holds, the block's first visit, and every
    later visit of the block reads what that one wrote.

    Algorithm 1's loop runs ``_rows_per_step`` rows a step and carries each
    row's g column and signs into the step that uses it: at the start
    of a step it lane-sums the next step's columns out of the g the step
    starts with, and each row of the step corrects them with its own update,
    ``gk = one_s * gk + (s * y_j) * (y_k * G[j, k])``: the operations, in
    order, that the full-width ``g`` update applies to column k. G[j, k]
    and G_jj are sublane reads of ``band_ref`` (t, k) = G[k, k + t], filled
    with the Gram, once a block, from ``gram_ref``. The chain from a row
    to the next (d^2, sqrt, divide, s, that correction) thus holds no lane
    sum; the full-width update of g, which only feeds the column sums a
    step later, and that of alpha leave it. On a block's last step the
    columns read ahead lie past the block: their one-hot masks are all
    false and the band reads are clamped to the block, and no row uses
    them.
    """
    d = w_ref.shape[1]

    # One block Gram of the *unsigned* rows, shared by every model (signs are
    # re-applied per model as rank-1 outer factors) and by every bank tile,
    # plus the tile/block inner products — the only O(D) work in the block,
    # all MXU.
    @pl.when(fill_gram)
    def _fill_gram():
        gram_ref[...] = _dot_nt(x_at, x_at, d)  # (block_n, block_n)

    h0 = _dot_nt(lambda c: w_ref[:, c], x_at, d)  # (b_tile, block_n): <w_b, x_k>

    if lookahead_max is None:
        # ----- Algorithm 1: immediate greedy acceptance (bit-exact with the
        # single-tile path — identical per-row arithmetic). -----
        u = _rows_per_step(block_n)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
        reps = -(-block_n // STATE_LANES)

        def rep(v):  # (rows, 1) -> (rows, 128): the column in every lane
            return jnp.broadcast_to(v, (v.shape[0], STATE_LANES))

        def wide(v):  # (rows, 128) lane-replicated -> (rows, block_n)
            v = jnp.concatenate([v] * reps, axis=1) if reps > 1 else v
            return v[:, :block_n]

        def lane_sum(mask, v):  # exact: every other term is 0.0
            return rep(jnp.sum(jnp.where(mask, v, 0.0), axis=1, keepdims=True))

        ys_at = lambda: ys_ref[...].astype(jnp.float32)
        # The Gram's band, lane-replicated: band_ref[t, k] = G[k, k + t]
        # (0.0 past the block), so a row reads its entries by sublane.
        @pl.when(fill_gram)
        def _fill_band():
            shape = (block_n, block_n)
            g_rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            g_cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            for t in range(band_ref.shape[0]):
                band_ref[t] = lane_sum(g_cols == g_rows + t, gram_ref[...])

        band = lambda t, k: band_ref[
            t, pl.ds(jnp.minimum(k, block_n - 1), 1), :
        ]

        def read_ahead(g, k):
            """Row k's g column and signs (unused for k >= block_n)."""
            hit = lanes == k
            return lane_sum(hit, g), lane_sum(hit, ys_at())

        def body(i, carry):
            g, alpha, decay, r, xi2, wsq, m, cols = carry
            j0 = i * u
            cols = list(cols)
            # The next step's columns, read before this step's rows update g:
            # each row below brings them up to date in (b_tile, 128) columns.
            ahead = [read_ahead(g, j0 + u + t) for t in range(u)]
            ys = ys_at()
            for a in range(u):
                jr = j0 + a
                grow = gram_ref[pl.ds(jr, 1), :]  # (1, block_n)
                gj, yj = cols[a]
                gjj = band(0, jr)  # G[j, j]
                # Inert per model: rows past n_valid, and rows whose sign is
                # 0 for that model (a caller's stream padding; padded bank
                # rows).
                live = jnp.logical_and(yj != 0.0, row0 + jr < n_valid)
                d2 = wsq - 2.0 * gj + gjj + xi2 + c_inv
                d = jnp.sqrt(jnp.maximum(d2, 1e-12))
                upd = jnp.logical_and(d >= r, live)
                s = jnp.where(upd, 0.5 * (1.0 - r / d), 0.0)
                one_s = 1.0 - s
                sy = s * yj

                def correct(col, k):
                    """This row's update of column k of g, as the full-width
                    update below computes it."""
                    gk, yk = col
                    g_jk = band(k - jr, jr)  # G[j, k]
                    return one_s * gk + sy * (yk * g_jk), yk

                cols[a + 1:] = [
                    correct(c, jr + 1 + t) for t, c in enumerate(cols[a + 1:])
                ]
                ahead = [correct(c, j0 + u + t) for t, c in enumerate(ahead)]
                # rank-1 maintenance of g under w_b <- (1-s_b) w_b + s_b y_bj
                # x_j: <x_j, y_bk x_k> = y_bk G[j, k]
                g = wide(one_s) * g + wide(sy) * (ys * grow)
                # Deferred bank update: w_end = decay * w_start + sum_j
                # alpha_j y_bj x_j with alpha_j = s_j * prod_{k>j} (1 - s_k)
                # — applied post-loop as ONE (b_tile, block_n) x (block_n, D)
                # matmul.
                alpha = wide(one_s) * alpha + jnp.where(
                    lanes == jr, wide(s), 0.0
                )
                decay = decay * one_s
                wsq = one_s**2 * wsq + 2.0 * s * one_s * gj + s**2 * gjj
                r = jnp.where(upd, r + 0.5 * (d - r), r)
                xi2 = xi2 * one_s**2 + s**2 * gain
                m = m + upd.astype(jnp.int32)
            return g, alpha, decay, r, xi2, wsq, m, tuple(ahead)

        g0 = ys_at() * h0  # g[b, k] = <w_b, y_bk x_k>
        c_inv, gain = rep(c_inv), rep(gain)
        init = (
            g0, jnp.zeros_like(g0), jnp.ones((b_tile, STATE_LANES), jnp.float32),
            rep(r), rep(xi2), rep(wsq), rep(m),
            tuple(read_ahead(g0, t) for t in range(u)),
        )
        g, alpha, decay, r, xi2, wsq, m, _ = jax.lax.fori_loop(
            0, block_n // u, body, init
        )
        coef = alpha * ys_at()
        for c in _d_chunks(d):
            w_ref[:, c] = decay[:, :1] * w_ref[:, c] + jax.lax.dot_general(
                coef, x_at(c), (((1,), (0,)), ((), ())),
                precision=_F32, preferred_element_type=jnp.float32,
            )
        return r[:, :1], xi2[:, :1], wsq[:, :1], m[:, :1], None

    # ----- Algorithm 2: deferred acceptance through per-model L-row
    # lookahead windows, flushed farthest-point-first. -----
    ys = ys_ref[...].astype(jnp.float32)
    g0 = ys * h0  # g[b, k] = <w_b, y_bk x_k>
    col_ids = jax.lax.broadcasted_iota(jnp.int32, ys.shape, 1)  # (b_tile, block_n)

    def read_row(jr, g):
        """Row jr: its one-hot lane mask, Gram row, G_jj, g[:, jr], y[:, jr],
        and which models it is live for. Sign-0 inertness is PER MODEL ROW:
        a row whose sign is 0 for model b never violates model b (the
        stream-padding contract, and what keeps padded *bank* rows from
        absorbing anything)."""
        hit = col_ids == jr
        grow = gram_ref[pl.ds(jr, 1), :]  # (1, block_n)
        gjj = jnp.sum(
            jnp.where(hit[:1], grow, 0.0), axis=1, keepdims=True
        )  # (1, 1)
        gj = jnp.sum(jnp.where(hit, g, 0.0), axis=1, keepdims=True)
        yj = jnp.sum(jnp.where(hit, ys, 0.0), axis=1, keepdims=True)
        live = jnp.logical_and(yj != 0.0, row0 + jr < n_valid)  # (b_tile, 1)
        return hit, grow, gjj, gj, yj, live

    slot = jax.lax.broadcasted_iota(jnp.int32, (lookahead_max, b_tile, 1), 0)

    def flush(fmask, g, r, xi2, wsq, cnt):
        r, xi2, g, cnt = _bank_flush(
            w_ref, r, xi2, g, cnt, buf_ref[...], fmask, x_at, ys, c_inv,
            gain,
        )
        w = w_ref[...]
        # w only changes here, so |w|^2 only needs refreshing here
        return g, r, xi2, jnp.sum(w * w, axis=1, keepdims=True), cnt

    def any_(mask):
        return jnp.max(jnp.where(mask, 1.0, 0.0)) > 0.0

    def body(jr, carry):
        g, r, xi2, wsq, m, cnt = carry
        _, _, gjj, gj, yj, live = read_row(jr, g)
        d2 = wsq - 2.0 * gj + gjj + xi2 + c_inv
        d = jnp.sqrt(jnp.maximum(d2, 1e-12))
        violate = jnp.logical_and(d >= r, live)
        # push the signed row into each violated model's window
        p = yj * x_row(jr)  # (b_tile, D)
        put = jnp.logical_and(violate[None], slot == cnt[None])
        buf_ref[...] = jnp.where(put, p[None], buf_ref[...])
        cnt = cnt + violate.astype(jnp.int32)
        m = m + violate.astype(jnp.int32)  # counted at push (QP parity)
        full = cnt >= l_arr
        g, r, xi2, wsq, cnt = jax.lax.cond(
            any_(full),
            functools.partial(flush, full),
            lambda *a: a,
            g, r, xi2, wsq, cnt,
        )
        return g, r, xi2, wsq, m, cnt

    g, r, xi2, wsq, m, cnt = jax.lax.fori_loop(
        0, block_n, body, (g0, r, xi2, wsq, m, cnt)
    )

    # Final partial flush on the last data block (paper lines 12-14 /
    # fit_chunked's boundary-flush semantics).
    pending = cnt > 0
    g, r, xi2, wsq, cnt = jax.lax.cond(
        jnp.logical_and(is_last_block, any_(pending)),
        functools.partial(flush, pending),
        lambda *a: a,
        g, r, xi2, wsq, cnt,
    )
    return r, xi2, wsq, m, cnt


def _kernel_many(
    *refs,  # inputs, aliased HBM inputs, HBM outputs, VMEM scratch
    block_n: int,
    b_tile: int,
    lookahead_max: int | None,
    n_blocks: int,
    n_btiles: int,
    n_slots: int,
    in_place: bool,
    skip: int,
    n_models: int,
):
    """The bank engine: state in HBM, tiles staged in VMEM slots.

    ``refs`` unpacks as the inputs: the stream tile x (block_n, D) of X's
    rows block_n * i .., with ``skip`` the tile xn (rows, D) that holds the
    first row of the next one; the sign tile (b_tile, block_n) of Y, with
    ``skip`` the tile (b_tile, 128) that holds the next block's first
    column; the (b_tile, 3) parameters [c_inv, gain, L] and the (1, 1) count
    of live rows. Then ``n_arrays`` aliased input refs (unused — the
    aliased OUTPUT refs address the same buffers and carry the initial
    state), then ``n_arrays`` HBM output refs [bank (B, D) f32,
    st (B, 128) f32 slabs (r, xi2, wsq, 0, ...), m (B, 128) i32, and with
    lookahead cnt (B, 128) i32 + buf (L_max, B, D) f32], then ``n_arrays``
    VMEM slot buffers with a leading ``n_slots`` axis, then one
    DMA-semaphore array of shape (n_arrays, 2, 2) = (array, in/out, slot),
    then the (block_n, block_n) Gram scratch, for Algorithm 1 the
    (2 * _rows_per_step(block_n), block_n, 128) Gram band, and ``in_place``
    last the (b_tile, block_n) f32 scratch of the block's signs. Every tile
    is a sublane slab (rows tile*b_tile ...), so each DMA is 8-aligned.
    The Gram and its band are filled on step (i, 0) and read by steps
    (i, 1 ..) of the same block; with one bank tile every step fills them.

    ``in_place``: the stream and the signs are the caller's arrays, read in
    place, and the block is rows ``skip + block_n * i ..`` of them. With
    ``skip`` (row 0 seeded the state) the block starts one row into its
    tiles, so each D chunk of X is rolled up one sublane and its last row
    taken from the next tile, and the signs one lane. Rows at or past the
    live count and models at or past ``n_models`` read 0.0 (as a
    zero-padded copy would hold), so whatever a ragged tile holds beyond the
    arrays, even a NaN, never reaches the arithmetic. Otherwise X and Y are
    such a copy already, and are read as they are.

    ``n_slots == n_btiles`` is the VMEM-resident layout: every tile owns a
    slot, loads on the first data block and writes back after the last.
    ``n_slots == 2 < n_btiles`` is the HBM ring: grid step
    t = i * n_btiles + j works on slot t % 2, prefetching step t+1's tile
    before compute on step t and writing step t's tile back async, waited at
    t+1 (hazard argument in the module docstring).
    """
    x_ref, refs = refs[0], refs[1:]
    xn_ref, refs = (refs[0], refs[1:]) if skip else (None, refs)
    ys_ref, refs = refs[0], refs[1:]
    yn_ref, refs = (refs[0], refs[1:]) if skip else (None, refs)
    p_ref, nv_ref, refs = refs[0], refs[1], refs[2:]
    n_arrays = 3 if lookahead_max is None else 5
    hbm = refs[n_arrays : 2 * n_arrays]  # aliased outputs == the live state
    slots = refs[2 * n_arrays : 3 * n_arrays]
    sems = refs[3 * n_arrays]
    gram_ref = refs[3 * n_arrays + 1]
    band_ref = refs[3 * n_arrays + 2] if lookahead_max is None else None

    i = pl.program_id(0)
    j = pl.program_id(1)
    J = n_btiles
    T = n_blocks * J
    t = i * J + j
    cycling = n_slots < J

    def _dmas(tt, direction):
        """The slot transfers of grid step tt (0 = HBM->VMEM, 1 = back).

        Reconstructing the same (src, dst, semaphore) triple is how a copy
        started at one grid step is waited at a later one.
        """
        tile = jax.lax.rem(tt, J)
        slot = jax.lax.rem(tt, 2) if cycling else tile
        rows = pl.ds(pl.multiple_of(tile * b_tile, 8), b_tile)
        srcs = [a.at[rows] for a in hbm[:4]]
        if lookahead_max is not None:
            srcs.append(hbm[4].at[:, rows])
        out = []
        for a, (hslice, buf) in enumerate(zip(srcs, slots)):
            pair = (hslice, buf.at[slot])
            src, dst = pair if direction == 0 else pair[::-1]
            sem = sems.at[a, direction, jax.lax.rem(slot, 2)]
            out.append(pltpu.make_async_copy(src, dst, sem))
        return out

    start_in = lambda tt: [d.start() for d in _dmas(tt, 0)]
    wait_in = lambda tt: [d.wait() for d in _dmas(tt, 0)]
    start_out = lambda tt: [d.start() for d in _dmas(tt, 1)]
    wait_out = lambda tt: [d.wait() for d in _dmas(tt, 1)]

    if not cycling:
        @pl.when(i == 0)
        def _load():
            start_in(t)
            wait_in(t)

        slot = j
    else:
        @pl.when(t == 0)
        def _warmup():
            start_in(0)

        @pl.when(t >= 1)
        def _drain_writeback():  # the async write-back issued at step t-1
            wait_out(t - 1)

        @pl.when(t + 1 < T)
        def _prefetch():  # overlap tile t+1's fetch with compute on tile t
            start_in(t + 1)

        wait_in(t)
        slot = jax.lax.rem(t, 2)

    bank, st_slots, m_slots = slots[0], slots[1], slots[2]

    @pl.when(i == 0)
    def _init_wsq():  # first visit: |w_b|^2 from the seeded centers
        st = st_slots[slot]
        st_slots[slot] = _state_slab(
            st[:, 0:1], st[:, 1:2],
            jnp.sum(bank[slot] ** 2, axis=1, keepdims=True),
        )

    # The block's rows and signs, realigned and masked (docstring above).
    row0 = i * block_n
    n_valid = nv_ref[0, 0]
    live = n_valid - row0  # live rows of this block (may be < 1)

    def x_at(c):
        x = x_ref[:, c].astype(jnp.float32)
        if not in_place:
            return x
        rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        if skip:
            nxt = xn_ref[:, c].astype(jnp.float32)[0:1]
            x = jnp.where(rows == block_n - 1, nxt,
                          pltpu.roll(x, block_n - 1, 0))
        return jnp.where(rows < live, x, 0.0)

    def x_row(jr):  # rows past the live count are never pushed
        if not skip:
            return x_ref[pl.ds(jr, 1), :].astype(jnp.float32)
        row = x_ref[pl.ds(jnp.minimum(jr + 1, block_n - 1), 1), :]
        nxt = xn_ref[...].astype(jnp.float32)[0:1]
        return jnp.where(jr == block_n - 1, nxt, row.astype(jnp.float32))

    if in_place:
        ys = ys_ref[...]
        cols = jax.lax.broadcasted_iota(jnp.int32, ys.shape, 1)
        if skip:
            if block_n % STATE_LANES == 0:  # the next block starts a lane tile
                nxt = yn_ref[:, 0:1]
            else:
                lane = jax.lax.broadcasted_iota(jnp.int32, yn_ref.shape, 1)
                at = jax.lax.rem((i + 1) * block_n, STATE_LANES)
                nxt = jnp.sum(jnp.where(lane == at, yn_ref[...], 0.0), axis=1,
                              keepdims=True)
            ys = jnp.where(cols == block_n - 1, nxt,
                           pltpu.roll(ys, block_n - 1, 1))
        models = jax.lax.broadcasted_iota(jnp.int32, ys.shape, 0)
        ys_ref = refs[-1]
        ys_ref[...] = jnp.where(
            jnp.logical_and(cols < live, models < n_models - j * b_tile),
            ys, 0.0,
        )

    st = st_slots[slot]
    params = p_ref[...]
    lookahead = lookahead_max is not None
    r, xi2, wsq, m, cnt = _block_update(
        x_at, x_row, ys_ref, bank.at[slot], gram_ref, band_ref,
        True if J == 1 else j == 0,  # the block's first visit fills the Gram
        st[:, 0:1], st[:, 1:2], st[:, 2:3], m_slots[slot][:, 0:1],
        slots[3][slot][:, 0:1] if lookahead else None,
        slots[4].at[slot] if lookahead else None,
        params[:, 0:1], params[:, 1:2],
        params[:, 2:3].astype(jnp.int32) if lookahead else None,
        row0, n_valid, i == n_blocks - 1,
        block_n=block_n, b_tile=b_tile, lookahead_max=lookahead_max,
    )
    st_slots[slot] = _state_slab(r, xi2, wsq)
    m_slots[slot] = _count_slab(m)
    if lookahead:
        slots[3][slot] = _count_slab(cnt)

    if not cycling:
        @pl.when(i == n_blocks - 1)
        def _store():
            start_out(t)
            wait_out(t)
    else:
        start_out(t)  # waited at step t+1 (or just below on the last step)

        @pl.when(t == T - 1)
        def _drain_last():
            wait_out(t)


def streamsvm_scan_many_pallas(
    X: jax.Array,
    Y: jax.Array,
    W0: jax.Array,
    r0: jax.Array,
    xi20: jax.Array,
    c_inv: jax.Array,
    m0: jax.Array,
    gain: jax.Array | None = None,
    *,
    lookahead: jax.Array | None = None,
    lookahead_max: int | None = None,
    n_valid=None,
    in_place: bool = True,
    skip: int = 0,
    block_n: int = 256,
    b_tile: int | None = None,
    stream_dtype=None,
    bank_resident: str = "vmem",
    interpret: bool = False,
):
    """One data pass updating a bank of B balls (the tiled multi-ball engine).

    X: (skip + N, D) stream (raw rows, no label signs), D a multiple of 128.
    Y: (B_y, skip + N) per-model label signs in {-1, +1}, B_y <= B (models
    past B_y are padding: a ragged last bank tile). The pass reads X and Y
    in place: it runs over their rows ``skip ..`` (``skip=1``: row 0 seeded
    the state and is consumed) in blocks of ``block_n`` rows, the last of
    which may be ragged; ``n_valid`` (traced; default N) counts the live
    rows, and later rows are ignored whatever they hold. ``in_place=False``
    says X and Y are instead a zero-padded copy of the rows to stream (the
    one ops.py makes of a stream it must pad or cast anyway): N a whole
    number of blocks and B_y = B, read as they are. Sign 0 marks an inert
    row for that model: it never violates, absorbs or buffers anything.
    W0/(r0, xi20, c_inv, m0): per-model starting state, shapes (B, D)/(B,).
    gain: per-model slack gain (defaults to c_inv — the "exact" variant).
    lookahead/lookahead_max: per-model (B,) int32 Algorithm-2 window sizes
    plus their static max — None runs Algorithm 1. Partial windows are
    flushed on the last grid step.
    b_tile: models per bank tile (must divide B; defaults to B, one tile).
    The grid is (ceil(rows / block_n), B/b_tile) with the DATA axis
    outer, so every stream tile is DMA'd from HBM once and revisited by all
    bank tiles.
    stream_dtype: dtype the (block_n, D) stream and (b_tile, block_n) sign
    tiles are DMA'd as (e.g. jnp.bfloat16 halves stream HBM traffic; only a
    copy, ``in_place=False``, may be bf16); bank, scalar state, and
    accumulators stay f32.
    bank_resident: "vmem" gives every bank tile its own VMEM slot, loaded
    once and written back once; "hbm" double-buffers (b_tile, D) tiles
    through 2 VMEM slots (see the module docstring), per-step VMEM working
    set O(2 slots + stream tile). One kernel serves both, so they are
    bit-exact (f32). ops.py resolves the "auto" policy before calling here.

    Returns (W, r, xi2, m) with leading axis B.
    """
    n_x, d = X.shape
    b = W0.shape[0]
    b_y, n_y = Y.shape
    if block_n < 8 or block_n % 8:
        raise ValueError(
            f"block_n={block_n} must be a positive multiple of 8 (the stream "
            "tile is a whole number of sublane tiles)"
        )
    n = n_y - skip  # rows to stream
    n_blocks = -(-n // block_n)
    if skip not in (0, 1) or n < 1 or (not in_place and skip):
        raise ValueError(
            f"skip={skip} must be 0 or 1 (0 for a copy) and leave rows of "
            f"Y.shape={Y.shape}"
        )
    if n_x != n_y or b_y > b or not in_place and (
            n % block_n or b_y != b):
        raise ValueError(
            f"Y must be (B, N) sign rows matching X and the state: got "
            f"Y.shape={Y.shape}, X.shape={X.shape}, B={b} (a copy is a "
            f"whole number of {block_n}-row blocks and of models)"
        )
    if b_tile is None:
        b_tile = b
    if b % b_tile != 0:
        raise ValueError(
            f"B={b} must be a multiple of b_tile={b_tile} (pad the bank; "
            "ops.streamsvm_fit_many does this)"
        )
    if (lookahead is None) != (lookahead_max is None):
        raise ValueError(
            "lookahead (per-model array) and lookahead_max (static int) must "
            f"be passed together: got {lookahead=}, {lookahead_max=}"
        )
    if bank_resident not in ("vmem", "hbm"):
        raise ValueError(
            f"unknown bank_resident {bank_resident!r}; expected 'vmem' or "
            "'hbm' (ops.streamsvm_fit_many resolves 'auto' before calling "
            "the kernel)"
        )
    n_btiles = b // b_tile
    stream_dtype = jnp.float32 if stream_dtype is None else stream_dtype
    if in_place and jnp.dtype(stream_dtype) != jnp.float32:
        raise ValueError(
            f"stream_dtype={stream_dtype!r}: only an f32 stream is read in "
            "place (pass a copy in the stream dtype with in_place=False)"
        )
    X = X.astype(stream_dtype)
    Y = Y.astype(stream_dtype)

    W0 = W0.reshape(b, d).astype(jnp.float32)
    c_inv = jnp.broadcast_to(jnp.asarray(c_inv, jnp.float32), (b,))
    gain = c_inv if gain is None else jnp.broadcast_to(
        jnp.asarray(gain, jnp.float32), (b,)
    )
    col = lambda v, dt=jnp.float32: jnp.broadcast_to(
        jnp.asarray(v, dt), (b,)
    )[:, None]
    l_arr = col(1 if lookahead is None else lookahead, jnp.int32)
    params = jnp.concatenate(
        [c_inv[:, None], gain[:, None], l_arr.astype(jnp.float32)], axis=1
    )  # (B, 3): [c_inv, gain, L]; L < 2**24 is exact in f32
    nv = jnp.asarray(n if n_valid is None else n_valid,
                     jnp.int32).reshape(1, 1)

    # The live state, in HBM, aliased input -> output so the kernel
    # updates it in place. Per-model scalars are (B, 128) slabs (see
    # STATE_LANES); wsq is derived in-kernel from the centers on the first
    # visit of each tile.
    state = [
        W0,
        _pad_lanes(jnp.concatenate([col(r0), col(xi20)], axis=1)),
        _pad_lanes(col(m0, jnp.int32)),
    ]
    if lookahead_max is not None:
        state += [
            jnp.zeros((b, STATE_LANES), jnp.int32),
            jnp.zeros((lookahead_max, b, d), jnp.float32),
        ]
    n_arrays = len(state)
    n_slots = n_btiles if bank_resident == "vmem" else min(2, n_btiles)
    slot_bufs = [
        pltpu.VMEM((n_slots,) + a.shape[:-2] + (b_tile, a.shape[-1]), a.dtype)
        for a in state
    ]
    # The stream tile and the sign tile; with ``skip`` also the tiles that
    # hold the next block's first row and column (clamped to the arrays on
    # the last block, whose last row is then past the stream).
    ins = [X]
    in_specs = [
        # The stream tile ignores the (inner) bank axis, so Pallas keeps
        # it resident across all bank tiles of a data block — the
        # data-major reuse the 2-D grid exists for.
        pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
    ]
    if skip:
        rows = math.gcd(block_n, 8 * 4 // jnp.dtype(stream_dtype).itemsize)
        last_rows = -(-n_x // rows) - 1
        ins.append(X)
        in_specs.append(pl.BlockSpec(
            (rows, d),
            lambda i, j: (jnp.minimum((i + 1) * (block_n // rows), last_rows),
                          0),
        ))
    ins.append(Y)
    in_specs.append(pl.BlockSpec((b_tile, block_n), lambda i, j: (j, i)))
    if skip:
        last_lanes = -(-n_y // STATE_LANES) - 1
        ins.append(Y)
        in_specs.append(pl.BlockSpec(
            (b_tile, STATE_LANES),
            lambda i, j: (j, jnp.minimum((i + 1) * block_n // STATE_LANES,
                                         last_lanes)),
        ))
    ins += [params, nv]
    in_specs += [
        pl.BlockSpec((b_tile, 3), lambda i, j: (j, 0)),
        pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
    ]
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    # Both grid axes stay sequential ("arbitrary", the default): besides the
    # bank state, the bank axis carries the block Gram and its band in
    # scratch from step (i, 0) to the steps (i, 1 ..) that read them.
    outs = pl.pallas_call(
        functools.partial(
            _kernel_many,
            block_n=block_n,
            b_tile=b_tile,
            lookahead_max=lookahead_max,
            n_blocks=n_blocks,
            n_btiles=n_btiles,
            n_slots=n_slots,
            in_place=in_place,
            skip=skip,
            n_models=b_y,
        ),
        grid=(n_blocks, n_btiles),
        in_specs=in_specs + [hbm_spec] * n_arrays,
        out_specs=[hbm_spec] * n_arrays,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in state],
        scratch_shapes=slot_bufs + [
            pltpu.SemaphoreType.DMA((n_arrays, 2, 2)),
            pltpu.VMEM((block_n, block_n), jnp.float32),
        ] + ([] if lookahead_max is not None else [
            pltpu.VMEM(
                (2 * _rows_per_step(block_n), block_n, STATE_LANES),
                jnp.float32,
            ),
        ]) + ([pltpu.VMEM((b_tile, block_n), jnp.float32)] if in_place
               else []),
        input_output_aliases={len(ins) + a: a for a in range(n_arrays)},
        interpret=interpret,
        name="streamsvm_scan_many",
    )(*ins, *state)
    w_out, st_out, m_out = outs[0], outs[1], outs[2]
    return w_out, st_out[:, 0], st_out[:, 1], m_out[:, 0]


def _pad_lanes(a):
    return jnp.pad(a, ((0, 0), (0, STATE_LANES - a.shape[1])))

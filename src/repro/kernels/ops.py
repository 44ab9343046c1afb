"""jit'd public wrappers around the Pallas kernels (padding, dtype policy).

These are the entry points the rest of the framework uses: one training
engine (``streamsvm_fit_many``, a bank of B models; one model is B = 1),
the serving readouts (``predict_bank``, ``predict_kernel_bank``) and the
Gram (``gram``). They handle 128-alignment padding (the lanes of a stream
whose D is not a multiple of 128; otherwise the bank engine reads its
stream and signs in place), interpret-mode selection
(``resolve_interpret``: the one place it is decided), bank tiling
(`b_tile`), the stream dtype policy, and state packing. Semantics match
ref.py exactly (tests sweep shapes and dtypes).

Dtype policy
------------
``stream_dtype`` controls the precision the *streamed* (block_n, D) data
and (b_tile, block_n) sign tiles are DMA'd from HBM as. ``"bf16"`` halves
stream HBM traffic, which is the dominant byte term at scale (the bank is
O(B*D) once, the stream is O(N*D) every fit), at the price of one cast copy
of X and Y (an f32 stream is read in place, ``reads_in_place``). The bank,
ball scalars, and every in-kernel accumulator stay f32 regardless. Labels
in {-1, 0, +1} are exact in bf16; feature rounding is bounded by the bf16
eps sweep in tests/test_tiled_engine.py.

Compile caching
---------------
``c`` / ``cs`` enter the kernels only through the traced ``1/C`` array, so
sweeping C values NEVER recompiles — only shape, ``block_n``, ``b_tile``,
``variant``, ``lookahead``, ``bank_resident`` and dtype changes do
(regression-tested via the jit cache in tests/test_tiled_engine.py and
tests/test_hbm_bank.py).

Bank residency policy
---------------------
``bank_resident`` picks where the engine keeps the (B, D) bank (plus state
and lookahead windows) while the grid runs:

  "vmem"  persistent VMEM scratch — the per-step working set contains the
          WHOLE bank, so B*D is capped by the VMEM budget;
  "hbm"   HBM/ANY-space buffers streamed through a 2-slot VMEM ring with
          async DMA (prefetch overlapped with compute) — the per-step
          working set is O(b_tile * D), independent of B;
  "auto"  picks from the per-step VMEM byte model (``engine_vmem_bytes`` /
          ``predict_vmem_bytes``) against a budget: the default
          ``DEFAULT_VMEM_BUDGET_BYTES`` (16 MiB — the scoped VMEM limit a
          Mosaic kernel gets by default on v5e; no kernel here raises it),
          overridable per call (``vmem_budget_bytes=``) or per process
          (``REPRO_VMEM_BUDGET_BYTES``).

Configs that fit NO residency (e.g. a single (b_tile, D) ring slot already
beyond the budget) are rejected up front with a ValueError carrying the
byte breakdown — including when ``bank_resident="vmem"`` is forced on an
oversized bank.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.meb import Ball
from .gram import gram_pallas
from .predict import NEG_MASK, predict_bank_pallas
from .streamsvm_scan import (
    D_CHUNK,
    _rows_per_step,
    streamsvm_scan_many_pallas,
)

_STREAM_DTYPES = {
    None: None,
    "f32": jnp.float32,
    "float32": jnp.float32,
    "bf16": jnp.bfloat16,
    "bfloat16": jnp.bfloat16,
}


def resolve_interpret(interpret: bool | None) -> bool:
    """Decide whether the Pallas kernels run in interpret mode.

    The kernel modules take ``interpret`` as given; every public wrapper
    here resolves it through this function. ``None`` means interpret mode
    exactly when the default backend is not a TPU (the CPU test host). On a
    TPU backend every kernel compiles with Mosaic: asking for interpret mode
    there is a ValueError, so no path can hide the device behind the
    interpreter.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU backend: the kernels compile with "
            "Mosaic there; pass interpret=None"
        )
    return bool(interpret)


def _resolve_stream_dtype(stream_dtype):
    if stream_dtype in _STREAM_DTYPES:
        return _STREAM_DTYPES[stream_dtype]
    try:
        return jnp.dtype(stream_dtype).type
    except TypeError:
        raise ValueError(
            f"unknown stream_dtype {stream_dtype!r}; expected None, 'f32', "
            "'bf16', or a jnp dtype"
        ) from None


def bank_tiling(b: int, b_tile: int | None):
    """Resolve the engines' bank tiling for B models.

    Returns ``(effective_b_tile, n_bank_tiles)``: the requested tile rounded
    up to the f32 sublane multiple of 8 (default: one tile holding the whole
    bank) and the number of tiles covering the (padded) bank. Every tile
    the engines stage is then a sublane slab at an 8-aligned row offset,
    which Mosaic takes for any such size; the predict engine's outputs are
    laid out so their tiles are legal too (see ``predict_bank_pallas``).
    The single source of truth for this policy.
    """
    bt = -(-b // 8) * 8 if b_tile is None else -(-b_tile // 8) * 8
    return bt, -(-b // bt)


def gram_tiling(m: int, n: int, bm: int, bn: int):
    """Resolve the Gram kernel's derived (bm_, bn_) block shapes.

    Shrinks the requested tiles to the data but keeps the f32 sublane/lane
    alignment Mosaic requires — bm_ a multiple of 8, bn_ a multiple of 128,
    for any M and N. The single source of truth for this policy;
    regression-tested on odd shapes in tests/test_kernel_bank.py.
    """
    bm_ = -(-min(bm, max(8, m)) // 8) * 8
    bn_ = -(-min(bn, max(128, n)) // 128) * 128
    return bm_, bn_


def ovr_group_tiling(b: int, n_classes: int, b_tile: int | None):
    """Resolve the predict engine's ovr-epilogue bank tiling for B models.

    Each group's ``n_classes`` class lanes are padded to the f32 sublane
    multiple of 8 (``nc_pad``) and the bank is tiled in WHOLE groups so a
    group's argmax never crosses a bank tile. Returns ``(nc_pad, g_tile,
    padded_groups)``: lanes per padded group, groups per bank tile (derived
    from the requested lane ``b_tile``; default one tile holding every
    group), and the group count padded to a whole number of tiles. Any
    g_tile compiles: the kernel writes each tile's (q_block, g_tile) result
    into an output plane of its own. The single source of truth for this
    policy.
    """
    g = b // n_classes
    nc_pad = -(-n_classes // 8) * 8
    g_tile = g if b_tile is None else max(1, b_tile // nc_pad)
    return nc_pad, g_tile, -(-g // g_tile) * g_tile


# ---------------------------------------------------------------------------
# Bank residency: per-step VMEM byte model + the "auto" policy
# ---------------------------------------------------------------------------

#: Default per-step VMEM budget for the "auto" residency policy (and the
#: preflight check): the 16 MiB of scoped VMEM a Mosaic kernel gets by
#: default on v5e (no pallas_call here sets compiler params). Overridable
#: per call (``vmem_budget_bytes=``) and per process
#: (``REPRO_VMEM_BUDGET_BYTES``).
DEFAULT_VMEM_BUDGET_BYTES = 16 * 2**20

_BANK_RESIDENCIES = ("vmem", "hbm", "auto")


def vmem_budget_bytes(override: int | None = None) -> int:
    """The VMEM budget the residency policy checks against, in bytes."""
    if override is not None:
        return int(override)
    env = os.environ.get("REPRO_VMEM_BUDGET_BYTES")
    return int(env) if env else DEFAULT_VMEM_BUDGET_BYTES


def _stream_bytes(stream_dtype) -> int:
    dt = _resolve_stream_dtype(stream_dtype)
    return 2 if dt == jnp.bfloat16 else 4


def reads_in_place(d: int, x_dtype, stream_dtype) -> bool:
    """Whether the bank engine reads the caller's X and Y in place.

    Only an f32 stream that arrives as f32 with D a multiple of 128 is read
    in place. Any other (lanes to pad, a cast, a bf16 stream) is copied
    once by ``streamsvm_fit_many``. ``engine_vmem_bytes`` counts the
    in-place tiles by the same rule."""
    sdt = jnp.dtype(_resolve_stream_dtype(stream_dtype) or jnp.float32)
    return d % 128 == 0 and sdt == jnp.float32 and jnp.dtype(x_dtype) == sdt


#: (b_tile, 1) per-model columns the engine's row loop keeps live at once
#: (r, xi2, wsq, m, decay, c_inv, gain and their temporaries). Mosaic holds
#: each in (8, 128) tiles, so one costs b_tile * 128 * 4 bytes of VMEM. The
#: count is calibrated against the scoped VMEM the v5e compiler allocates
#: (measured by compiling under ``vmem_limit_bytes``) for the quickstart
#: bank (B = 600, D = 784, N = 65,536) at b_tile 256, 512 and 600 and the
#: beyond-VMEM bank (B = 3000, D = 4096, N = 16,384) at b_tile 64 and 128:
#: with it the model is at or above the compiler wherever it is near the
#: budget, so the tile it derives compiles. Algorithm 1's read-ahead loop
#: was checked at the same points: with its Gram band counted apart
#: (``gram_band``) the count still holds.
ROW_LOOP_COLUMNS = 35


def engine_vmem_bytes(
    b: int,
    d: int,
    *,
    block_n: int = 256,
    b_tile: int | None = None,
    stream_dtype=None,
    lookahead_max: int | None = None,
    bank_resident: str = "vmem",
    x_dtype=jnp.float32,
) -> dict:
    """Per-step VMEM working set of the training engine, bytes by term.

    Models the padded shapes the kernel allocates (D to the lane multiple
    of 128, B to whole bank tiles, per-model arrays to 128-lane rows):
    the double-buffered stream and sign tiles (for a stream read in place,
    with the small tiles that hold the next block's first row and column,
    and the signs' realigned copy), the bf16 parts Mosaic splits a full-f32 dot's operands into (one
    ``D_CHUNK``-wide chunk of the stream tile and of the bank tile at a
    time) and the realigned stream chunk, the block Gram scratch and
    Algorithm 1's band of it, the per-model parameter tile, the row loop's
    per-model columns, and the VMEM slots of the bank, its state slabs and
    the lookahead windows — one slot per bank tile when VMEM-resident, two
    when the tiles ring through from HBM. The "auto" policy and the
    preflight ValueError both read this; the BENCH harnesses record its
    total per row as ``vmem_working_set_bytes``. ``x_dtype`` is the dtype X
    arrives in, which with ``reads_in_place`` decides whether the stream is
    read in place.
    """
    sz = _stream_bytes(stream_dtype)
    bt, n_tiles = bank_tiling(b, b_tile)
    dp = -(-d // 128) * 128
    L = lookahead_max or 0
    slots = n_tiles if bank_resident == "vmem" else min(2, n_tiles)
    lane_row = 128 * 4  # one per-model row of a (rows, 128) f32/i32 slab
    in_place = reads_in_place(d, x_dtype, stream_dtype)  # else X is copied
    next_rows = 8 if in_place else 0  # the tile of the next block's first row
    f32_copy = 4 if sz != 4 else 0  # bf16 sign tiles are upcast to f32 values
    u = 0 if L else _rows_per_step(block_n)  # Algorithm 1's band
    return {
        "stream_tile": 2 * (block_n + next_rows) * dp * sz,
        # signs in the stream dtype; read in place, their realigned copy
        # and the next block's first column too
        "sign_tile": 2 * bt * block_n * sz + bt * block_n * f32_copy
        + in_place * (bt * block_n * 4 + 2 * bt * 128 * 4),
        # three bf16 parts (6 bytes) per element of both dot operands
        "dot_split": 6 * (block_n + bt) * min(dp, D_CHUNK),
        # a stream read in place (f32, D a multiple of 128) realigned by a
        # row and masked, a D chunk at a time: the rolled and the masked f32
        # chunk (+0.9 MB on the v5e compiler's scoped VMEM at B 3000, D 4096,
        # tile 64)
        "realign": 2 * block_n * min(dp, D_CHUNK) * 4 if in_place else 0,
        "gram": block_n * block_n * 4,
        "gram_band": 2 * u * block_n * lane_row,
        "params": 2 * bt * lane_row,
        "row_loop": ROW_LOOP_COLUMNS * bt * lane_row,
        "bank": slots * bt * dp * 4,
        "state": slots * (3 if L else 2) * bt * lane_row,
        # window slots, plus one window-sized temporary of the push/flush
        "lookahead": (slots + 1) * L * bt * dp * 4,
    }


def kernel_engine_vmem_bytes(
    b: int,
    d: int,
    *,
    coreset_size: int,
    block_n: int = 256,
    s_tile: int | None = None,
    stream_dtype=None,
) -> dict:
    """Per-step VMEM working set of the kernelized bank engine, bytes by term.

    The kernelized engine's resident blocks are the two fused Gram launches'
    tiles: the K_cs launch scores a (block_n, D) stream tile against the
    (B * s_chunk, D) core-set operand (``s_tile`` chunks the S axis per
    model, so the Gram N axis — and with it the operand and output tiles —
    shrinks from B*S to B*s_tile columns per launch: the kernel-bank twin of
    PR 5's ``bank_resident`` knob, same budget, same preflight), and the
    K_tt launch is (block_n, block_n). Gram operands are staged f32
    (``gram`` upcasts before padding), BlockSpec-delivered tiles count twice
    (Pallas double-buffers its own pipeline), and the f32 accumulator
    scratch counts once. The preflight in ``core.fit_kernel_bank`` and the
    BENCH engine harness's kernelized ``vmem_working_set_bytes`` both read
    this.
    """
    S = int(coreset_size)
    st = S if s_tile is None else min(int(s_tile), S)
    cols = b * st  # columns per K_cs launch
    bm_, bn_ = gram_tiling(block_n, cols, 256, 256)
    bk = min(512, -(-d // 512) * 512)  # gram pads the feature axis to 512s
    dp = -(-d // 128) * 128
    return {
        # one K_cs launch's per-step tiles: A/B operands + out + the f32
        # accumulator; BlockSpec-staged tiles count twice (Pallas double-
        # buffers its own pipeline). The grid bounds these at (256, 256)
        # regardless of B*S.
        "gram_tiles": (
            2 * (bm_ + bn_) * bk * 4 + 2 * bm_ * bn_ * 4 + bm_ * bn_ * 4
        ),
        # The terms ``s_tile`` actually caps — the whole-buffer analogues of
        # the linear engine's VMEM-resident bank term: each tile step
        # materializes the launch's full (block_n, B * s_chunk) K_cs block
        # for the recursion to read, plus the (B * s_chunk, D) gathered
        # core-set operand it was scored against.
        "k_cs_block": block_n * cols * 4,
        "coreset_operand": cols * dp * 4,
        # the K_tt block and the stream tile itself
        "k_tt": block_n * block_n * 4,
        "stream_tile": 2 * block_n * dp * 4,
    }


def predict_vmem_bytes(
    b: int,
    d: int,
    *,
    q_block: int = 256,
    b_tile: int | None = None,
    stream_dtype=None,
    epilogue: str = "scores",
    n_classes: int | None = None,
    k: int | None = None,
    bank_resident: str = "vmem",
) -> dict:
    """Per-step VMEM working set of the predict engine, bytes by term.

    The serving kernel holds no full-bank scratch in either residency — a
    (b_tile, D) slice is staged per step by the BlockSpec pipeline ("vmem")
    or the explicit 2-slot ring ("hbm"), so the two working sets coincide.
    What "hbm" changes is WHERE the bank lives between steps (ANY/HBM, never
    claiming VMEM residency) — the policy knob exists so a bank too big to
    train VMEM-resident also serves HBM-resident (see
    ``resolve_bank_resident``).
    """
    sz = _stream_bytes(stream_dtype)
    dp = -(-d // 128) * 128
    if epilogue == "ovr":
        nc_pad, g_tile, gp = ovr_group_tiling(b, n_classes, b_tile)
        bt = g_tile * nc_pad
        out_cols = 2 * g_tile  # class ids + margins
    else:
        bt, _ = bank_tiling(b, b_tile)
        out_cols = 2 * k if epilogue == "topk" else bt
    out = {
        "query_tile": 2 * q_block * dp * sz,
        "bank": 2 * bt * dp * 4,  # BlockSpec pipeline or 2-slot ring: same
        "bias": 2 * bt * 128 * 4,  # (b_tile, 1) rows pad to 128 lanes
        "epilogue_state": (2 * q_block * k * 4 if epilogue == "topk" else 0),
        "out_tiles": 2 * q_block * out_cols * 4,
    }
    return out


def derive_b_tile(b: int, byte_model_at, *, vmem_budget: int):
    """Pick a bank tile for one residency when the caller gave none.

    The default ``b_tile=None`` means "one tile holding the whole bank".
    The engines' per-step working set grows with the tile (an HBM ring
    holds two of them; every tile's row loop holds per-model columns), so a
    large bank can be over the budget as one tile and fit as several.
    ``byte_model_at(b_tile)`` returns one residency's working-set breakdown
    for a candidate tile; this returns None if the whole bank fits as one
    tile, else the largest power-of-two tile (512 down to 8) under the
    budget, or 8 when nothing fits (the preflight then raises). A
    caller-supplied ``b_tile`` is never overridden.
    """
    if sum(byte_model_at(None).values()) <= vmem_budget:
        return None  # the whole bank fits as one tile — keep it
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if cand < b and sum(byte_model_at(cand).values()) <= vmem_budget:
            return cand
    return 8  # nothing fits: smallest tile, and let the preflight raise


def resolve_bank_resident(
    bank_resident: str,
    byte_model,
    *,
    vmem_budget: int,
    what: str,
    shapes: str,
) -> tuple[str, dict]:
    """Resolve the residency policy against the per-step VMEM byte model.

    ``byte_model(residency)`` returns the working-set breakdown for one
    residency. "auto" picks "vmem" when its working set fits ``vmem_budget``
    and "hbm" otherwise; a FORCED residency whose working set exceeds the
    budget, and configs no residency can satisfy, raise a ValueError
    carrying the shapes, the breakdown and the budget (this preflight is
    what turns the old opaque Pallas scratch-allocation failure into an
    actionable error). Returns ``(residency, breakdown)``.
    """
    if bank_resident not in _BANK_RESIDENCIES:
        raise ValueError(
            f"unknown bank_resident {bank_resident!r}; expected one of "
            f"{_BANK_RESIDENCIES}"
        )
    if bank_resident == "auto":
        by = byte_model("vmem")
        if sum(by.values()) <= vmem_budget:
            return "vmem", by
        bank_resident = "hbm"
    by = byte_model(bank_resident)
    total = sum(by.values())
    if total > vmem_budget:
        hint = (
            "shrink b_tile/block_n/lookahead or raise the budget"
            if bank_resident == "hbm"
            else 'use bank_resident="hbm" (or "auto"), or shrink the bank'
        )
        raise ValueError(
            f"{what} with {shapes} needs a per-step VMEM working set of "
            f"{total} bytes under bank_resident={bank_resident!r} "
            f"(breakdown: {by}), exceeding the budget of {vmem_budget} "
            f"bytes — {hint}. The budget follows vmem_budget_bytes(): "
            "pass vmem_budget_bytes= or set REPRO_VMEM_BUDGET_BYTES."
        )
    return bank_resident, by


# vmem_budget_bytes is shadowed inside plan_bank_engine and the jit'd
# wrappers, whose keyword arguments reuse the public name.
_vmem_budget = vmem_budget_bytes


def plan_bank_engine(
    b: int,
    d: int,
    *,
    block_n: int = 256,
    b_tile: int | None = None,
    stream_dtype=None,
    lookahead_max: int | None = None,
    bank_resident: str = "auto",
    vmem_budget_bytes: int | None = None,
    x_dtype=jnp.float32,
) -> tuple[str, int | None]:
    """The training engine's ``(residency, b_tile)`` for one configuration.

    ``b_tile=None`` means "whole bank in one tile". When the caller named no
    tile, one that fits the budget is derived: VMEM-resident first (under
    "auto"), then as an HBM ring, so "auto" rescues beyond-VMEM banks. Then
    the residency preflight resolves "auto" and rejects configs whose
    per-step VMEM working set fits no residency — before Pallas gets a
    chance to fail opaquely inside lowering (it guards forced "vmem" too).
    ``streamsvm_fit_many`` trains with exactly this plan.
    """
    budget = _vmem_budget(vmem_budget_bytes)
    bytes_at = lambda bt_, res: engine_vmem_bytes(
        b, d, block_n=block_n, b_tile=bt_, stream_dtype=stream_dtype,
        lookahead_max=lookahead_max, bank_resident=res, x_dtype=x_dtype,
    )
    if b_tile is None and bank_resident in _BANK_RESIDENCIES:
        order = ("vmem", "hbm") if bank_resident == "auto" else (bank_resident,)
        for res in order:
            b_tile = derive_b_tile(
                b, lambda bt_: bytes_at(bt_, res), vmem_budget=budget
            )
            if sum(bytes_at(b_tile, res).values()) <= budget:
                break
    residency, _ = resolve_bank_resident(
        bank_resident,
        lambda res: bytes_at(b_tile, res),
        vmem_budget=budget,
        what="streamsvm_fit_many",
        shapes=(
            f"B={b}, D={d}, block_n={block_n}, b_tile={b_tile}, "
            f"lookahead_max={lookahead_max}, stream_dtype={stream_dtype!r}"
        ),
    )
    return residency, b_tile


def bank_engine_grid(
    n: int,
    b: int,
    d: int,
    *,
    variant: str = "exact",
    lookahead=None,
    block_n: int = 256,
    b_tile: int | None = None,
    stream_dtype=None,
    bank_resident: str = "auto",
    x_dtype=jnp.float32,
) -> tuple[int, int]:
    """``(data blocks, bank tiles)``: the training engine's grid when
    ``streamsvm_fit_many`` fits B models on an (n, d) stream from scratch
    (row 0 seeds the bank; the grid streams the other n - 1 rows), tiled
    by the same plan. The engine fills the block Gram once per data block
    and visits each block once per bank tile."""
    l_max = None
    if variant in ("lookahead", "lookahead-paper"):
        lookahead = 1 if lookahead is None else lookahead
        l_max = lookahead if isinstance(lookahead, int) else max(lookahead)
    stream_dtype = _resolve_stream_dtype(stream_dtype)
    _, b_tile = plan_bank_engine(
        b, d, block_n=block_n, b_tile=b_tile, stream_dtype=stream_dtype,
        lookahead_max=l_max, bank_resident=bank_resident, x_dtype=x_dtype,
    )
    return -(-(n - 1) // block_n), bank_tiling(b, b_tile)[1]


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(
    jax.jit,
    static_argnames=(
        "variant", "lookahead", "block_n", "b_tile", "stream_dtype",
        "bank_resident", "vmem_budget_bytes", "interpret",
    ),
)
def streamsvm_fit_many(
    X: jax.Array,
    Y: jax.Array,
    cs: jax.Array,
    balls: Ball | None = None,
    *,
    variant: str = "exact",
    lookahead=None,
    block_n: int = 256,
    b_tile: int | None = None,
    stream_dtype=None,
    bank_resident: str = "auto",
    vmem_budget_bytes: int | None = None,
    interpret: bool | None = None,
    n_valid=None,
) -> Ball:
    """One-pass Algorithm 1/2 for a bank of B models — ONE read of the stream.

    X: (N, D) shared stream; Y: (B, N) per-model label signs in {-1, +1}
    (classes x C-grid x variants all flatten onto the B axis). A sign of 0
    marks a STREAMED row inert *for that model* — no violation, no absorb,
    no lookahead buffering. ``n_valid`` (traced; default N) counts the live
    rows of X and Y: rows past it are inert for every model whatever they
    hold, which is how core.fit_bank_sharded gives each shard its live row
    count. Caveat: when ``balls`` is None, row 0 is consumed as every
    model's init example BEFORE the contract applies, so it must carry a
    real +-1 sign for every model (``Y[b, 0] == 0`` would seed model b from
    the zero point w=0, m=1 — pass an explicit ``balls`` or keep sign-0
    rows off position 0).

    X and Y are read in place (``reads_in_place``). The engine makes no
    copy of either when D is a multiple of 128, the stream is f32 and X and
    Y are f32, for any N and any B: the seed is taken from ``X[0]`` and
    ``Y[:, 0]`` and the kernel starts at row 1 of the caller's arrays; the
    last block of rows is ragged and masked by the live row count; Y keeps
    its B rows, so the last bank tile is ragged and its missing models read
    sign 0. Only (B,)- and (B, D)-sized state is padded to whole bank
    tiles; a Y of another dtype is cast to f32. Otherwise (X's lanes to pad
    to a multiple of 128, an X of another dtype, or
    ``stream_dtype="bf16"``) X and Y are copied once, in the stream dtype,
    as the zero-padded stream without its seed row. The results are those
    of the zero-padded stream, bit for bit.
    cs: scalar or
    (B,) per-model C (traced — a C sweep reuses one compilation). Starts from
    ``balls`` (a Ball stacked on a leading B axis) if given, else initializes
    every model from the first example. Returns a stacked Ball; state stays
    O(B * D) while each (block_n, D) tile is loaded from HBM exactly once and
    updates all B models.

    variant: "exact" / "paper-listing" select Algorithm 1's slack gain;
    "lookahead" / "lookahead-paper" run fused Algorithm 2 (exact vs
    paper-listing slack gain) with per-model windows given by ``lookahead``
    (an int, or a length-B tuple of ints; static). Windows are flushed
    farthest-point-first when full and at end of stream.
    b_tile: models per VMEM bank tile (rounded up to the f32 sublane multiple
    of 8; default: one tile holding the whole bank). The engine's grid is
    data-major, so any B runs in ONE stream pass — B/b_tile bank tiles
    revisit each resident stream tile instead of re-reading it.
    stream_dtype: None/"f32" or "bf16" — see the module dtype policy.
    bank_resident: "vmem" / "hbm" / "auto" (default) — see the module
    residency policy. "hbm" lifts the VMEM cap on B*D by keeping the bank,
    state and lookahead windows in HBM/ANY space, double-buffered through a
    2-slot VMEM ring (bit-exact f32 with "vmem"); impossible configs raise
    a ValueError carrying the per-step byte breakdown and the budget
    (``vmem_budget_bytes`` / REPRO_VMEM_BUDGET_BYTES).
    """
    if Y.ndim != 2:
        raise ValueError(
            f"Y must be 2-D (B, N) sign rows: got Y.shape={Y.shape}, "
            f"X.shape={X.shape}; one model's labels y are Y = y[None]"
        )
    b, n_y = Y.shape
    n, d = X.shape
    if n_y != n:
        raise ValueError(
            f"Y must be (B, N) sign rows matching X: got Y.shape={Y.shape}, "
            f"X.shape={X.shape}"
        )
    if variant not in ("exact", "paper-listing", "lookahead", "lookahead-paper"):
        raise ValueError(
            f"unknown variant {variant!r}; expected 'exact', 'paper-listing', "
            "'lookahead' or 'lookahead-paper'"
        )
    is_lookahead = variant in ("lookahead", "lookahead-paper")
    if not is_lookahead and lookahead is not None:
        raise ValueError(
            f"lookahead={lookahead!r} requires variant='lookahead' or "
            f"'lookahead-paper' (got variant={variant!r})"
        )
    stream_dtype = _resolve_stream_dtype(stream_dtype)
    cs = jnp.broadcast_to(jnp.asarray(cs, jnp.float32), (b,))
    c_inv = 1.0 / cs
    gain = (
        jnp.ones_like(c_inv)
        if variant in ("paper-listing", "lookahead-paper")
        else c_inv
    )
    if is_lookahead:
        lookahead = 1 if lookahead is None else lookahead
        if isinstance(lookahead, int):
            lookahead = (lookahead,) * b
        lookahead = tuple(int(l) for l in lookahead)
        if len(lookahead) != b or min(lookahead) < 1:
            raise ValueError(
                f"lookahead must be an int >= 1 or a length-B tuple of them: "
                f"got {lookahead} for B={b}"
            )
    l_max = max(lookahead) if is_lookahead else None
    residency, b_tile = plan_bank_engine(
        b, d, block_n=block_n, b_tile=b_tile, stream_dtype=stream_dtype,
        lookahead_max=l_max, bank_resident=bank_resident,
        vmem_budget_bytes=vmem_budget_bytes, x_dtype=X.dtype,
    )
    skip = 1 if balls is None else 0
    if balls is None:
        w0 = Y[:, 0:1] * X[0][None, :]
        r0 = jnp.zeros((b,), jnp.float32)
        xi20, m0 = gain, jnp.ones((b,), jnp.float32)
    else:
        w0, r0, xi20, m0 = balls.w, balls.r, balls.xi2, balls.m
    if n == skip:  # nothing (left) to stream — the initial state IS the answer
        return Ball(
            w=w0.astype(jnp.float32),
            r=jnp.broadcast_to(jnp.asarray(r0, jnp.float32), (b,)),
            xi2=jnp.broadcast_to(jnp.asarray(xi20, jnp.float32), (b,)),
            m=jnp.broadcast_to(jnp.asarray(m0, jnp.int32), (b,)),
        )
    # Pad the state to a whole number of bank tiles (tiles themselves to the
    # f32 sublane multiple of 8); padded models carry C=1, L=1 and an
    # infinite starting radius, read sign 0 from the kernel, never
    # "violate", so they absorb nothing (in lookahead mode never buffer or
    # flush), and are sliced off below.
    bt, _ = bank_tiling(b, b_tile)
    bp = -(-b // bt) * bt
    live = jnp.arange(bp) < b
    W0p = _pad_to(_pad_to(w0.astype(jnp.float32), 128, 1), bp, 0)
    pad1 = lambda v: _pad_to(
        jnp.broadcast_to(jnp.asarray(v, jnp.float32), (b,)), bp, 0
    )
    if is_lookahead:
        l_pad = lookahead + (1,) * (bp - b)
        l_arr = jnp.asarray(l_pad, jnp.int32)
        l_max = max(lookahead)
    else:
        l_arr = None
        l_max = None
    # The stream is read in place where the kernel can take X as it is. One
    # that has to be copied anyway (lanes to pad, a dtype to cast) is copied
    # as the kernel reads it fastest: rows past the seed, zeros past the
    # live rows, whole blocks and whole bank tiles.
    sdt = jnp.dtype(stream_dtype or jnp.float32)
    in_place = reads_in_place(d, X.dtype, sdt)
    if not in_place:
        X, Y = X[skip:], Y[:, skip:].astype(sdt)
        if n_valid is not None:
            rows = jnp.arange(n - skip)
            X = jnp.where(rows[:, None] < n_valid - skip, X, 0)
            Y = jnp.where(rows[None, :] < n_valid - skip, Y, 0)
        X = _pad_to(_pad_to(X.astype(sdt), 128, 1), block_n, 0)
        Y = _pad_to(_pad_to(Y, block_n, 1), bp, 0)
    W, r, xi2, m = streamsvm_scan_many_pallas(
        X,
        Y,
        W0p,
        jnp.where(live, pad1(r0), jnp.inf),
        pad1(xi20),
        jnp.where(live, pad1(c_inv), 1.0),
        _pad_to(jnp.broadcast_to(jnp.asarray(m0, jnp.int32), (b,)), bp, 0),
        jnp.where(live, pad1(gain), 1.0),
        lookahead=l_arr,
        lookahead_max=l_max,
        n_valid=(n if n_valid is None else n_valid) - skip,
        in_place=in_place,
        skip=skip if in_place else 0,
        block_n=block_n,
        b_tile=bt,
        stream_dtype=stream_dtype,
        bank_resident=residency,
        interpret=resolve_interpret(interpret),
    )
    return Ball(w=W[:b, :d], r=r[:b], xi2=xi2[:b], m=m[:b])


@partial(
    jax.jit,
    static_argnames=("epilogue", "bm", "bn", "bk", "interpret"),
)
def gram(
    A: jax.Array,
    B: jax.Array,
    *,
    epilogue: str = "linear",
    gamma=1.0,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Kernel matrix K[i, j] = k(a_i, b_j) with MXU tiling.

    ``gamma`` is TRACED (a (1, 1) scalar operand of the Pallas launch), so a
    gamma sweep reuses one compilation — regression-tested alongside the C
    sweep in tests/test_kernel_bank.py.
    """
    m, d = A.shape
    n, _ = B.shape
    if B.shape[1] != d:
        raise ValueError(
            f"A and B must share the feature axis: got A.shape={A.shape}, "
            f"B.shape={B.shape}"
        )
    bm_, bn_ = gram_tiling(m, n, bm, bn)
    Ap = _pad_to(_pad_to(A.astype(jnp.float32), bk, 1), bm_, 0)
    Bp = _pad_to(_pad_to(B.astype(jnp.float32), bk, 1), bn_, 0)
    out = gram_pallas(
        Ap, Bp, epilogue=epilogue, gamma=gamma, bm=bm_, bn=bn_, bk=bk,
        interpret=resolve_interpret(interpret),
    )
    return out[:m, :n]


@partial(
    jax.jit,
    static_argnames=(
        "epilogue", "n_classes", "k", "q_block", "b_tile", "stream_dtype",
        "bank_resident", "vmem_budget_bytes", "interpret",
    ),
)
def predict_bank(
    X: jax.Array,
    W: jax.Array,
    *,
    epilogue: str = "scores",
    n_classes: int | None = None,
    k: int | None = None,
    q_block: int = 256,
    b_tile: int | None = None,
    stream_dtype=None,
    bank_resident: str = "auto",
    vmem_budget_bytes: int | None = None,
    interpret: bool | None = None,
):
    """Score (Q, D) queries against a (B, D) bank with a fused epilogue.

    The serving twin of ``streamsvm_fit_many``: the kernel's 2-D grid is
    data-major (query tiles outer), so each (q_block, D) query tile is DMA'd
    from HBM once and revisited by every (b_tile, D) bank tile. ``W`` is the
    trained bank's weight rows (``bank.w`` of a fit_bank/fit_ovr/fit_c_grid
    result). Only shapes and the static epilogue parameters compile — serving
    a NEW bank of the same shape never recompiles (regression-tested via the
    jit cache in tests/test_predict_engine.py).

    epilogue:
      "scores"          -> (Q, B) f32 margins, bit-exact (f32 queries) with
                           the jnp readout ``X @ W.T``
      "ovr", n_classes= -> ((Q, G) int32, (Q, G) f32): winning class id and
                           its margin per C-grid group, G = B // n_classes,
                           bank laid out class-major within each group
                           (model = g * n_classes + class — exactly the
                           fit_ovr/fit_c_grid flattening). Groups are padded
                           to whole bank tiles so the argmax fuses into the
                           matmul step.
      "topk", k=        -> ((Q, k) f32, (Q, k) int32) descending top-k model
                           scores and ids per query.

    q_block: query rows per tile (the microbatch slot count BankServer packs
    into). b_tile: bank lanes per tile (rounded up to the f32 sublane
    multiple of 8; for "ovr" rounded to whole padded groups; default: one
    tile holding the whole bank). stream_dtype: None/"f32" or "bf16" — query
    tiles DMA'd as bf16 (half the dominant HBM term; the bank, bias and
    accumulators stay f32; see the module dtype policy).
    bank_resident: "vmem" / "hbm" / "auto" (default). "hbm" keeps the bank
    in ANY/HBM space and rings (b_tile, D) slices through a 2-slot VMEM
    buffer with async-copy prefetch (bit-exact f32 with "vmem"); "auto"
    serves HBM-resident exactly when the bank's full (B, D) f32 footprint
    exceeds the VMEM budget — the dominant term of the training policy's
    boundary, so train/serve residency decisions agree except in the
    narrow window where training's extra per-step stream-tile terms tip
    it over first (a bank clearly beyond VMEM trains AND serves
    HBM-resident). Per-step working sets are preflighted against the
    budget either way (ValueError with the byte breakdown on impossible
    configs).
    """
    q, d = X.shape
    b, dw = W.shape
    if dw != d:
        raise ValueError(
            f"queries and bank must share the feature axis: got X.shape="
            f"{X.shape}, W.shape={W.shape}"
        )
    if epilogue not in ("scores", "ovr", "topk"):
        raise ValueError(
            f"unknown epilogue {epilogue!r}; expected 'scores', 'ovr' or "
            "'topk'"
        )
    if epilogue != "ovr" and n_classes is not None:
        raise ValueError(
            f"n_classes={n_classes} requires epilogue='ovr' (got "
            f"epilogue={epilogue!r})"
        )
    if epilogue != "topk" and k is not None:
        raise ValueError(
            f"k={k} requires epilogue='topk' (got epilogue={epilogue!r})"
        )
    if epilogue == "ovr" and (
        n_classes is None or n_classes < 1 or b % n_classes
    ):
        raise ValueError(
            f"epilogue='ovr' needs n_classes >= 1 dividing B: got "
            f"n_classes={n_classes}, B={b}"
        )
    if epilogue == "topk" and (k is None or not (1 <= k <= b)):
        raise ValueError(
            f"epilogue='topk' needs 1 <= k <= B: got k={k}, B={b}"
        )
    stream_dtype = _resolve_stream_dtype(stream_dtype)
    # Residency: "auto" serves HBM-resident exactly when the full bank's f32
    # footprint exceeds the VMEM budget — the dominant term of the training
    # policy's boundary (which also counts per-step stream-tile terms), so
    # train/serve decisions agree away from the boundary; the chosen
    # residency's per-step working set is then preflighted either way.
    budget = _vmem_budget(vmem_budget_bytes)
    if bank_resident == "auto":  # unknown strings fall through to the
        dp = -(-d // 128) * 128  # resolver's own membership ValueError
        bank_resident = "hbm" if b * dp * 4 > budget else "vmem"
    predict_bytes_at = lambda bt_, res: predict_vmem_bytes(
        b, d, q_block=q_block, b_tile=bt_, stream_dtype=stream_dtype,
        epilogue=epilogue, n_classes=n_classes, k=k, bank_resident=res,
    )
    if bank_resident == "hbm" and b_tile is None:
        # default "whole bank per tile" is self-defeating as a ring slot —
        # derive a budget-fitting tile (a caller's b_tile is never touched)
        b_tile = derive_b_tile(
            b, lambda bt_: predict_bytes_at(bt_, "hbm"), vmem_budget=budget
        )
    residency, _ = resolve_bank_resident(
        bank_resident,
        lambda res: predict_bytes_at(b_tile, res),
        vmem_budget=budget,
        what="predict_bank",
        shapes=(
            f"Q={q}, B={b}, D={d}, q_block={q_block}, b_tile={b_tile}, "
            f"epilogue={epilogue!r}, stream_dtype={stream_dtype!r}"
        ),
    )
    interpret = resolve_interpret(interpret)
    Xp = _pad_to(_pad_to(X.astype(jnp.float32), 128, 1), q_block, 0)
    if stream_dtype is not None:
        Xp = Xp.astype(stream_dtype)
    Wf = W.astype(jnp.float32)

    if epilogue == "ovr":
        g = b // n_classes
        # Pad each group's class lanes to the sublane multiple of 8, then
        # tile the bank in whole GROUPS so a group's argmax never crosses a
        # tile boundary (the cross-tile running state "scores" and "topk"
        # need is unnecessary here).
        nc_pad, g_tile, gp = ovr_group_tiling(b, n_classes, b_tile)
        Wg = _pad_to(_pad_to(Wf.reshape(g, n_classes, d), nc_pad, 1), gp, 0)
        Wp = _pad_to(Wg.reshape(gp * nc_pad, d), 128, 1)
        lane = jnp.arange(gp * nc_pad)
        live = jnp.logical_and(
            lane % nc_pad < n_classes, lane // nc_pad < g
        )
        bias = jnp.where(live, 0.0, NEG_MASK)[:, None].astype(jnp.float32)
        cls, margin = predict_bank_pallas(
            Xp, Wp, bias, epilogue="ovr", q_block=q_block,
            b_tile=g_tile * nc_pad, nc_pad=nc_pad, bank_resident=residency,
            interpret=interpret,
        )
        return cls[:q, :g], margin[:q, :g]

    bt, _ = bank_tiling(b, b_tile)
    bp = -(-b // bt) * bt
    Wp = _pad_to(_pad_to(Wf, 128, 1), bp, 0)
    bias = jnp.where(jnp.arange(bp) < b, 0.0, NEG_MASK)[:, None].astype(
        jnp.float32
    )
    if epilogue == "topk":
        vals, ids = predict_bank_pallas(
            Xp, Wp, bias, epilogue="topk", q_block=q_block, b_tile=bt, k=k,
            bank_resident=residency, interpret=interpret,
        )
        return vals[:q], ids[:q]
    scores = predict_bank_pallas(
        Xp, Wp, bias, epilogue="scores", q_block=q_block, b_tile=bt,
        bank_resident=residency, interpret=interpret,
    )
    return scores[:q, :b]


@partial(
    jax.jit,
    static_argnames=(
        "kernel", "epilogue", "n_classes", "k", "q_block",
        "stream_dtype", "interpret",
    ),
)
def predict_kernel_bank(
    X: jax.Array,
    points: jax.Array,
    coef: jax.Array,
    *,
    kernel: str = "rbf",
    gamma=1.0,
    epilogue: str = "scores",
    n_classes: int | None = None,
    k: int | None = None,
    q_block: int = 256,
    stream_dtype=None,
    interpret: bool | None = None,
):
    """Score (Q, D) queries against a kernelized bank's stored core sets.

    The serving twin of ``core.fit_kernel_bank``: ``points`` is the bank's
    (B, S, D) core-set buffer and ``coef`` its (B, S) signed coefficients
    (free slots hold coef == 0, so they contribute exactly nothing). One
    fused Gram launch (``gram``, the same linear/RBF epilogue the trainer
    used) evaluates k(query tile, EVERY model's core set) as a
    (Q, B*S) block; the per-model readout is then the contraction

        scores[qi, bi] = sum_s coef[bi, s] * k(x_qi, points[bi, s])

    which is bit-exact (f32) with ``ref.predict_kernel_bank_ref`` /
    ``kernelized.decision_function`` against the stored core set — the
    train->serve parity contract of the linear ``predict_bank``, carried to
    kernel space. Epilogues mirror ``predict_bank``:

      "scores"          -> (Q, B) f32 margins
      "ovr", n_classes= -> ((Q, G) int32, (Q, G) f32) per C-grid group,
                           G = B // n_classes, class-major flattening
      "topk", k=        -> ((Q, k) f32, (Q, k) int32) descending

    ``gamma`` is traced through the Gram launch — a gamma sweep at serve
    time reuses one compilation, exactly like the C sweep at train time.

    q_block: query rows per Gram tile (BankServer's microbatch slot count).
    stream_dtype: "bf16" rounds the query tiles before the Gram launch; the
    core-set points and coefficients stay f32. The (B, S) state is small by
    construction (that is the point of the core-set bound), so there is no
    bank_resident knob here — the Gram operand is (B*S, D) and already
    streams through the tiled kernel's own block pipeline.
    """
    q, d = X.shape
    b, s, dp = points.shape
    if dp != d:
        raise ValueError(
            f"queries and core-set points must share the feature axis: got "
            f"X.shape={X.shape}, points.shape={points.shape}"
        )
    if coef.shape != (b, s):
        raise ValueError(
            f"coef must be (B, S) matching points: got coef.shape="
            f"{coef.shape}, points.shape={points.shape}"
        )
    if kernel not in ("linear", "rbf"):
        raise ValueError(
            f"unknown kernel {kernel!r}; expected 'linear' or 'rbf'"
        )
    if epilogue not in ("scores", "ovr", "topk"):
        raise ValueError(
            f"unknown epilogue {epilogue!r}; expected 'scores', 'ovr' or "
            "'topk'"
        )
    if epilogue != "ovr" and n_classes is not None:
        raise ValueError(
            f"n_classes={n_classes} requires epilogue='ovr' (got "
            f"epilogue={epilogue!r})"
        )
    if epilogue != "topk" and k is not None:
        raise ValueError(
            f"k={k} requires epilogue='topk' (got epilogue={epilogue!r})"
        )
    if epilogue == "ovr" and (
        n_classes is None or n_classes < 1 or b % n_classes
    ):
        raise ValueError(
            f"epilogue='ovr' needs n_classes >= 1 dividing B: got "
            f"n_classes={n_classes}, B={b}"
        )
    if epilogue == "topk" and (k is None or not (1 <= k <= b)):
        raise ValueError(
            f"epilogue='topk' needs 1 <= k <= B: got k={k}, B={b}"
        )
    sdt = _resolve_stream_dtype(stream_dtype)
    Xq = X.astype(jnp.float32)
    if sdt is not None:
        Xq = Xq.astype(sdt)
    K = gram(
        Xq, points.reshape(b * s, d).astype(jnp.float32),
        epilogue=kernel, gamma=gamma, bm=q_block, interpret=interpret,
    )
    scores = jnp.einsum(
        "qbs,bs->qb", K.reshape(q, b, s), coef.astype(jnp.float32)
    )
    if epilogue == "scores":
        return scores
    if epilogue == "ovr":
        g = b // n_classes
        grouped = scores.reshape(q, g, n_classes)
        cls = jnp.argmax(grouped, axis=-1).astype(jnp.int32)
        margin = jnp.max(grouped, axis=-1)
        return cls, margin
    vals, ids = jax.lax.top_k(scores, k)
    return vals, ids.astype(jnp.int32)

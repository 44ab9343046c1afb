"""Distributed one-pass StreamSVM — beyond-paper mesh parallelism.

The stream is sharded into contiguous ranges across mesh axes; each shard
runs Algorithm 1/2 locally (one pass, O(B*D) state), then shards exchange
their balls with an all_gather and every shard deterministically folds them
with the paper's Sec-4.3 merge operator (exact in the augmented space
because shards touch disjoint slack coordinates — DESIGN.md §5).

Entry points:

``fit_bank_sharded``  a BANK of B models per shard via the tiled multi-ball
                      Pallas engine (one model is B = 1) — M stream shards
                      x B models in ONE data pass each, folded with the
                      bank-vectorized merge (meb.fold_merge over the
                      gathered (S, B, ...) stack, outside the mesh program
                      when called eagerly).
                      Each shard reads its own live row count, so any N
                      works on any shard count.
``fit_kernel_bank_sharded``
                      the KERNELIZED bank per shard (bounded core-set
                      buffers), folded with the kernelized Sec-4.3 merge
                      (meb.merge_kernel_banks: cross-Gram center distance +
                      coreset-of-coresets compression back to S slots).

Communication: one all_gather of B * (D+3) floats per shard, once per stream —
negligible against ICI bandwidth at any B * D that fits in HBM.

The fold is commutative and, up to bounded geometric slack, order-invariant
(any fold order yields an enclosing ball with radius within 2x of the optimum
and center inside the hull of the shard centers — property-tested in
tests/test_sharded_bank.py), so straggler re-assignment / elastic reshard
does not change the model class.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from .kernel_bank import KernelBank, _fit_kernel_bank
from .meb import Ball, fold_merge, merge_banks, merge_kernel_banks


def _mesh_axes(axis: str | Tuple[str, ...]) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _n_shards(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def shard_ranges(n: int, n_shards: int) -> list[Tuple[int, int]]:
    """The canonical ceil-split of ``n`` stream rows into ``n_shards``
    contiguous ``[lo, hi)`` ranges — exactly the ranges ``fit_bank_sharded``
    and ``fit_kernel_bank_sharded`` assign to mesh shards (rows-per-shard
    ``ceil(n / n_shards)``, remainder padded with inert rows on the last
    live shard, trailing shards empty).

    Always returns ``n_shards`` entries; shards past the data get empty
    ``(n, n)`` ranges. The elastic live loop keys its LOGICAL fold structure
    on these ranges, so per-range single-device fits fold bit-identically to
    the mesh fast path regardless of the physical device count.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1: got {n_shards}")
    if n < 0:
        raise ValueError(f"n must be >= 0: got {n}")
    shard_n = -(-n // n_shards) if n else 0
    return [
        (min(j * shard_n, n), min((j + 1) * shard_n, n))
        for j in range(n_shards)
    ]


@partial(
    jax.jit,
    static_argnames=(
        "mesh", "axes", "n_rows", "variant", "lookahead", "block_n",
        "b_tile", "stream_dtype", "bank_resident", "interpret",
    ),
)
def _sharded_fits(
    X, Y, cs, *,
    mesh, axes, n_rows, variant, lookahead, block_n, b_tile, stream_dtype,
    bank_resident, interpret,
):
    """jit'd shard_map core of fit_bank_sharded: one bank fit per shard,
    gathered into an (S, B, ...) stack replicated on every device, NO
    fold. Shard k holds rows ``k * shard_n ..`` of the stream, whose first
    ``n_rows`` are live: each shard's fit reads its own live row count, so
    rows past the stream are inert whatever they hold.

    Module-level so repeated calls with the same (shapes, mesh, config) hit
    the jit cache instead of rebuilding and re-tracing the shard_map closure
    — fit_chunked_many(mesh=...) calls this once per CHUNK.
    """

    def local_fit(Xs, Ys, cs_):
        from repro.kernels.ops import streamsvm_fit_many  # lazy: module cycle

        shard_n = Xs.shape[0]
        sid = jnp.zeros((), jnp.int32)
        for a in axes:
            sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
        bank = streamsvm_fit_many(
            Xs, Ys, cs_, None,
            variant=variant, lookahead=lookahead, block_n=block_n,
            b_tile=b_tile, stream_dtype=stream_dtype,
            bank_resident=bank_resident, interpret=interpret,
            n_valid=jnp.clip(n_rows - sid * shard_n, 0, shard_n),
        )
        return jax.tree.map(
            lambda v: jax.lax.all_gather(v, axes, tiled=False), bank
        )

    fn = _shard_map(
        local_fit,
        mesh=mesh,
        in_specs=(P(axes), P(None, axes), P()),
        out_specs=jax.tree.map(lambda _: P(), Ball(0, 0, 0, 0)),
        check_vma=False,
    )
    return fn(X, Y, cs)


@partial(
    jax.jit,
    static_argnames=(
        "mesh", "axes", "n_shards", "shard_n", "n_rows", "kernel",
        "coreset_size", "eviction", "variant", "block_n", "s_tile",
        "stream_dtype", "interpret",
    ),
)
def _sharded_kernel_fold(
    X, Y, cs, gamma, *,
    mesh, axes, n_shards, shard_n, n_rows, kernel, coreset_size, eviction,
    variant, block_n, s_tile, stream_dtype, interpret,
):
    """jit'd shard_map core of fit_kernel_bank_sharded.

    Module-level for the same jit-cache reason as ``_sharded_fits``. Each
    shard runs the kernelized engine over its contiguous range (the engine's
    DEFERRED seeding makes ranges starting with inert sign-0 rows — or
    entirely padding — correct without special-casing), rewrites its
    buffer's stream indices to GLOBAL coordinates, gathers every shard's
    7-leaf bank, and folds them with the kernelized Sec-4.3 merge. Fully
    padded shards produce m == 0 banks — exact merge identities — and are
    additionally skipped statically (shard liveness is a trace-time
    constant).
    """

    def local_fit(Xs, Ys, cs_, gamma_):
        bank = _fit_kernel_bank(
            Xs, Ys, cs_, gamma_,
            kernel=kernel, coreset_size=coreset_size, eviction=eviction,
            variant=variant, block_n=block_n, s_tile=s_tile,
            stream_dtype=stream_dtype, interpret=interpret,
        )
        # Shard-local buffer indices -> global stream indices (the shards
        # hold contiguous ranges in mesh-axes row-major order, matching the
        # all_gather stacking below). Points were already gathered from the
        # LOCAL rows by the engine, so only idx needs the offset.
        sid = jnp.zeros((), jnp.int32)
        for a in axes:
            sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
        bank = bank._replace(
            idx=jnp.where(bank.idx >= 0, bank.idx + sid * shard_n, bank.idx)
        )
        gather = lambda v: jax.lax.all_gather(v, axes, tiled=False)
        stacked = KernelBank(*(gather(leaf) for leaf in bank))
        take = lambda i: jax.tree.map(lambda x: x[i], stacked)
        live = [i * shard_n < n_rows for i in range(n_shards)]
        acc = None
        for i in range(n_shards):
            if not live[i]:
                continue
            acc = take(i) if acc is None else merge_kernel_banks(
                acc, take(i), kernel=kernel, gamma=gamma_, eviction=eviction
            )
        return acc

    fn = _shard_map(
        local_fit,
        mesh=mesh,
        in_specs=(P(axes), P(None, axes), P(), P()),
        out_specs=jax.tree.map(lambda _: P(), KernelBank(*range(7))),
        check_vma=False,
    )
    return fn(X, Y, cs, gamma)


def fit_kernel_bank_sharded(
    X: jax.Array,
    Y: jax.Array,
    cs,
    mesh: Mesh,
    *,
    axis: str | Tuple[str, ...] = "data",
    kernel: str = "rbf",
    gamma=1.0,
    coreset_size: int = 64,
    eviction: str = "smallest-coef",
    variant: str = "exact",
    block_n: int = 256,
    s_tile: int | None = None,
    stream_dtype=None,
    interpret: bool | None = None,
) -> KernelBank:
    """M stream shards x B kernelized models in one pass each.

    The kernel-space twin of ``fit_bank_sharded``: the stream is split into
    ``n_shards`` contiguous ranges over the ``axis`` axes of ``mesh``; every
    shard runs the tiled core-set engine (``core.fit_kernel_bank``'s jit'd
    core — ``coreset_size``, ``eviction``, ``s_tile``, ``stream_dtype`` all
    apply per shard) over its local range, the per-shard (B, S) banks are
    exchanged with one all_gather (B * S * (D + 2) floats + the ball
    scalars, still independent of N), and every model lane is folded with
    the kernelized Sec-4.3 merge: concatenate core-set buffers, re-compress
    to S slots (coreset-of-coresets), merge (q, r, xi2) with the
    ``merge_balls`` algebra (``meb.merge_kernel_banks``).

    Ragged N is fine: the remainder is padded with inert rows (feature 0,
    sign 0), shard ranges that START with padding seed on their first live
    row (the engine's deferred seeding), and fully-padded shards fold as
    exact m == 0 identities AND are skipped statically. The folded bank's
    ``idx`` leaf carries GLOBAL stream indices, so the result is directly
    comparable with a single-device fit's buffer.

    Numpy oracle for the fold: per-range single-device fits merged with
    ``kernels.ref.merge_kernel_banks_ref`` (tests/test_kernel_merge.py).
    Returns the folded KernelBank, replicated on every device — checkpoint
    it with ``save_kernel_bank`` and ``BankServer.from_checkpoint`` serves
    it bit-exact with ``kernel_bank_decision`` (f32).
    """
    axes = _mesh_axes(axis)
    n_shards = _n_shards(mesh, axes)
    n, d = X.shape
    b = Y.shape[0]
    if Y.shape != (b, n):
        raise ValueError(
            f"Y must be (B, N) sign rows matching X: got Y.shape={Y.shape}, "
            f"X.shape={X.shape}"
        )
    if n < 1:
        raise ValueError(f"need at least one stream row: got X.shape={X.shape}")
    cs = jnp.broadcast_to(jnp.asarray(cs, jnp.float32), (b,))
    gamma = jnp.asarray(gamma, jnp.float32)

    shard_n = -(-n // n_shards)  # rows per shard, ceil
    pad = shard_n * n_shards - n
    if pad:
        # Inert remainder rows: feature 0 AND sign 0 — never seed, violate
        # or absorb, so the padded run folds identically to the ragged
        # ranges.
        X = jnp.pad(X, ((0, pad), (0, 0)))
        Y = jnp.pad(Y, ((0, 0), (0, pad)))
    if not isinstance(X, jax.core.Tracer):  # eager call: place shards up front
        X = jax.device_put(X, NamedSharding(mesh, P(axes)))
        Y = jax.device_put(Y, NamedSharding(mesh, P(None, axes)))
    return _sharded_kernel_fold(
        X, Y, cs, gamma,
        mesh=mesh, axes=axes, n_shards=n_shards, shard_n=shard_n, n_rows=n,
        kernel=kernel, coreset_size=coreset_size, eviction=eviction,
        variant=variant, block_n=block_n, s_tile=s_tile,
        stream_dtype=stream_dtype, interpret=interpret,
    )


@partial(
    jax.jit,
    static_argnames=(
        "mesh", "axes", "n_shards", "shard_n", "kernel", "coreset_size",
        "eviction", "variant", "block_n", "s_tile", "stream_dtype",
        "interpret",
    ),
)
def _sharded_kernel_shards(
    X, Y, cs, gamma, *,
    mesh, axes, n_shards, shard_n, kernel, coreset_size, eviction,
    variant, block_n, s_tile, stream_dtype, interpret,
):
    """jit'd shard_map core of fit_kernel_bank_shards: per-shard fits +
    all_gather, NO in-jit fold. Module-level for the jit-cache reason of
    ``_sharded_kernel_fold``."""

    def local_fit(Xs, Ys, cs_, gamma_):
        bank = _fit_kernel_bank(
            Xs, Ys, cs_, gamma_,
            kernel=kernel, coreset_size=coreset_size, eviction=eviction,
            variant=variant, block_n=block_n, s_tile=s_tile,
            stream_dtype=stream_dtype, interpret=interpret,
        )
        sid = jnp.zeros((), jnp.int32)
        for a in axes:
            sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
        bank = bank._replace(
            idx=jnp.where(bank.idx >= 0, bank.idx + sid * shard_n, bank.idx)
        )
        gather = lambda v: jax.lax.all_gather(v, axes, tiled=False)
        return KernelBank(*(gather(leaf) for leaf in bank))

    fn = _shard_map(
        local_fit,
        mesh=mesh,
        in_specs=(P(axes), P(None, axes), P(), P()),
        out_specs=jax.tree.map(lambda _: P(), KernelBank(*range(7))),
        check_vma=False,
    )
    return fn(X, Y, cs, gamma)


def fit_kernel_bank_shards(
    X: jax.Array,
    Y: jax.Array,
    cs,
    mesh: Mesh,
    *,
    axis: str | Tuple[str, ...] = "data",
    kernel: str = "rbf",
    gamma=1.0,
    coreset_size: int = 64,
    eviction: str = "smallest-coef",
    variant: str = "exact",
    block_n: int = 256,
    s_tile: int | None = None,
    stream_dtype=None,
    interpret: bool | None = None,
) -> KernelBank:
    """Per-shard kernelized fits on the mesh WITHOUT the in-jit fold.

    Returns the STACKED per-shard banks — every KernelBank leaf grows a
    leading ``(n_shards,)`` axis, replicated on every device — with ``idx``
    already rewritten to global stream coordinates. The caller folds them
    however it likes (``meb.merge_kernel_banks`` / ``fold_kernel_banks``),
    typically skipping shards whose range is empty (see ``shard_ranges``).

    Why this exists next to ``fit_kernel_bank_sharded``: the in-jit fold
    fuses the merge interpolation arithmetic differently from the eager
    ``merge_kernel_banks`` chain (last-ulp q/xi2 differences), while the
    per-shard FITS are bit-identical to single-device fits of the same
    ranges. The elastic live loop needs its mesh fast path and its
    per-range degraded path to agree bit-exactly (f32), so it takes the
    stacked banks from here and folds them with the SAME eager merge code
    both paths share. Ragged N pads with inert sign-0 rows exactly like
    ``fit_kernel_bank_sharded``; fully-padded shards come back as exact
    m == 0 identity banks.
    """
    axes = _mesh_axes(axis)
    n_shards = _n_shards(mesh, axes)
    n, d = X.shape
    b = Y.shape[0]
    if Y.shape != (b, n):
        raise ValueError(
            f"Y must be (B, N) sign rows matching X: got Y.shape={Y.shape}, "
            f"X.shape={X.shape}"
        )
    if n < 1:
        raise ValueError(f"need at least one stream row: got X.shape={X.shape}")
    cs = jnp.broadcast_to(jnp.asarray(cs, jnp.float32), (b,))
    gamma = jnp.asarray(gamma, jnp.float32)

    shard_n = -(-n // n_shards)  # rows per shard, ceil (== shard_ranges)
    pad = shard_n * n_shards - n
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
        Y = jnp.pad(Y, ((0, 0), (0, pad)))
    if not isinstance(X, jax.core.Tracer):  # eager call: place shards up front
        X = jax.device_put(X, NamedSharding(mesh, P(axes)))
        Y = jax.device_put(Y, NamedSharding(mesh, P(None, axes)))
    return _sharded_kernel_shards(
        X, Y, cs, gamma,
        mesh=mesh, axes=axes, n_shards=n_shards, shard_n=shard_n,
        kernel=kernel, coreset_size=coreset_size, eviction=eviction,
        variant=variant, block_n=block_n, s_tile=s_tile,
        stream_dtype=stream_dtype, interpret=interpret,
    )


def _place_rows(A, sharding: NamedSharding, axis: int, size: int) -> jax.Array:
    """``A`` split along ``axis`` as ``sharding`` lays out ``size`` (>= A's)
    entries, each device receiving only its own slice; entries past A's end
    are zeros on the device that holds them. So no copy of the whole of A is
    made; an A already laid out so is returned as it is."""
    n = A.shape[axis]
    if n == size:
        return jax.device_put(A, sharding)
    shape = A.shape[:axis] + (size,) + A.shape[axis + 1:]
    parts = []
    for dev, index in sharding.addressable_devices_indices_map(shape).items():
        lo, hi, _ = index[axis].indices(size)
        take = [slice(None)] * A.ndim
        take[axis] = slice(min(lo, n), min(hi, n))
        part = jax.device_put(A[tuple(take)], dev)
        if part.shape[axis] < hi - lo:
            widths = [(0, 0)] * A.ndim
            widths[axis] = (0, hi - lo - part.shape[axis])
            part = jnp.pad(part, widths)
        parts.append(part)
    return jax.make_array_from_single_device_arrays(shape, sharding, parts)


def _fold_device(mesh: Mesh) -> jax.Device:
    """The device an eager fit folds on: the default device where the mesh
    holds it, else the mesh's first."""
    devices = list(mesh.devices.flat)
    default = jax.config.jax_default_device
    if not isinstance(default, jax.Device):
        default = jax.local_devices()[0]
    return default if default in devices else devices[0]


def _replica_on(v: jax.Array, dev: jax.Device) -> jax.Array:
    """The replicated array ``v``'s copy on ``dev`` as a one-device array:
    the replica ``dev`` already holds, read in place, else a copy put
    there."""
    for shard in v.addressable_shards:
        if shard.device == dev:
            return shard.data
    return jax.device_put(v, dev)


def fit_bank_sharded(
    X: jax.Array,
    Y: jax.Array,
    cs,
    mesh: Mesh,
    balls: Ball | None = None,
    *,
    axis: str | Tuple[str, ...] = "data",
    variant: str = "exact",
    lookahead=None,
    block_n: int = 256,
    b_tile: int | None = None,
    stream_dtype=None,
    bank_resident: str = "auto",
    interpret: bool | None = None,
) -> Ball:
    """M stream shards x B models in one pass: the sharded bank engine.

    The stream is split into ``n_shards`` contiguous ranges over the ``axis``
    axes of ``mesh``; every shard runs the tiled multi-ball Pallas engine
    (``kernels.streamsvm_fit_many`` — ``b_tile``, fused ``lookahead``,
    ``stream_dtype="bf16"`` and ``bank_resident`` all apply per shard: each
    device holds its own bank copy, so residency is a per-shard decision
    and "auto" resolves identically on every shard) over its local range, the
    per-shard (B, D) banks are gathered, and every model lane is folded
    with the Sec-4.3 merge (``meb.fold_merge`` over the (S, B, ...) stack).
    Called eagerly, the fold runs on one device, outside the mesh program,
    over the gathered bits as the mesh program left them on that device, so
    the result is bit-identical to single-device fits of the same ranges
    folded in order (``shard_ranges``) on any backend. Total data movement:
    each stream row is read from HBM exactly once, on exactly one shard.

    X: (N, D) stream, Y: (B, N) per-model sign rows, cs: scalar or (B,)
    per-model C (traced). Shard k holds rows ``[k * shard_n, (k + 1) *
    shard_n)`` with ``shard_n = ceil(N / n_shards)`` (``shard_ranges``), and
    its fit reads its own live row count, so rows past the stream are inert
    whatever they hold. For N divisible by the shard count X and Y pass
    through untouched: an X already split by rows over the mesh is read in
    place, with no copy of the stream. ``N % n_shards != 0`` is fine too:
    called eagerly, each device receives only its own rows (the ragged last
    shard filled up to ``shard_n`` rows on its own device), never a padded
    copy of the whole stream; under a trace the stream is padded in the
    program. Shards with no live row are left out of the fold, so the
    result is identical to folding the ragged ranges. (Every live shard's
    first row — its engine init example — is a real stream row, so the init
    caveat on ``streamsvm_fit_many`` never triggers here.)

    Called eagerly, the fit runs as four phases, each a
    ``jax.profiler.TraceAnnotation`` span that ends when its result is
    ready (points where the fit waits anyway):

    - ``fit.shards``: the mesh program, dispatch until its stacked (S, B,
      ...) banks are ready. Its arguments ``gram_fills`` and
      ``tile_visits`` count a shard's data blocks, where the engine fills
      the block Gram, and its (block, bank tile) visits
      (``kernels.ops.bank_engine_grid``);
    - ``fit.gather``: the fold device's replica of the stacked banks (the
      default device where the mesh holds it, else the mesh's first), read
      in place, with the live shards sliced off on that device. Its
      argument ``moved_bytes`` counts the bytes copied to bring the stack
      there: 0 when the replica is read in place;
    - ``fit.fold``: ``fold_merge`` of the live shards on that device, a
      program of its own (the one every per-range fold runs), which is
      what makes the result exact;
    - ``fit.place``: the folded bank replicated on the mesh.

    ``balls`` (a stacked bank) continues a previous fit: shards fit their
    ranges FRESH (keeping shard example-sets disjoint, which the merge's
    slack orthogonality needs) and the prior bank is folded in at the end —
    this is what makes checkpoint/resume under a mesh shard-count agnostic.

    Returns the folded bank (Ball stacked on B), replicated on every device.
    """
    axes = _mesh_axes(axis)
    n_shards = _n_shards(mesh, axes)
    n, d = X.shape
    b = Y.shape[0]
    if Y.shape != (b, n):
        raise ValueError(
            f"Y must be (B, N) sign rows matching X: got Y.shape={Y.shape}, "
            f"X.shape={X.shape}"
        )
    if n < 1:
        raise ValueError(f"need at least one stream row: got X.shape={X.shape}")
    cs = jnp.broadcast_to(jnp.asarray(cs, jnp.float32), (b,))
    if isinstance(lookahead, list):  # static arg below: must be hashable
        lookahead = tuple(lookahead)

    shard_n = -(-n // n_shards)  # rows per shard, ceil
    if isinstance(X, jax.core.Tracer) or isinstance(Y, jax.core.Tracer):
        pad = shard_n * n_shards - n
        if pad:
            X = jnp.pad(X, ((0, pad), (0, 0)))
            Y = jnp.pad(Y, ((0, 0), (0, pad)))
    else:  # eager call: place shards up front, each device its own rows
        X = _place_rows(X, NamedSharding(mesh, P(axes)), 0, shard_n * n_shards)
        Y = _place_rows(Y, NamedSharding(mesh, P(None, axes)), 1,
                        shard_n * n_shards)
    fits = partial(
        _sharded_fits,
        mesh=mesh, axes=axes, n_rows=n, variant=variant, lookahead=lookahead,
        block_n=block_n, b_tile=b_tile, stream_dtype=stream_dtype,
        bank_resident=bank_resident, interpret=interpret,
    )
    # Shards with no live row (a suffix) stay out of the fold.
    n_live = -(-n // shard_n)
    if isinstance(X, jax.core.Tracer):
        stacked = fits(X, Y, cs)
        folded = fold_merge(jax.tree.map(lambda v: v[:n_live], stacked))
    else:
        # Eager call: fold the per-shard banks off the mesh program, on one
        # device, with the same fold program per-range single-device fits
        # are folded with; then replicate. Compiled into the mesh program,
        # XLA may fuse the merge arithmetic differently, which on a TPU
        # moves the last bits of the result. Exactness comes from that
        # one-device fold reading the gathered bits, which the mesh program
        # left on every mesh device: the fold reads its own device's
        # replica in place, with no copy through the host.
        from repro.kernels.ops import bank_engine_grid  # lazy: module cycle

        blocks, tiles = bank_engine_grid(
            shard_n, b, d, variant=variant, lookahead=lookahead,
            block_n=block_n, b_tile=b_tile, stream_dtype=stream_dtype,
            bank_resident=bank_resident, x_dtype=X.dtype,
        )
        with jax.profiler.TraceAnnotation(
            "fit.shards", gram_fills=blocks, tile_visits=blocks * tiles
        ):
            stacked = jax.block_until_ready(fits(X, Y, cs))
        dev = _fold_device(mesh)
        moved = sum(v.nbytes for v in jax.tree.leaves(stacked)
                    if dev not in v.devices())
        with jax.profiler.TraceAnnotation("fit.gather", moved_bytes=moved):
            local = jax.tree.map(lambda v: _replica_on(v, dev), stacked)
            if n_live < n_shards:
                local = jax.tree.map(lambda v: v[:n_live], local)
            local = jax.block_until_ready(local)
        with jax.profiler.TraceAnnotation("fit.fold"):
            folded = jax.block_until_ready(fold_merge(local))
        with jax.profiler.TraceAnnotation("fit.place"):
            folded = jax.block_until_ready(jax.tree.map(
                lambda v: jax.device_put(v, NamedSharding(mesh, P())), folded))
    if balls is not None:
        # The prior bank saw a disjoint (earlier) slice of the stream, so it
        # merges exactly like one more shard.
        prior = Ball(
            w=jnp.asarray(balls.w, jnp.float32),
            r=jnp.broadcast_to(jnp.asarray(balls.r, jnp.float32), (b,)),
            xi2=jnp.broadcast_to(jnp.asarray(balls.xi2, jnp.float32), (b,)),
            m=jnp.broadcast_to(jnp.asarray(balls.m, jnp.int32), (b,)),
        )
        if not isinstance(prior.w, jax.core.Tracer):
            # A checkpoint may come from a run on a DIFFERENT mesh (elastic
            # reshard); re-place it on this mesh so the merge has one device
            # set.
            prior = jax.tree.map(
                lambda v: jax.device_put(v, NamedSharding(mesh, P())), prior
            )
        folded = merge_banks(prior, folded)
    return folded

"""LiveBank — the always-on ingest -> train -> fold -> hot-swap loop.

Closes the loop between the one-pass trainer (``core.fit_bank`` /
``fit_chunked_many``) and the serving engine (``serve.BankServer``): consume
an unbounded stream of ``(X_chunk, y_chunk)`` chunks, train each into the
active sub-bank through the tiled Pallas engine, fold the sub-banks with the
bank-vectorized Sec-4.3 merge, and hot-swap the merged bank into a running
server on a cadence — checkpointed, restartable, and drift-repairing.

``bank_kind="kernel"`` runs the same loop in RKHS: chunks train through
``core.fit_kernel_bank`` into bounded (B, S) core-set sub-banks, each
arriving chunk Sec-4.3-merges into the active slot's prior state
(``merge_kernel_banks`` — exact while the live slots fit S, then lossy
top-k re-compression whose dropped |coef| mass is audited in
``LiveStats.merge_dropped_mass``), retirement re-merges kernel epochs, and
the serving fold goes through ``fold_kernel_banks`` over the live slots,
oldest first. Everything else — cadences, checkpoints, crash equivalence —
is bank-kind agnostic.

K-sub-bank drift-repair contract
--------------------------------
The paper's one-pass recursion is stream-order sensitive: a single greedy
ball absorbs every point into an ever-growing radius, so early data shapes
the center forever and late drift is diluted. The repair (blurred-ball
cover, "Accurate Streaming SVMs", PAPERS.md) keeps a small COVER of balls
instead of one:

  - the stream is cut into epochs of ``rotate_every`` chunks; each epoch
    trains its OWN fresh sub-bank (Algorithm 1 from scratch — per model,
    a ball enclosing just that epoch's augmented points);
  - the serving bank is the Sec-4.3 fold of the <= K live sub-banks,
    oldest first (``core.fold_banks``) — exact in the augmented space
    because epochs touch disjoint examples;
  - when all K slots are full, the OLDEST sub-bank is retired:
    ``retire="merge"`` re-merges the two oldest into one (no example's
    influence is dropped — the cover coarsens at the old end, blurred-ball
    style), ``retire="drop"`` forgets the oldest epoch outright (bounded
    memory of the last ~K * rotate_every chunks — concept-drift adaptation).

Bound: each sub-ball encloses its epoch's points by the Algorithm-1
invariant, and every fold/merge yields a ball enclosing both inputs with
radius within 2x of the optimal enclosing ball (property-tested bounds in
tests/test_sharded_bank.py). Order sensitivity is therefore confined WITHIN
an epoch (``rotate_every`` chunks of lookback); across epochs the cover
re-merges from small balls instead of absorbing points one by one — drift
in a new epoch lands in a fresh ball at full weight rather than nudging a
giant stale center.

Fault tolerance
---------------
Every fold commits an atomic ``StreamCheckpoint`` (checkpoint/ckpt.py:
manifest-commit protocol — a crash at any instant leaves the previous or
the new checkpoint, never a torn mix). ``run()`` always resumes from the
last durable checkpoint, and the source is addressed by absolute chunk
index (see sources.py), so a crash at ANY phase boundary replays to a
bit-identical (f32) bank: train/fold/swap are pure functions of
(checkpoint state, chunk index). Flaky fetches retry under a
``runtime.RetryPolicy`` (capped exponential backoff); chunks that exhaust
the budget are quarantined — recorded, skipped, and the loop moves on.
The server is decoupled: while the trainer crashes and recovers, an
attached ``BankServer`` keeps answering with the last good bank, and
``LiveStats.bank_age_chunks`` reports how stale it is.

Elastic sharded training
------------------------
``mesh=`` / ``n_stream_shards=`` turn per-chunk training into mesh
training that tolerates losing or gaining devices mid-stream. The key
split is LOGICAL vs PHYSICAL:

  - ``n_stream_shards`` (durable in every checkpoint) fixes the chunk's
    fold STRUCTURE: each chunk is ceil-split into that many contiguous
    ranges (``core.shard_ranges``), fit fresh per range, and folded in
    ascending-range order with the eager Sec-4.3 merges; the active
    slot's prior state merges in last. This structure never depends on
    hardware.
  - the physical mesh only decides WHERE the range fits execute. When
    the device count equals the logical shard count and the chunk is
    fault-free, one mesh dispatch runs all ranges at once
    (``core.fit_bank_sharded`` for linear; ``core.fit_kernel_bank_shards``
    — per-shard fits gathered WITHOUT the in-jit fold — for kernel); any
    other device count, including none, falls back to per-range
    single-device fits. Both paths are bit-identical (f32), so a
    checkpoint written on 8 devices resumes bit-exactly on 4, 1, or 16
    (the ``remeshes`` counter records the transition).

Mid-chunk shard faults degrade gracefully instead of killing the loop: a
lost device or declared straggler (``StragglerPolicy`` over per-shard
heartbeats) has its range re-issued to the surviving shards
(``runtime.rebalance_ranges``; counted in ``ranges_reissued``), and a
shard whose fetch faults exhaust the per-shard retry budget is masked out
through the inert-range contract — its rows are recorded in
``LiveStats.rows_lost`` / ``shard_ranges_lost`` and the fold simply skips
the range. The chaos harness (live/chaos.py) proves process kills and
remesh events are INVISIBLE: final bank, served scores, and durable stats
bit-identical (f32) to the crash-free reference under the same shard-
fault plan.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core.distributed import (
    _mesh_axes,
    _n_shards,
    fit_bank_sharded,
    fit_kernel_bank_shards,
    shard_ranges,
)
from repro.core.kernel_bank import KernelBank, fit_kernel_bank
from repro.core.meb import (
    Ball,
    fold_banks,
    fold_kernel_banks,
    merge_banks,
    merge_kernel_banks,
    nonfinite_rows,
    stack_banks,
    fold_merge,
)
from repro.core.multiball import fit_bank
from repro.runtime.fault_tolerance import (
    DeviceLostError,
    InjectedFailure,
    RetryPolicy,
    StragglerPolicy,
    default_live_retryable,
    rebalance_ranges,
    runtime_device_errors,
)

from .sources import TransientSourceError

# fetch() sentinels: stream exhausted / chunk abandoned after retries
_END = object()
_QUARANTINED = object()
# "server has no kernel attribute" sentinel for duck-typed swap targets
_NO_KERNEL_ATTR = object()

PHASES = (
    "fetch", "post_train", "post_rotate", "post_fold", "post_swap",
    "mid_checkpoint", "post_checkpoint",
)


@dataclasses.dataclass
class LiveStats:
    """Trainer-side staleness/health surface, mirroring serve.ServerStats.

    Durable counters (restored from the checkpoint on restart, so a crashy
    run's final accounting matches the uninterrupted run's): chunks/rows
    ingested, folds, swaps, rotations, retirements, checkpoints, the
    quarantined chunk ids, ``last_swap_chunk``, and — for kernelized loops
    — ``merge_dropped_mass``: the total |coef| mass every 2S->S kernel-
    merge re-compression has discarded (chunk continuation merges, retire
    merges, and counted serving folds; exactly 0.0 while the live slots
    always fit S — the re-compression loss audit).

    The elastic sharded loop adds durable loss/repair accounting —
    derived from the deterministic shard-fault plan, so a crash replay
    re-derives them identically:

    ``rows_lost``          stream rows masked out because their shard's
                           fetch faults exhausted the shard retry budget.
    ``shard_ranges_lost``  how many assigned ranges those rows spanned.
    ``ranges_reissued``    lost/straggler shard ranges re-issued to
                           survivors via ``runtime.rebalance_ranges``.
    ``folds_quarantined``  serving folds refused by the non-finite
                           publish guard (NaN/Inf model rows) — the
                           server kept the last good bank.

    Volatile counters (facts about THIS process's life, never restored):
    ``restarts``, ``retries``, ``shard_retries`` (per-shard fetch retries
    burned), and ``remeshes`` (resumes whose physical mesh differed from
    the mesh that wrote the checkpoint). ``bank_age_chunks`` is the
    staleness signal: chunks ingested since the served bank was last
    swapped.
    """

    chunks_ingested: int = 0
    rows_ingested: int = 0
    folds: int = 0
    swaps: int = 0
    rotations: int = 0
    retirements: int = 0
    checkpoints: int = 0
    quarantined: List[int] = dataclasses.field(default_factory=list)
    last_swap_chunk: int = -1
    merge_dropped_mass: float = 0.0
    rows_lost: int = 0
    shard_ranges_lost: int = 0
    ranges_reissued: int = 0
    folds_quarantined: int = 0
    bank_age_chunks: int = 0
    restarts: int = 0
    retries: int = 0
    shard_retries: int = 0
    remeshes: int = 0

    _DURABLE = (
        "chunks_ingested", "rows_ingested", "folds", "swaps", "rotations",
        "retirements", "checkpoints", "quarantined", "last_swap_chunk",
        "merge_dropped_mass", "rows_lost", "shard_ranges_lost",
        "ranges_reissued", "folds_quarantined",
    )

    def durable(self) -> dict:
        return {k: getattr(self, k) for k in self._DURABLE}

    def load_durable(self, d: dict) -> None:
        for k in self._DURABLE:
            if k in d:
                setattr(self, k, d[k])


class LiveBank:
    """Continuous train->serve driver over a replayable chunk source.

    source:        ``source(i) -> (X, y) | None`` — absolute-chunk-index
                   addressing; must replay (sources.py documents the
                   contract). ``y`` is (n,) shared labels or (B, n) signs.
    cs:            (B,) per-model C values (scalar broadcasts).
    n_sub_banks:   K rotating sub-bank slots (drift-repair cover size).
    rotate_every:  chunks per sub-bank epoch before rotation.
    swap_every:    chunks between fold + hot-swap pushes.
    retire:        "merge" (re-merge two oldest, keep everything) or
                   "drop" (forget the oldest epoch) when slots exhaust.
    ckpt_dir:      StreamCheckpoint directory; ``run()`` resumes from it.
    checkpoint_every_folds: folds per checkpoint commit (0 disables — then
                   a restart replays the stream from chunk 0).
    server / server_factory: hot-swap target. ``server_factory(bank)`` is
                   called at the first fold to build one (e.g.
                   ``lambda b: BankServer(b)``); an existing server can be
                   passed or attached any time with ``attach_server``.
    retry:         RetryPolicy classifying fetch failures (default:
                   TransientSourceError/OSError/TimeoutError retry with
                   capped exponential backoff; others propagate). Chunks
                   exhausting the budget are quarantined and skipped.
    failpoints:    crash-injection hooks for tests: a set of
                   ``(phase, chunk_idx)`` pairs (phase in PHASES); each
                   fires ONCE, raising InjectedFailure at that boundary.
                   ``mid_checkpoint`` additionally drops a garbage
                   ``.tmp`` into ckpt_dir first — the exact debris an
                   OS-level crash mid-commit leaves behind.
    bank_kind:     "linear" (Ball sub-banks via ``core.fit_bank``) or
                   "kernel" (KernelBank sub-banks via
                   ``core.fit_kernel_bank``; each chunk fits fresh with
                   ``seed_check=False`` and Sec-4.3-merges into the active
                   slot — core-set ids are lifted to absolute stream
                   coordinates so resume replays bit-exactly).
    kernel/gamma/coreset_size/eviction/s_tile: the kernel-engine knobs
                   (``core.fit_kernel_bank``); used only when
                   ``bank_kind="kernel"``. The same kernel/gamma/eviction
                   drive every retire merge and serving fold, and are
                   persisted in the checkpoint meta (the
                   ``save_kernel_bank`` meta contract, so
                   ``BankServer.from_checkpoint`` reads them back).
    mesh / shard_axis: train each chunk across this device mesh (the
                   elastic sharded path — see the module docstring).
                   When the mesh's device count equals the logical shard
                   count and a chunk is fault-free, training is one mesh
                   dispatch (``fit_bank_sharded`` / the stacked-shards
                   kernel path); otherwise ranges fit per-device,
                   bit-identically. With a mesh (or n_stream_shards > 1)
                   the linear loop switches from in-engine continuation
                   to fresh-fit + Sec-4.3 prior merge — the shard-count-
                   agnostic semantics an elastic resume needs.
    n_stream_shards: the LOGICAL shard count — fixes each chunk's fold
                   structure, durable in every checkpoint. Defaults to
                   the mesh's device count (or 1 without a mesh). A
                   resumed loop that did not set it explicitly ADOPTS
                   the checkpoint's value, which is what makes an
                   8 -> 4 -> 1 remesh bit-exact; setting it explicitly
                   to a different value than the checkpoint raises.
    shard_faults:  a ``sources.ShardFaults`` plan (or duck-typed
                   equivalent) injecting per-(chunk, shard) device-loss /
                   straggler / fetch faults — the chaos-testing surface.
    shard_retry:   RetryPolicy for per-shard fetch faults (default:
                   transient source / OS / timeout / device-lost errors,
                   2 retries). Past the budget the shard's assigned
                   ranges are masked out and recorded in ``rows_lost``.
    straggler_policy: ``runtime.StragglerPolicy`` applied to the fault
                   plan's per-shard elapsed times; declared stragglers
                   are re-issued like lost shards.
    rotate_on:     optional ``rotate_on(stats) -> bool`` extra rotation
                   trigger, composing (OR) with ``rotate_every`` — e.g.
                   fire on a ``merge_dropped_mass`` spike. Evaluated
                   after every ingested chunk; keep it a pure function
                   of DURABLE stats so a crash replay re-fires rotations
                   identically (replay stability).
    strict_finite: non-finite publish guard mode. A serving fold with
                   NaN/Inf in any model row is never hot-swapped; by
                   default it is quarantined (``folds_quarantined``
                   counts it, the server keeps the last good bank) —
                   ``strict_finite=True`` raises a ValueError naming the
                   offending model rows instead.
    Engine kwargs (variant/block_n/b_tile/stream_dtype/bank_resident/
    interpret) pass straight through to ``core.fit_bank`` (the kernel
    engine takes all but b_tile/bank_resident, which are linear-engine
    knobs).
    """

    def __init__(
        self,
        source: Callable,
        cs,
        *,
        ckpt_dir: str,
        n_sub_banks: int = 4,
        rotate_every: int = 8,
        swap_every: int = 1,
        retire: str = "merge",
        checkpoint_every_folds: int = 1,
        server=None,
        server_factory: Optional[Callable] = None,
        retry: Optional[RetryPolicy] = None,
        failpoints: Optional[Sequence[Tuple[str, int]]] = None,
        sleep: Callable[[float], None] = time.sleep,
        bank_kind: str = "linear",
        kernel: str = "rbf",
        gamma=1.0,
        coreset_size: int = 64,
        eviction: str = "smallest-coef",
        s_tile: Optional[int] = None,
        # elastic sharded training
        n_stream_shards: Optional[int] = None,
        shard_faults=None,
        shard_retry: Optional[RetryPolicy] = None,
        straggler_policy: Optional[StragglerPolicy] = None,
        # cadence / publish hooks
        rotate_on: Optional[Callable[[LiveStats], bool]] = None,
        strict_finite: bool = False,
        # engine passthrough
        variant: str = "exact",
        block_n: int = 256,
        b_tile: Optional[int] = None,
        stream_dtype=None,
        bank_resident: str = "auto",
        mesh=None,
        shard_axis="data",
        interpret: Optional[bool] = None,
    ):
        if bank_kind not in ("linear", "kernel"):
            raise ValueError(
                f"bank_kind must be 'linear' or 'kernel': got {bank_kind!r}"
            )
        if n_sub_banks < 1:
            raise ValueError(f"n_sub_banks must be >= 1: got {n_sub_banks}")
        if rotate_every < 1:
            raise ValueError(f"rotate_every must be >= 1: got {rotate_every}")
        if swap_every < 1:
            raise ValueError(f"swap_every must be >= 1: got {swap_every}")
        if retire not in ("merge", "drop"):
            raise ValueError(
                f"retire must be 'merge' or 'drop': got {retire!r}"
            )
        for fp in failpoints or ():
            if fp[0] not in PHASES:
                raise ValueError(
                    f"unknown failpoint phase {fp[0]!r}; expected one of "
                    f"{PHASES}"
                )
        self.source = source
        self.cs = jnp.atleast_1d(jnp.asarray(cs, jnp.float32))
        self.n_models = int(self.cs.shape[0])
        self.ckpt_dir = ckpt_dir
        self.k = int(n_sub_banks)
        self.rotate_every = int(rotate_every)
        self.swap_every = int(swap_every)
        self.retire = retire
        self.checkpoint_every_folds = int(checkpoint_every_folds)
        self.server = server
        self.server_factory = server_factory
        self.retry = retry or RetryPolicy(
            retryable=(TransientSourceError, OSError, TimeoutError),
            max_retries=4,
        )
        # a SET passed in is kept by reference (not copied): the chaos
        # driver shares one failpoint set across relaunches so every kill
        # fires exactly once per run, not once per process
        self._failpoints: Set[Tuple[str, int]] = (
            failpoints if isinstance(failpoints, set)
            else set(failpoints or ())
        )
        self._sleep = sleep
        self.mesh = mesh
        self.shard_axis = shard_axis
        self._shards_explicit = n_stream_shards is not None
        if n_stream_shards is None:
            n_stream_shards = self._mesh_devices() or 1
        if n_stream_shards < 1:
            raise ValueError(
                f"n_stream_shards must be >= 1: got {n_stream_shards}"
            )
        self.n_stream_shards = int(n_stream_shards)
        self.shard_faults = shard_faults
        self.shard_retry = shard_retry or RetryPolicy(
            retryable=(
                TransientSourceError, OSError, TimeoutError, DeviceLostError,
            ) + runtime_device_errors(),
            max_retries=2,
        )
        self.straggler_policy = straggler_policy
        self.rotate_on = rotate_on
        self.strict_finite = bool(strict_finite)
        self.bank_kind = bank_kind
        self.kernel = kernel if bank_kind == "kernel" else None
        self.gamma = float(gamma)
        self.coreset_size = int(coreset_size)
        self.eviction = eviction
        if bank_kind == "kernel":
            # fail fast on a bad kernel config instead of at the first chunk
            if kernel not in ("rbf", "linear"):
                raise ValueError(
                    f"unknown kernel {kernel!r}; expected 'rbf' or 'linear'"
                )
            if eviction not in ("smallest-coef", "farthest-point"):
                raise ValueError(
                    f"unknown eviction {eviction!r}; expected 'smallest-coef'"
                    " or 'farthest-point'"
                )
            if self.coreset_size < 1:
                raise ValueError(
                    f"coreset_size must be >= 1, got {coreset_size}"
                )
            # seed_check=False: a mid-stream continuation chunk has no
            # "row 0 seeds every model" contract (deferred seeding is exact).
            # mesh/shard_axis are NOT in the engine kwargs: the elastic
            # trainer owns placement (per-range fits must run single-device
            # so the degraded path stays bit-identical to the mesh path).
            self._engine_kw = dict(
                kernel=kernel, gamma=self.gamma,
                coreset_size=self.coreset_size, eviction=eviction,
                variant=variant, block_n=block_n, s_tile=s_tile,
                stream_dtype=stream_dtype, interpret=interpret,
                seed_check=False,
            )
            self._merge_kw = dict(
                kernel=kernel, gamma=self.gamma, eviction=eviction
            )
        else:
            self._engine_kw = dict(
                variant=variant, block_n=block_n, b_tile=b_tile,
                stream_dtype=stream_dtype, bank_resident=bank_resident,
                interpret=interpret,
            )
            self._merge_kw = {}
        self.stats = LiveStats()
        self._reset_state()

    # -- state ---------------------------------------------------------------

    def _mesh_devices(self) -> Optional[int]:
        """Physical device count across the training axes (None: no mesh)."""
        if self.mesh is None:
            return None
        return _n_shards(self.mesh, _mesh_axes(self.shard_axis))

    def _mesh_shape(self) -> Optional[List[int]]:
        """Per-axis device counts of the training mesh, for checkpoint meta
        (json-stable list; None without a mesh)."""
        if self.mesh is None:
            return None
        return [int(self.mesh.shape[a]) for a in _mesh_axes(self.shard_axis)]

    def _reset_state(self) -> None:
        self._slots: List[Optional[object]] = [None] * self.k  # Ball|KernelBank
        self._birth: List[int] = [0] * self.k
        self._active: int = 0
        self.chunk_idx: int = 0
        self._folds_since_ckpt: int = 0
        self._last_merged = None
        self._fold_dropped: float = 0.0  # |coef| mass the LAST fold cut
        # reset durable counters without touching volatile ones (restarts,
        # retries, bank_age are facts about this process, not the stream)
        self.stats.load_durable(LiveStats().durable())

    def _state_tree(self) -> dict:
        ref = next(s for s in self._slots if s is not None)
        zero = jax.tree.map(jnp.zeros_like, ref)
        slots = [s if s is not None else zero for s in self._slots]
        return {
            "birth": jnp.asarray(self._birth, jnp.int32),
            "live": jnp.asarray(
                [s is not None for s in self._slots], bool
            ),
            # stack every sub-bank leaf on a NEW leading K axis — works for
            # Ball (w (K,B,D), r, xi2, m) and KernelBank (idx (K,B,S), ...)
            "sub": jax.tree.map(lambda *xs: jnp.stack(xs), *slots),
        }

    def _resume_from_disk(self) -> None:
        """Disk is the source of truth at run() entry: reset in-memory state
        and reload the last durable StreamCheckpoint (if any) — the restart
        path after a crash, and a no-op-equivalent on a fresh start."""
        self._reset_state()
        if not ckpt.exists(self.ckpt_dir):
            return
        manifest = ckpt.load_manifest(self.ckpt_dir)
        meta = manifest["meta"]
        if meta.get("live_k") != self.k or meta.get("n_models") != self.n_models:
            raise ValueError(
                f"checkpoint at {self.ckpt_dir!r} was written by a live loop "
                f"with K={meta.get('live_k')}, B={meta.get('n_models')}; this "
                f"loop is configured K={self.k}, B={self.n_models} — resume "
                "needs a matching configuration"
            )
        ck_kind = meta.get("bank_kind", "linear")
        if ck_kind != self.bank_kind:
            raise ValueError(
                f"checkpoint at {self.ckpt_dir!r} holds bank_kind={ck_kind!r} "
                f"state; this loop is configured bank_kind={self.bank_kind!r}"
                " — linear Ball and kernelized core-set states are not "
                "interchangeable"
            )
        if self.bank_kind == "kernel":
            ck_cfg = {
                key: meta.get(key)
                for key in ("kernel", "gamma", "coreset_size", "eviction")
            }
            cfg = {
                "kernel": self.kernel, "gamma": self.gamma,
                "coreset_size": self.coreset_size, "eviction": self.eviction,
            }
            if ck_cfg != cfg:
                raise ValueError(
                    f"checkpoint at {self.ckpt_dir!r} was written with "
                    f"kernel config {ck_cfg}; this loop is configured {cfg} "
                    "— a resumed kernel stream needs the exact same kernel, "
                    "gamma, coreset size and eviction policy"
                )
        # The LOGICAL shard count is durable: it pins every chunk's fold
        # structure, so it must survive any physical remesh. An explicit
        # mismatch is a configuration error; an implicit (mesh-derived or
        # defaulted) count ADOPTS the checkpoint's — the elastic resume.
        ck_shards = int(meta.get("n_stream_shards", 1))
        if self._shards_explicit and ck_shards != self.n_stream_shards:
            raise ValueError(
                f"checkpoint at {self.ckpt_dir!r} was written with "
                f"n_stream_shards={ck_shards}; this loop explicitly set "
                f"n_stream_shards={self.n_stream_shards} — the logical "
                "shard count pins the per-chunk fold structure and cannot "
                "change mid-stream (the PHYSICAL mesh can: pass a different "
                "mesh=, or omit n_stream_shards to adopt the checkpoint's)"
            )
        self.n_stream_shards = ck_shards
        if meta.get("mesh_shape") != self._mesh_shape():
            # volatile: an elastic remesh happened between processes
            self.stats.remeshes += 1
        # leaf order of the state dict (sorted keys, then NamedTuple field
        # order): birth (K,), live (K,), then the stacked sub-bank leaves —
        # Ball (w (K,B,D), r, xi2, m) or KernelBank (idx (K,B,S), coef,
        # points, q, r, xi2, m)
        head = ckpt.zeros_like_manifest(manifest, 0, 2)
        sub_cls = KernelBank if self.bank_kind == "kernel" else Ball
        target = {
            "birth": head[0],
            "live": head[1].astype(bool),
            "sub": sub_cls(*ckpt.zeros_like_manifest(manifest, 2)),
        }
        # Re-place the restored sub-banks on the CURRENT mesh, replicated —
        # a checkpoint written under any device count restores onto this
        # one (placement is a property of the restore call, not the file).
        shardings = (
            ckpt.replicated_shardings(target, self.mesh)
            if self.mesh is not None else None
        )
        state = ckpt.restore(self.ckpt_dir, target, shardings=shardings)
        live = np.asarray(state["live"])
        self._birth = [int(b) for b in np.asarray(state["birth"])]
        self._slots = [
            jax.tree.map(lambda x, i=i: x[i], state["sub"]) if live[i] else None
            for i in range(self.k)
        ]
        self._active = int(meta["active_slot"])
        self.chunk_idx = int(meta["chunk_idx"])
        self.stats.load_durable(meta["stats"])
        if any(s is not None for s in self._slots):
            merged = self._merged()
            # the resume fold is uncounted; a poisoned restored state keeps
            # _last_merged at None so nothing non-finite ever gets served
            if merged is not None and not bool(jnp.any(nonfinite_rows(merged))):
                self._last_merged = merged

    def _checkpoint(self, i: int) -> None:
        if all(s is None for s in self._slots):
            return  # nothing durable yet (e.g. every chunk so far quarantined)
        self._failpoint("mid_checkpoint", i, torn_tmp=True)
        # Count the commit in the meta it rides in: restoring checkpoint N
        # must report N checkpoints, or every restart would lose one.
        self.stats.checkpoints += 1
        meta = {
            "chunk_idx": self.chunk_idx,
            "active_slot": self._active,
            "live_k": self.k,
            "n_models": self.n_models,
            "bank_kind": self.bank_kind,
            # elastic contract: the LOGICAL fold structure is durable, the
            # physical mesh shape is informational (remesh detection)
            "n_stream_shards": self.n_stream_shards,
            "mesh_shape": self._mesh_shape(),
            "stats": self.stats.durable(),
        }
        if self.bank_kind == "kernel":
            # the save_kernel_bank meta contract — what
            # BankServer.from_checkpoint reads kernel config back from
            meta.update(
                kernel=self.kernel, gamma=self.gamma,
                coreset_size=self.coreset_size, eviction=self.eviction,
            )
        ckpt.save(self.ckpt_dir, self._state_tree(), meta=meta)
        self._folds_since_ckpt = 0
        self._failpoint("post_checkpoint", i)

    # -- failure injection ---------------------------------------------------

    def _failpoint(self, phase: str, i: int, torn_tmp: bool = False) -> None:
        key = (phase, i)
        if key not in self._failpoints:
            return
        self._failpoints.discard(key)  # fire once: the restart sails past
        if torn_tmp:
            # The debris an OS crash mid-commit leaves under the atomic
            # protocol: a half-written arrays tmp nothing references. The
            # resume path must shrug it off and restore the previous commit.
            with open(
                os.path.join(self.ckpt_dir, "arrays-torn.npz.tmp"), "wb"
            ) as f:
                f.write(b"\x00garbage, not a zip")
        raise InjectedFailure(f"injected at {phase} of chunk {i}")

    # -- ingest --------------------------------------------------------------

    def _fetch(self, i: int):
        attempt = 0
        while True:
            try:
                chunk = self.source(i)
            except Exception as e:
                if not self.retry.is_retryable(e):
                    raise  # programming error: surface it
                if attempt >= self.retry.max_retries:
                    self.stats.quarantined.append(i)
                    return _QUARANTINED
                self._sleep(self.retry.delay(attempt))
                attempt += 1
                self.stats.retries += 1
                continue
            return _END if chunk is None else chunk

    # -- train / fold / swap -------------------------------------------------

    def _train(self, X, y) -> int:
        Xc = jnp.asarray(X)
        yc = jnp.asarray(y)
        if yc.ndim == 1:
            yc = jnp.broadcast_to(yc[None, :], (self.n_models, yc.shape[0]))
        n = int(Xc.shape[0])
        if self.n_stream_shards == 1 and self.mesh is None:
            self._train_single(Xc, yc)
        else:
            self._train_elastic(Xc, yc, n)
        return n

    def _train_single(self, Xc, yc) -> None:
        """The legacy single-device chunk path (no mesh, one logical shard):
        linear chunks CONTINUE the active slot inside the engine; kernel
        chunks fit fresh and Sec-4.3-merge into the prior."""
        prior = self._slots[self._active]
        if self.bank_kind == "kernel":
            bank = fit_kernel_bank(Xc, yc, self.cs, **self._engine_kw)
            # Lift the chunk-local core-set ids to ABSOLUTE stream
            # coordinates. rows_ingested is durable and not yet advanced for
            # this chunk, so a crash-replayed chunk re-derives the identical
            # offset — the id lift is replay-stable, hence bit-exact resume.
            offset = self.stats.rows_ingested
            bank = bank._replace(
                idx=jnp.where(bank.idx >= 0, bank.idx + offset, bank.idx)
            )
            if prior is not None:
                bank, dropped = merge_kernel_banks(
                    prior, bank, return_dropped=True, **self._merge_kw
                )
                self.stats.merge_dropped_mass += float(jnp.sum(dropped))
        else:
            bank = fit_bank(Xc, yc, self.cs, prior, **self._engine_kw)
        self._slots[self._active] = jax.tree.map(jnp.asarray, bank)

    # -- elastic sharded chunk path ------------------------------------------

    def _train_elastic(self, Xc, yc, n: int) -> None:
        """One chunk across the LOGICAL stream shards (module docstring:
        "Elastic sharded training").

        Fold structure is fixed by ``n_stream_shards`` alone: ranges fit
        FRESH, fold in ascending-range order through the eager Sec-4.3
        merges, and the active slot's prior merges in last. The physical
        mesh only decides where the fits execute, so the mesh fast path,
        the per-range degraded path, and any later remesh all produce
        bit-identical (f32) sub-bank state.
        """
        i = self.chunk_idx
        ranges = shard_ranges(n, self.n_stream_shards)
        dead = self._dead_shards(i, ranges)
        if len(dead) == len(ranges):
            # every shard lost at once: the whole chunk degrades to
            # recorded loss (there is no survivor to re-issue ranges to)
            self.stats.rows_lost += n
            self.stats.shard_ranges_lost += sum(
                1 for lo, hi in ranges if lo < hi
            )
            return
        clean = not dead and (
            self.shard_faults is None or self.shard_faults.clean(i)
        )
        if clean and self.mesh is not None and (
            self._mesh_devices() == self.n_stream_shards
        ):
            parts = self._fit_chunk_mesh(Xc, yc, ranges)
        else:
            parts = self._fit_chunk_ranges(Xc, yc, i, ranges, dead)
        if not parts:
            return  # every range masked out: the chunk contributes nothing
        bank = self._fold_chunk(parts)
        prior = self._slots[self._active]
        if self.bank_kind == "kernel":
            # chunk-local -> absolute stream ids; rows_ingested advances by
            # the FULL chunk (masked rows included) so ids stay unique and
            # replay-stable whatever was lost
            offset = self.stats.rows_ingested
            bank = bank._replace(
                idx=jnp.where(bank.idx >= 0, bank.idx + offset, bank.idx)
            )
            if prior is not None:
                bank, dropped = merge_kernel_banks(
                    prior, bank, return_dropped=True, **self._merge_kw
                )
                self.stats.merge_dropped_mass += float(jnp.sum(dropped))
        elif prior is not None:
            bank = merge_banks(prior, bank)
        self._slots[self._active] = jax.tree.map(jnp.asarray, bank)

    def _dead_shards(self, i: int, ranges) -> set:
        """Structurally dead logical shards for chunk ``i``: planned device
        losses plus declared stragglers. Plan-keyed and stateless, so every
        run (crash replay, chaos reference) re-derives the same set."""
        faults = self.shard_faults
        if faults is None:
            return set()
        dead = {int(j) for j in faults.lost(i) if 0 <= int(j) < len(ranges)}
        elapsed = faults.elapsed(i)
        if elapsed is not None and self.straggler_policy is not None:
            dead |= {
                j for j in self.straggler_policy.stragglers(list(elapsed))
                if 0 <= j < len(ranges)
            }
        return dead

    def _fit_chunk_mesh(self, Xc, yc, ranges):
        """Fast path: every logical shard fits on its own device in ONE mesh
        dispatch. Returns the same (lo, bank) parts list as the degraded
        path — for kernel banks literally the per-shard fits (gathered,
        unfolded); for linear banks the mesh's folded bank as a single part
        (``fit_bank_sharded`` folds its per-shard fits with the same eager
        fold on one device, so both paths agree)."""
        if self.bank_kind == "kernel":
            kw = {k: v for k, v in self._engine_kw.items() if k != "seed_check"}
            stacked = fit_kernel_bank_shards(
                Xc, yc, self.cs, self.mesh, axis=self.shard_axis, **kw
            )
            return [
                (lo, jax.tree.map(lambda x, j=j: x[j], stacked))
                for j, (lo, hi) in enumerate(ranges) if lo < hi
            ]
        folded = fit_bank_sharded(
            Xc, yc, self.cs, self.mesh, None, axis=self.shard_axis,
            **self._engine_kw,
        )
        return [(0, folded)]

    def _fit_chunk_ranges(self, Xc, yc, i: int, ranges, dead):
        """Degraded path: per-range single-device fits. Lost/straggler
        ranges are re-issued to survivors (``rebalance_ranges``); a shard
        whose fetch faults exhaust the retry budget has its whole assigned
        queue masked out with the loss recorded durably."""
        if dead:
            queues = rebalance_ranges(list(ranges), sorted(dead), grouped=True)
            self.stats.ranges_reissued += sum(
                1 for j in dead if ranges[j][0] < ranges[j][1]
            )
        else:
            queues = {j: [r] for j, r in enumerate(ranges)}
        parts = []
        for j in sorted(queues):
            work = [(lo, hi) for lo, hi in queues[j] if lo < hi]
            if not work:
                continue
            if not self._shard_fetch_ok(i, j):
                self.stats.rows_lost += sum(hi - lo for lo, hi in work)
                self.stats.shard_ranges_lost += len(work)
                continue
            for lo, hi in work:
                parts.append((lo, self._fit_range(Xc, yc, lo, hi)))
        parts.sort(key=lambda part: part[0])
        return parts

    def _shard_fetch_ok(self, i: int, j: int) -> bool:
        """Clear shard ``j``'s fetch channel for chunk ``i`` under the
        per-shard retry budget. False = budget exhausted: mask the shard's
        ranges out (the caller records the loss)."""
        if self.shard_faults is None:
            return True
        attempt = 0
        while True:
            try:
                self.shard_faults.check(i, j)
                return True
            except Exception as e:
                if not self.shard_retry.is_retryable(e):
                    raise  # programming error: surface it
                if attempt >= self.shard_retry.max_retries:
                    return False
                self._sleep(self.shard_retry.delay(attempt))
                attempt += 1
                self.stats.shard_retries += 1

    def _fit_range(self, Xc, yc, lo: int, hi: int):
        """Fresh single-device fit of rows [lo, hi); kernel ids lifted to
        chunk coordinates (the +lo the mesh path applies in-shard_map)."""
        Xr, Yr = Xc[lo:hi], yc[:, lo:hi]
        if self.bank_kind == "kernel":
            bank = fit_kernel_bank(Xr, Yr, self.cs, **self._engine_kw)
            return bank._replace(
                idx=jnp.where(bank.idx >= 0, bank.idx + lo, bank.idx)
            )
        return fit_bank(Xr, Yr, self.cs, None, **self._engine_kw)

    def _fold_chunk(self, parts):
        """Eager ascending-range Sec-4.3 fold of the chunk's per-range banks
        — the ONE fold implementation both execution paths share, which is
        what makes them bit-identical. Kernel re-compression drops are
        audited into ``merge_dropped_mass`` (deterministic: the fold
        structure is logical, so every run derives the same drops)."""
        banks = [b for _, b in parts]
        if self.bank_kind == "kernel":
            folded, dropped = fold_kernel_banks(
                banks, return_dropped=True, **self._merge_kw
            )
            self.stats.merge_dropped_mass += float(jnp.sum(dropped))
            return folded
        if len(banks) == 1:
            return banks[0]
        return fold_merge(stack_banks(banks))

    def _age_order(self) -> List[int]:
        """Live slot indices, oldest epoch first (deterministic)."""
        return sorted(
            (s for s in range(self.k) if self._slots[s] is not None),
            key=lambda s: (self._birth[s], s),
        )

    def _rotate(self) -> None:
        if self._slots[self._active] is None:
            return  # empty epoch (all chunks quarantined): nothing to freeze
        free = [s for s in range(self.k) if self._slots[s] is None]
        if free:
            nxt = free[0]
        else:
            order = self._age_order()
            oldest = order[0]
            if self.retire == "drop" or self.k == 1:
                self._slots[oldest] = None
            else:
                second = order[1]
                if self.bank_kind == "kernel":
                    merged, dropped = merge_kernel_banks(
                        self._slots[oldest], self._slots[second],
                        return_dropped=True, **self._merge_kw,
                    )
                    self.stats.merge_dropped_mass += float(jnp.sum(dropped))
                else:
                    merged = merge_banks(
                        self._slots[oldest], self._slots[second]
                    )
                self._slots[second] = jax.tree.map(jnp.asarray, merged)
                self._birth[second] = self._birth[oldest]
                self._slots[oldest] = None
            self.stats.retirements += 1
            nxt = oldest
        self._active = nxt
        self._birth[nxt] = self.chunk_idx
        self.stats.rotations += 1

    def _merged(self):
        """Serving fold of the live slots, oldest first (Ball or KernelBank).

        Also records the fold's dropped |coef| mass in ``_fold_dropped`` —
        the caller that COUNTS the fold (cadence/finalize, not resume)
        accumulates it into the durable ``stats.merge_dropped_mass``.
        """
        order = self._age_order()
        if not order:
            return None
        banks = [self._slots[s] for s in order]
        if self.bank_kind == "kernel":
            folded, dropped = fold_kernel_banks(
                banks, return_dropped=True, **self._merge_kw
            )
            self._fold_dropped = float(jnp.sum(dropped))
        else:
            folded = fold_banks(banks)
            self._fold_dropped = 0.0
        return jax.tree.map(jnp.asarray, folded)

    def _check_server_config(self, server) -> None:
        """Refuse hot-swapping into a server with a mismatched kernel config.

        Duck-typed swap targets without a ``kernel`` attribute (e.g. test
        recorders) opt out; a real ``serve.BankServer`` always has one.
        """
        skernel = getattr(server, "kernel", _NO_KERNEL_ATTR)
        if skernel is _NO_KERNEL_ATTR:
            return
        sgamma = getattr(server, "gamma", None)
        mine = (
            f"bank_kind={self.bank_kind!r}, kernel={self.kernel!r}, "
            f"gamma={self.gamma if self.kernel else None!r}"
        )
        theirs = f"kernel={skernel!r}, gamma={sgamma!r}"
        if skernel != self.kernel or (
            self.kernel is not None
            and sgamma is not None
            and float(sgamma) != self.gamma
        ):
            raise ValueError(
                f"live loop ({mine}) cannot hot-swap into a server "
                f"configured {theirs} — a bank scored under the wrong "
                "kernel config serves silent garbage; rebuild the server "
                "with the loop's kernel configuration"
            )

    def _push(self, merged) -> None:
        if merged is None:
            return
        self._last_merged = merged
        if self.server is None and self.server_factory is not None:
            self.server = self.server_factory(merged)
            self._check_server_config(self.server)
        elif self.server is not None:
            self._check_server_config(self.server)
            self.server.swap_bank(merged)
        self.stats.swaps += 1
        self.stats.last_swap_chunk = self.chunk_idx
        self.stats.bank_age_chunks = 0

    # -- public surface ------------------------------------------------------

    def attach_server(self, server, push_current: bool = True) -> None:
        """Point hot-swaps at ``server``; optionally push the current bank."""
        self._check_server_config(server)
        self.server = server
        if push_current and self._last_merged is not None:
            server.swap_bank(self._last_merged)

    def serving_bank(self):
        """The last folded bank — Ball or KernelBank by ``bank_kind`` —
        i.e. what an attached server is serving."""
        return self._last_merged

    def run(self, max_chunks: Optional[int] = None) -> LiveStats:
        """Resume from the last durable checkpoint and consume the stream.

        Stops when the source returns None (bounded/drained stream) or
        after ``max_chunks`` chunk positions this call. On exit a final
        fold + swap + checkpoint makes the tail durable and served. Crash
        recovery = call run() again (see run_live_with_restarts).
        """
        self._resume_from_disk()
        processed = 0
        while max_chunks is None or processed < max_chunks:
            i = self.chunk_idx
            self._failpoint("fetch", i)
            chunk = self._fetch(i)
            if chunk is _END:
                break
            if chunk is _QUARANTINED:
                self.chunk_idx = i + 1
                processed += 1
                self._cadences(i)
                continue
            X, y = chunk
            if np.asarray(X).shape[0] == 0:
                self.chunk_idx = i + 1
                processed += 1
                continue
            rows = self._train(X, y)
            self._failpoint("post_train", i)
            self.chunk_idx = i + 1
            self.stats.chunks_ingested += 1
            self.stats.rows_ingested += rows
            processed += 1
            self._cadences(i)
        self._finalize()
        return self.stats

    def _publishable(self, merged) -> bool:
        """The non-finite publish guard: a fold with NaN/Inf in ANY model
        row must never be hot-swapped (one poisoned coordinate turns every
        score of that row into NaN). Default: quarantine the fold —
        ``folds_quarantined`` counts it, the server keeps the last good
        bank. ``strict_finite=True``: raise, naming the offending rows."""
        bad = nonfinite_rows(merged)
        if not bool(jnp.any(bad)):
            return True
        rows = np.flatnonzero(np.asarray(bad)).tolist()
        if self.strict_finite:
            raise ValueError(
                f"non-finite serving fold at chunk {self.chunk_idx}: model "
                f"row(s) {rows} contain NaN/Inf — refusing to publish "
                "(strict_finite=True). The last good bank keeps serving; "
                "inspect the stream window since the last swap."
            )
        self.stats.folds_quarantined += 1
        return False

    def _cadences(self, i: int) -> None:
        """Rotation / fold+swap / checkpoint, keyed on the ABSOLUTE chunk
        position so a replayed window re-fires them identically (and
        ``rotate_on`` sees only replay-stable durable stats)."""
        rotate = self.chunk_idx % self.rotate_every == 0
        if not rotate and self.rotate_on is not None:
            rotate = bool(self.rotate_on(self.stats))
        if rotate:
            self._rotate()
            self._failpoint("post_rotate", i)
        if self.chunk_idx % self.swap_every == 0:
            merged = self._merged()
            if merged is not None:
                if self._publishable(merged):
                    self.stats.folds += 1
                    self.stats.merge_dropped_mass += self._fold_dropped
                    self._folds_since_ckpt += 1
                    self._failpoint("post_fold", i)
                    self._push(merged)
                    self._failpoint("post_swap", i)
                else:
                    # quarantined folds still count toward the checkpoint
                    # cadence: durability must not stall on poisoned data
                    self._folds_since_ckpt += 1
        if (
            self.checkpoint_every_folds
            and self._folds_since_ckpt >= self.checkpoint_every_folds
        ):
            self._checkpoint(i)
        if self.stats.last_swap_chunk >= 0:
            self.stats.bank_age_chunks = (
                self.chunk_idx - self.stats.last_swap_chunk
            )

    def _finalize(self) -> None:
        """Drained-stream tail: fold+swap anything trained since the last
        cadence hit, then commit a final checkpoint."""
        if self.chunk_idx % self.swap_every != 0:
            merged = self._merged()
            if merged is not None and (
                self.stats.last_swap_chunk != self.chunk_idx
            ):
                if self._publishable(merged):
                    self.stats.folds += 1
                    self.stats.merge_dropped_mass += self._fold_dropped
                    self._folds_since_ckpt += 1
                    self._push(merged)
                else:
                    self._folds_since_ckpt += 1
        if self.checkpoint_every_folds and self._folds_since_ckpt:
            self._checkpoint(self.chunk_idx - 1)


def run_live_with_restarts(
    live: LiveBank,
    *,
    max_restarts: int = 8,
    policy: Optional[RetryPolicy] = None,
    sleep: Callable[[float], None] = time.sleep,
    max_chunks: Optional[int] = None,
) -> LiveStats:
    """Crash-recovery driver: re-enter ``live.run()`` after retryable
    failures (the live-loop analogue of runtime.run_with_restarts).

    Each restart resumes from the last durable StreamCheckpoint — the
    crash-equivalence suite proves the recovered bank and served scores are
    bit-identical (f32) to an uninterrupted run. Non-retryable exceptions
    (programming errors) propagate immediately.

    The default policy classifies injected test failures, ``DeviceLostError``
    and the JAX runtime's device-fault exception
    (``jax.errors.JaxRuntimeError``) as retryable
    (``runtime.default_live_retryable``): a transient device fault burns a
    restart instead of propagating as if it were a programming error. A
    Mosaic compile refusal or a device out-of-memory error raises the same
    class, so under this policy it burns the restarts, recompiling each
    time, before it surfaces: call ``live.run()`` directly to see it at once.
    """
    policy = policy or RetryPolicy(
        retryable=default_live_retryable(), max_retries=max_restarts
    )
    restarts = 0
    while True:
        try:
            return live.run(max_chunks=max_chunks)
        except Exception as e:
            if not policy.is_retryable(e):
                raise
            restarts += 1
            if restarts > max_restarts:
                raise
            live.stats.restarts += 1
            sleep(policy.delay(restarts - 1))

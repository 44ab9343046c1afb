"""Bank-serving engine: microbatched query scoring against a StreamSVM bank.

The inference-side twin of the token scheduler (token_scheduler.py), built
for the deploy shape the paper's one-pass training produces: a *tiny,
constant-storage* (B, D) bank — classes x C-grid x variants — and a firehose
of queries. Same slot/utilization discipline as continuous batching, applied
to query ROWS instead of decode tokens:

  - a fixed microbatch of ``q_block`` row slots (the Pallas predict kernel's
    query-tile height, so every step is one fused kernel launch);
  - ragged requests (any number of rows each) are packed FIFO into the free
    slots of each step — a large request spans several steps, several small
    requests share one — so slot waste is only the final partial batch;
  - ``SchedulerStats``-style accounting: busy-row / idle-row utilization.

Scoring runs through ``kernels.ops.predict_bank`` (data-major tiled grid,
fused scores / per-C-grid-group ovr-argmax / topk epilogues, optional bf16
query tiles). f32 served scores are bit-exact with the direct jnp readout
``X @ bank.w.T`` (tests/test_bank_server.py pins this against
core.predict_ovr).

Train -> serve handoff: ``BankServer.from_checkpoint`` loads the stacked-Ball
bank a ``fit_chunked_many`` checkpoint callback persisted via
``repro.checkpoint.ckpt.save`` (manifest + npz), picking up ``n_classes``
from the checkpoint meta when serving OVR.

Hot swap: ``swap_bank`` replaces the bank between steps WITHOUT dropping
queued requests — rows already scored keep their results, every row scored
after the swap sees the new bank, and a same-shape swap never recompiles
(only shapes and epilogue parameters are static to the kernel's jit).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.kernel_bank import KernelBank
from repro.core.meb import Ball, fold_banks, fold_kernel_banks
from repro.kernels.ops import predict_bank, predict_kernel_bank


@dataclasses.dataclass
class ScoreRequest:
    """One scoring request: a ragged block of query rows and its results.

    ``result`` is filled in place as the server's microbatches cover the
    request's rows: an (n, B) f32 array for the "scores" epilogue, an
    ``((n, G) int32 class ids, (n, G) f32 margins)`` pair for "ovr", and an
    ``((n, k) f32, (n, k) int32)`` pair for "topk".

    ``t_submit``, ``t_first`` and ``t_done`` are ``time.perf_counter()``
    stamps, NaN until set: when ``submit`` took the request, when a step
    packed its first row, and when the answers to its last row reached the
    host. A zero-row request gets all three at submit.
    """

    rid: int
    queries: np.ndarray  # (n, D) float32
    result: Union[np.ndarray, Tuple[np.ndarray, ...], None] = None
    rows_scored: int = 0
    done: bool = False
    t_submit: float = math.nan
    t_first: float = math.nan
    t_done: float = math.nan


@dataclasses.dataclass
class ServerStats:
    """Row-slot accounting, mirroring token_scheduler.SchedulerStats."""

    steps: int = 0
    admitted: int = 0
    finished: int = 0
    slot_busy_rows: int = 0
    slot_idle_rows: int = 0
    bank_swaps: int = 0

    @property
    def utilization(self) -> float:
        tot = self.slot_busy_rows + self.slot_idle_rows
        return self.slot_busy_rows / tot if tot else 0.0


class BankServer:
    """Serve a trained (B, D) bank: microbatch, score, hot-swap.

    bank: a stacked ``Ball`` (``fit_bank``/``fit_ovr``/``fit_c_grid`` result
    or a restored checkpoint), a plain (B, D) weight array, or a
    ``KernelBank`` (``fit_kernel_bank`` result) — the kernelized bank is
    detected by its (B, S, D) core-set ``points``/(B, S) ``coef`` arrays and
    served through ``kernels.ops.predict_kernel_bank`` instead, with
    ``kernel=`` ("linear"/"rbf", REQUIRED for kernel banks) and ``gamma=``
    naming the kernel the bank was trained with (they must match the fit —
    the checkpoint meta records them, and ``from_checkpoint`` restores them
    automatically).
    epilogue/n_classes/k/q_block/b_tile/stream_dtype/bank_resident: the
    fused-kernel serving configuration — see ``kernels.ops.predict_bank``
    (``bank_resident="hbm"`` serves the bank straight out of ANY/HBM space
    through the kernel's 2-slot ring — the deploy shape for banks whose
    (B, D) footprint exceeds the VMEM budget; "auto" picks that exactly
    when it does). These are static (fixed per server); the bank itself is
    traced, so ``swap_bank`` with a same-shape bank reuses the compiled
    kernel — in any residency. Kernel banks ignore ``b_tile`` and
    ``bank_resident`` (their state is bounded by construction — the Gram
    operand streams through the tiled kernel's own block pipeline).

    Tracing: under a profiler capture (``jax.profiler.start_trace``) each
    ``step`` shows as five consecutive host spans, on the device ops' clock:
    ``serve.pack`` (a fresh query buffer, the FIFO packing loop),
    ``serve.copy_in`` (``jnp.asarray`` of the buffer; it can return before
    the transfer ends), ``serve.launch`` (the scoring call, which returns
    before the device finishes), ``serve.readback`` (``np.asarray`` of the
    outputs: waits for the transfer in, the kernel and the copy out) and
    ``serve.scatter`` (answers into requests, the queue rebuild,
    ``ServerStats``). With no capture running a span costs well under a
    microsecond. Each ``ScoreRequest`` carries ``time.perf_counter()``
    stamps: ``t_submit`` (``submit`` took it), ``t_first`` (the step that
    packed its first row began) and ``t_done`` (the step that answered its
    last row had its outputs on the host). ``t_first - t_submit`` is the
    wait in this server's queue and ``t_done - t_first`` the service time;
    their sum is the server's share of a request's latency. A caller's own
    wait before ``submit`` (e.g. a single-threaded client busy in ``step``
    when the request fell due) is not in them: time that from the caller's
    clock.
    """

    def __init__(
        self,
        bank,
        *,
        epilogue: str = "scores",
        n_classes: Optional[int] = None,
        k: Optional[int] = None,
        q_block: int = 256,
        b_tile: Optional[int] = None,
        stream_dtype=None,
        bank_resident: str = "auto",
        kernel: Optional[str] = None,
        gamma: float = 1.0,
        interpret: Optional[bool] = None,
    ):
        if self._is_kernel_bank(bank):
            if kernel is None:
                raise ValueError(
                    "serving a KernelBank needs kernel='linear' or 'rbf' "
                    "(the kernel the bank was trained with); pass it "
                    "explicitly or use from_checkpoint, which restores it "
                    "from the checkpoint meta"
                )
            self._w = None
            self._points, self._coef = self._kernel_bank_arrays(bank)
            b, _, d = self._points.shape
        else:
            if kernel is not None:
                raise ValueError(
                    f"kernel={kernel!r} only applies to a KernelBank; this "
                    "bank is a linear (B, D) weight bank"
                )
            self._w = self._bank_weights(bank)
            self._points = self._coef = None
            b, d = self._w.shape
        self.kernel = kernel
        self.gamma = float(gamma)
        self._b, self._d = b, d
        if epilogue not in ("scores", "ovr", "topk"):
            raise ValueError(
                f"unknown epilogue {epilogue!r}; expected 'scores', 'ovr' "
                "or 'topk'"
            )
        if epilogue == "ovr":
            if n_classes is None or n_classes < 1 or b % n_classes:
                raise ValueError(
                    f"epilogue='ovr' needs n_classes >= 1 dividing B: got "
                    f"n_classes={n_classes}, B={b}"
                )
        elif epilogue == "topk" and (k is None or not (1 <= k <= b)):
            raise ValueError(
                f"epilogue='topk' needs 1 <= k <= B: got k={k}, B={b}"
            )
        self.epilogue = epilogue
        self.n_classes = n_classes
        self.k = k
        self.q_block = int(q_block)
        self.b_tile = b_tile
        self.stream_dtype = stream_dtype
        self.bank_resident = bank_resident
        self.interpret = interpret
        self.stats = ServerStats()
        self._queue: List[ScoreRequest] = []  # FIFO; head may be partial
        self._next_rid = 0

    # -- bank management ----------------------------------------------------

    @staticmethod
    def _is_kernel_bank(bank) -> bool:
        return hasattr(bank, "points") and hasattr(bank, "coef")

    @staticmethod
    def _kernel_bank_arrays(bank) -> Tuple[jnp.ndarray, jnp.ndarray]:
        points = jnp.asarray(bank.points, jnp.float32)
        coef = jnp.asarray(bank.coef, jnp.float32)
        if points.ndim != 3 or coef.shape != points.shape[:2]:
            raise ValueError(
                f"KernelBank needs (B, S, D) points with (B, S) coef: got "
                f"points.shape={tuple(points.shape)}, coef.shape="
                f"{tuple(coef.shape)}"
            )
        return points, coef

    @staticmethod
    def _bank_weights(bank) -> jnp.ndarray:
        w = bank.w if hasattr(bank, "w") else bank
        w = jnp.asarray(w, jnp.float32)
        if w.ndim != 2:
            raise ValueError(
                f"bank must be a stacked Ball or a (B, D) weight array: got "
                f"weights of shape {w.shape}"
            )
        return w

    @property
    def bank_shape(self) -> Tuple[int, ...]:
        if self._w is None:
            return tuple(self._points.shape)
        return tuple(self._w.shape)

    def swap_bank(self, bank, *, kernel: Optional[str] = None,
                  gamma=None) -> None:
        """Replace the served bank between steps; queued requests survive.

        Rows already scored keep their (old-bank) results; every row scored
        from the next ``step()`` on sees the new bank. The new bank must
        match the current shape — (B, D) weights for a linear server,
        (B, S, D) core sets for a kernel server (a linear bank cannot swap
        into a kernel server or vice versa) — same shape means the kernel's
        jit cache is reused, so a swap never stalls serving on a recompile.

        ``kernel``/``gamma``: optionally declare the kernel config the
        incoming bank was TRAINED with; a mismatch with this server's
        config raises a ValueError naming both instead of serving silent
        garbage scores (a core-set bank scored under the wrong kernel or
        gamma is numerically valid but semantically wrong).
        """
        if kernel is not None and kernel != self.kernel:
            raise ValueError(
                f"hot-swap bank was trained with kernel={kernel!r}; this "
                f"server is configured kernel={self.kernel!r} "
                f"(gamma={self.gamma}) — scoring under a different kernel "
                "serves silent garbage; start a BankServer matching the "
                "bank's kernel config"
            )
        if (
            gamma is not None
            and self.kernel is not None
            and float(gamma) != self.gamma
        ):
            raise ValueError(
                f"hot-swap bank was trained with gamma={float(gamma)}; this "
                f"server is configured kernel={self.kernel!r} with "
                f"gamma={self.gamma} — scoring under a different gamma "
                "serves silent garbage; start a BankServer matching the "
                "bank's kernel config"
            )
        if self._w is None:
            if not self._is_kernel_bank(bank):
                raise ValueError(
                    "this server serves a KernelBank; hot-swap needs another "
                    "KernelBank of the same (B, S, D) shape"
                )
            points, coef = self._kernel_bank_arrays(bank)
            if points.shape != self._points.shape:
                raise ValueError(
                    f"hot-swap core-set shape {tuple(points.shape)} != "
                    f"served shape {tuple(self._points.shape)}; start a new "
                    "BankServer to change shape"
                )
            self._points, self._coef = points, coef
            self.stats.bank_swaps += 1
            return
        if self._is_kernel_bank(bank):
            raise ValueError(
                "this server serves a linear (B, D) bank; a KernelBank "
                "needs its own BankServer(kernel=...)"
            )
        w = self._bank_weights(bank)
        if w.shape != self._w.shape:
            raise ValueError(
                f"hot-swap bank shape {tuple(w.shape)} != served bank shape "
                f"{tuple(self._w.shape)}; start a new BankServer to change "
                "shape"
            )
        self._w = w
        self.stats.bank_swaps += 1

    @classmethod
    def from_checkpoint(cls, path: str, **kwargs) -> "BankServer":
        """Serve the bank a trainer checkpoint persisted to disk.

        ``path`` is a ``repro.checkpoint.ckpt.save`` directory whose tree is
        the stacked Ball (the ``StreamCheckpoint.ball`` handed to the
        checkpoint callback) — or, when the manifest meta carries
        ``bank_kind == "kernel"`` (a ``core.save_kernel_bank`` checkpoint),
        the 7-leaf ``KernelBank``, in which case ``kernel``/``gamma`` are
        restored from the meta unless overridden. A ``repro.live``
        StreamCheckpoint (meta carries ``live_k``) also serves directly:
        the K-slot state is restored and the live sub-banks are folded
        oldest-first — linear or kernelized per the meta's ``bank_kind`` —
        into exactly the bank the live loop itself would push next (serve
        straight from the trainer's last durable commit after a trainer
        death). The manifest's shapes/dtypes rebuild the restore target;
        ``meta["n_classes"]`` (if the trainer recorded it) fills in OVR
        serving unless overridden.
        """
        from repro.checkpoint import ckpt

        manifest = ckpt.load_manifest(path)
        shapes = manifest["shapes"]
        meta = manifest.get("meta", {})
        if "live_k" in meta:
            bank = cls._fold_live_checkpoint(path, manifest, meta, kwargs)
        elif meta.get("bank_kind") == "kernel":
            if len(shapes) != len(KernelBank._fields):
                raise ValueError(
                    f"kernel-bank checkpoint at {path!r} has {len(shapes)} "
                    f"leaves; expected the {len(KernelBank._fields)}-leaf "
                    "KernelBank a save_kernel_bank checkpoint carries"
                )
            target = KernelBank(*ckpt.zeros_like_manifest(manifest))
            kwargs.setdefault("kernel", meta.get("kernel"))
            kwargs.setdefault("gamma", float(meta.get("gamma", 1.0)))
            bank = ckpt.restore(path, target)
        elif len(shapes) != 4:
            raise ValueError(
                f"checkpoint at {path!r} has {len(shapes)} leaves; expected "
                "the 4-leaf stacked Ball (w, r, xi2, m) a fit_chunked_many "
                "checkpoint carries"
            )
        else:
            target = Ball(*ckpt.zeros_like_manifest(manifest))
            bank = ckpt.restore(path, target)
        if (
            kwargs.get("epilogue") == "ovr"
            and "n_classes" not in kwargs
            and "n_classes" in meta
        ):
            kwargs["n_classes"] = int(meta["n_classes"])
        return cls(bank, **kwargs)

    @staticmethod
    def _fold_live_checkpoint(path, manifest, meta, kwargs):
        """Fold a repro.live StreamCheckpoint into its serving bank.

        The state tree is ``{"birth": (K,), "live": (K,), "sub": stacked
        Ball|KernelBank}``; the serving bank is the Sec-4.3 fold of the
        LIVE slots, oldest (birth, slot) first — the same order and fold
        the loop's own serving fold uses, so the result is bit-identical
        (f32) to what the loop was serving at its last durable commit.
        Kernel folds read kernel/gamma/eviction from the meta (the
        save_kernel_bank meta contract) and seed the server's ``kernel=``/
        ``gamma=`` unless overridden.
        """
        from repro.checkpoint import ckpt

        kind = meta.get("bank_kind", "linear")
        sub_cls = KernelBank if kind == "kernel" else Ball
        head = ckpt.zeros_like_manifest(manifest, 0, 2)
        target = {
            "birth": head[0],
            "live": head[1].astype(bool),
            "sub": sub_cls(*ckpt.zeros_like_manifest(manifest, 2)),
        }
        state = ckpt.restore(path, target)
        live = np.asarray(state["live"])
        birth = np.asarray(state["birth"])
        order = sorted(
            (int(s) for s in np.flatnonzero(live)),
            key=lambda s: (int(birth[s]), s),
        )
        if not order:
            raise ValueError(
                f"live checkpoint at {path!r} has no live sub-bank slots — "
                "nothing to fold into a serving bank"
            )
        banks = [
            jax.tree.map(lambda x, s=s: x[s], state["sub"]) for s in order
        ]
        if kind == "kernel":
            kwargs.setdefault("kernel", meta.get("kernel"))
            kwargs.setdefault("gamma", float(meta.get("gamma", 1.0)))
            return fold_kernel_banks(
                banks,
                kernel=meta.get("kernel"),
                gamma=float(meta.get("gamma", 1.0)),
                eviction=meta.get("eviction", "smallest-coef"),
            )
        return fold_banks(banks)

    # -- request lifecycle --------------------------------------------------

    def submit(self, queries) -> ScoreRequest:
        """Queue a ragged block of query rows; returns its ScoreRequest."""
        t_submit = time.perf_counter()
        q = np.asarray(queries, np.float32)
        if q.ndim != 2 or q.shape[1] != self._d:
            raise ValueError(
                f"queries must be (n, D={self._d}) rows: got shape "
                f"{q.shape}"
            )
        n = q.shape[0]
        b = self._b
        if self.epilogue == "scores":
            result = np.empty((n, b), np.float32)
        elif self.epilogue == "ovr":
            g = b // self.n_classes
            result = (np.empty((n, g), np.int32), np.empty((n, g), np.float32))
        else:
            result = (
                np.empty((n, self.k), np.float32),
                np.empty((n, self.k), np.int32),
            )
        req = ScoreRequest(
            rid=self._next_rid, queries=q, result=result, t_submit=t_submit
        )
        self._next_rid += 1
        self.stats.admitted += 1
        if n == 0:  # nothing to score — finished on arrival
            req.done = True
            req.t_first = req.t_done = t_submit
            self.stats.finished += 1
        else:
            self._queue.append(req)
        return req

    def pending_rows(self) -> int:
        return sum(r.queries.shape[0] - r.rows_scored for r in self._queue)

    def step(self) -> int:
        """Pack up to q_block queued rows, run ONE fused kernel launch,
        scatter results back. Returns the number of rows scored."""
        if not self._queue:
            return 0
        with TraceAnnotation("serve.pack"):
            t_first = time.perf_counter()
            buf = np.zeros((self.q_block, self._d), np.float32)
            segments: List[Tuple[ScoreRequest, int, int, int]] = []
            filled = 0
            qi = 0
            while qi < len(self._queue) and filled < self.q_block:
                req = self._queue[qi]
                off = req.rows_scored
                take = min(req.queries.shape[0] - off, self.q_block - filled)
                buf[filled : filled + take] = req.queries[off : off + take]
                if off == 0:
                    req.t_first = t_first
                segments.append((req, off, take, filled))
                filled += take
                qi += 1
        with TraceAnnotation("serve.copy_in"):
            x = jnp.asarray(buf)
        with TraceAnnotation("serve.launch"):
            if self._w is None:
                out = predict_kernel_bank(
                    x,
                    self._points,
                    self._coef,
                    kernel=self.kernel,
                    gamma=self.gamma,
                    epilogue=self.epilogue,
                    n_classes=self.n_classes,
                    k=self.k,
                    q_block=self.q_block,
                    stream_dtype=self.stream_dtype,
                    interpret=self.interpret,
                )
            else:
                out = predict_bank(
                    x,
                    self._w,
                    epilogue=self.epilogue,
                    n_classes=self.n_classes,
                    k=self.k,
                    q_block=self.q_block,
                    b_tile=self.b_tile,
                    stream_dtype=self.stream_dtype,
                    bank_resident=self.bank_resident,
                    interpret=self.interpret,
                )
        with TraceAnnotation("serve.readback"):
            parts = (out,) if self.epilogue == "scores" else out
            parts = tuple(np.asarray(p) for p in parts)
        with TraceAnnotation("serve.scatter"):
            t_done = time.perf_counter()
            finished = 0
            for req, off, take, at in segments:
                dests = (
                    (req.result,) if self.epilogue == "scores" else req.result
                )
                for dst, src in zip(dests, parts):
                    dst[off : off + take] = src[at : at + take]
                req.rows_scored = off + take
                if req.rows_scored == req.queries.shape[0]:
                    req.done = True
                    req.t_done = t_done
                    finished += 1
            self._queue = [r for r in self._queue if not r.done]
            self.stats.steps += 1
            self.stats.slot_busy_rows += filled
            self.stats.slot_idle_rows += self.q_block - filled
            self.stats.finished += finished
        return filled

    def run(self, max_steps: int = 100_000) -> ServerStats:
        """Drain the queue; raises if ``max_steps`` can't cover it.

        Every step scores at least one row, so the queue always drains given
        enough steps — ``max_steps`` is a runaway valve, and exhausting it
        with rows still pending is an error (returning would leave requests
        with uninitialized result rows)."""
        for _ in range(max_steps):
            if not self._queue:
                return self.stats
            self.step()
        if self._queue:
            raise RuntimeError(
                f"run(max_steps={max_steps}) left {self.pending_rows()} rows "
                f"pending in {len(self._queue)} request(s); raise max_steps"
            )
        return self.stats

    def score(self, queries):
        """Submit one request and drain: returns its epilogue result."""
        req = self.submit(queries)
        self.run()
        return req.result

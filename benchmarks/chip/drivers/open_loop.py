"""Serving window: open-loop Poisson arrivals into a ``BankServer``.

Set-up makes a bank of the configuration's shape on the device from the
seed (one-vs-rest x C-grid rows along the class prototypes of the
configuration's generator, so answers are decided as a trained bank's are),
a pool of query rows from the same generator, copied to the host once, and
a ``BankServer`` with the mix's readout (``epilogue="topk"``, ``k``) and its
default ``q_block``. One full step compiles (or loads) the serving kernel
before the window opens.

The window offers the traffic mix's requests at their due times, from one
thread: each turn submits every request that is due, then runs one
``step()``, or sleeps until the next due time when nothing is queued.
Latency is (time the request's last row was answered) - (time it was due),
so a stall delays every later request; how late the generator itself
submitted is kept too (``loadgen_late_ms_p99``). The requests due in the
window are all answered before the check, a minute past the close at most;
one that never is counts as failed.

Every seed gets the same request sizes and arrival gaps, drawn once from the
mix's ``schedule_seed``; the run's seed shuffles their order within blocks of
``shuffle_block`` requests, so every seed offers the same rows in every block
(the same load, a few tens of milliseconds at a time), and picks which pool
rows each request sends. The requests the check compares are drawn from the
seed before the window; the driver keeps no other request once it is
answered, as a client would not, so the window's heap stays as small as a
server's own.
"""
from __future__ import annotations

import gc
import math
import time
from collections import deque

import numpy as np

from benchmarks.chip import harness
from benchmarks.chip.refs import bank_ref

#: How long past the window's close unanswered requests are waited for.
DRAIN_S = 60.0


def schedule(mix: dict, seconds: float, seed: int):
    """(due_s, sizes, offsets) of the requests due in a window.

    n = rate x seconds requests. Gaps are exponential and sizes lognormal
    (median ``size_median`` rows, log-sd ``size_sigma``, clipped to
    [``size_min``, ``size_max``]), drawn from ``schedule_seed``: the same for
    every run seed. The run seed permutes both within each block of
    ``shuffle_block`` requests and draws each request's first pool row. The
    gaps are scaled to span the window exactly, so the i-th request is due at
    seconds * (sum of the first i gaps) / (sum of all).
    """
    n = int(round(mix["rate_rps"] * seconds))
    base = np.random.default_rng(mix["schedule_seed"])
    gaps = base.exponential(1.0, n)
    sizes = np.clip(np.rint(base.lognormal(math.log(mix["size_median"]),
                                           mix["size_sigma"], n)),
                    mix["size_min"], mix["size_max"]).astype(np.int64)
    rng = harness.seed_rng(seed, 3)
    gaps, sizes = (a[block_permutation(rng, n, mix["shuffle_block"])]
                   for a in (gaps, sizes))
    due = seconds * (np.cumsum(gaps) - gaps) / gaps.sum()
    offsets = rng.integers(0, mix["pool_rows"] - sizes + 1)
    return due, sizes, offsets


def block_permutation(rng, n: int, block: int) -> np.ndarray:
    """A permutation of range(n) that moves each index only within its
    block of ``block`` consecutive indices."""
    keys = np.arange(n) // block + rng.random(n)
    return np.argsort(keys, kind="stable")


def percentiles_ms(latency) -> dict:
    """The latency percentiles the notes report, in milliseconds."""
    qs = (50, 90, 95, 99, 99.9)
    vals = np.percentile(latency, qs) * 1e3 if len(latency) else [math.nan] * 5
    return {f"p{q:g}": float(v) for q, v in zip(qs, vals)}


def serve_bank(proto, c_grid, key):
    """(G*K, D) bank, class-major within each C point: per C point, each
    class's prototype direction (centered over classes, unit norm), grown
    with C, plus a little noise."""
    import jax
    import jax.numpy as jnp

    def make(proto, key):
        u = proto - proto.mean(axis=0, keepdims=True)
        u = u / jnp.linalg.norm(u, axis=1, keepdims=True)
        groups = []
        for g, c in enumerate(c_grid):
            noise = jax.random.normal(jax.random.fold_in(key, g), u.shape)
            groups.append((1.0 + 0.1 * math.log10(c)) * u
                          + 0.02 * noise / math.sqrt(u.shape[1]))
        return jnp.concatenate(groups, axis=0).astype(jnp.float32)

    return jax.jit(make)(proto, key)


class _GcWatch:
    """Records how long each of the interpreter's full (generation 2)
    garbage collections in the window took."""

    def __init__(self, out: list):
        self.out, self.t = out, None

    def __call__(self, phase, info):
        if info.get("generation") != 2:
            return
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t is not None:
            self.out.append(time.perf_counter() - self.t)
            self.t = None


class Cell:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        cfg = ctx.cfg
        self.k, self.c_grid = cfg["n_classes"], cfg["c_grid"]
        self.mix = ctx.traffic

    def setup(self) -> None:
        import jax
        from repro.serve import BankServer

        ctx = self.ctx
        key = ctx.key()
        sharding = ctx.stream_sharding()
        proto = ctx.gen.prototypes(ctx.cfg, key)
        self.W = serve_bank(proto, self.c_grid, jax.random.fold_in(key, 7))
        pool, _ = ctx.gen.stream(ctx.cfg, key, self.mix["pool_rows"], sharding)
        self.pool = np.asarray(pool)
        del pool, proto
        served_bank, self.served_pool = self.W, self.pool
        if ctx.control:
            # The program's own bf16 serving path (stream_dtype="bf16") does
            # not compile at this bank's size, so the control is the f32 path
            # on operands rounded to bf16: the bank and every query row
            # carry bf16 precision, the products are summed in f32.
            import jax.numpy as jnp

            bf16 = lambda a: jnp.asarray(a).astype(jnp.bfloat16).astype(
                jnp.float32)
            served_bank = bf16(self.W)
            self.served_pool = np.asarray(bf16(self.pool))
        self.server = BankServer(served_bank, epilogue="topk", k=self.mix["k"])
        self.server.submit(self.served_pool[: self.server.q_block])
        self.server.run()

    def sample(self, sizes) -> list[int]:
        """The requests the check compares: ``check_requests`` drawn from
        the seed, and the longest request."""
        n = len(sizes)
        take = min(n, self.mix["check_requests"])
        pick = self.ctx.rng(4).choice(n, take, replace=False).tolist()
        return sorted(set(pick) | {int(np.argmax(sizes))}) if n else []

    def measure(self, seconds: float) -> harness.Window:
        import jax

        server, pool = self.server, self.served_pool
        due, sizes, offsets = schedule(self.mix, seconds, self.ctx.seed)
        n = len(due)
        self.sizes, self.offsets = sizes, offsets
        self.kept = dict.fromkeys(self.sample(sizes))
        submit = np.full(n, np.nan)
        finish = np.full(n, np.nan)
        step_s = []
        pending = deque()  # (index, request), in submission (= answer) order
        steps0 = server.stats.steps
        busy0, idle0 = server.stats.slot_busy_rows, server.stats.slot_idle_rows
        span = jax.profiler.TraceAnnotation
        pauses = []
        gc_watch = _GcWatch(pauses)
        gc.collect()
        gc.callbacks.append(gc_watch)
        with self.ctx.window():
            t0 = time.perf_counter()
            deadline = seconds + DRAIN_S
            i, backlog = 0, None
            while True:
                now = time.perf_counter() - t0
                while i < n and due[i] <= now:
                    with span("loadgen.submit"):
                        submit[i] = time.perf_counter() - t0
                        req = server.submit(pool[offsets[i]: offsets[i] + sizes[i]])
                    if i in self.kept:
                        self.kept[i] = req
                    pending.append((i, req))
                    i += 1
                if i == n and backlog is None:
                    backlog = server.pending_rows()
                if pending:
                    with span("serve.step"):
                        server.step()
                    t = time.perf_counter() - t0
                    step_s.append(t - now)
                    while pending and pending[0][1].done:
                        finish[pending.popleft()[0]] = t
                elif i < n:
                    wait = due[i] - (time.perf_counter() - t0)
                    if wait > 0:
                        with span("loadgen.idle"):
                            time.sleep(wait)
                else:
                    break
                if now > deadline:
                    break
            elapsed = time.perf_counter() - t0
        gc.callbacks.remove(gc_watch)
        pending.clear()
        answered = np.isfinite(finish)
        # an unanswered request counts as failed; its latency, at least the
        # whole wait, still enters the percentiles
        latency = np.where(answered, finish, elapsed) - due
        late = (submit - due)[np.isfinite(submit)]
        late_p99 = float(np.percentile(late, 99)) * 1e3 if late.size else 0.0
        steps = server.stats.steps - steps0
        step_ms = np.asarray(step_s) * 1e3
        pct = percentiles_ms(latency)
        half = n // 2
        return harness.Window(
            e2e={"serve_p50_ms": pct["p50"]},
            counters={
                "steps": steps, "requests": n,
                "latency_p99_ms": pct["p99"],
                "rows_answered": int(sizes[answered].sum()),
                "slot_busy_rows": server.stats.slot_busy_rows - busy0,
                "slot_idle_rows": server.stats.slot_idle_rows - idle0,
                "loadgen_late_ms_p99": late_p99,
                "backlog_rows_at_close": backlog,
                "p99_ms_first_half": percentiles_ms(latency[:half])["p99"],
                "p99_ms_second_half": percentiles_ms(latency[half:])["p99"],
                "n_models": int(self.W.shape[0]), "n_features": int(self.W.shape[1]),
                "out_bytes": 8 * self.mix["k"], "q_block": server.q_block,
                "query_bytes": 4,
                "elapsed_s": elapsed,
                "gc_pauses": len(pauses),
                "gc_pause_ms_max": max(pauses, default=0.0) * 1e3,
                "steps_over_20ms": int(np.sum(step_ms > 20.0)),
                "step_ms_max": float(step_ms.max()) if steps else 0.0,
            },
            attempted=n,
            failed=int(n - answered.sum()),
            notes=[f"loadgen late p99 {late_p99!r} ms over {late.size} "
                   f"submits; {len(pauses)} full collections, longest "
                   f"{max(pauses, default=0.0) * 1e3:.3f} ms; "
                   f"{n} requests, {int(sizes.sum())} rows, "
                   f"{steps} steps in {elapsed:.3f} s; "
                   f"{int(np.sum(step_ms > 20.0))} steps over 20 ms, longest "
                   f"{float(step_ms.max()) if steps else 0.0:.3f} ms",
                   "latency ms " + " ".join(f"{k} {v!r}" for k, v in pct.items())],
        )

    def check(self) -> dict:
        """The sampled requests, the longest among them, against the
        float64 readout; one that was never answered is counted by
        ``failed`` and has no answer to compare."""
        sample = [j for j, req in self.kept.items() if req is not None and req.done]
        rows = [self.pool[self.offsets[j]: self.offsets[j] + self.sizes[j]]
                for j in sample]
        vals = [self.kept[j].result[0] for j in sample]
        ids = [self.kept[j].result[1] for j in sample]
        W = np.asarray(self.W)
        del self.server, self.W, self.kept
        if not sample:
            return {"score_err": math.inf, "wrong_ids": math.inf}
        decided = 2.0 * self.ctx.limits["limits"]["score_err"]
        return bank_ref.topk_errors(np.concatenate(vals), np.concatenate(ids),
                                    np.concatenate(rows), W, self.mix["k"],
                                    decided)

"""Training window: whole one-pass fits of a bank, back to back.

Set-up makes the cell's stream and its one-vs-rest x C-grid sign rows on
the device from the seed (split over the cell's chips along rows), and runs
one fit through the timed entry, ``repro.core.fit_bank`` (with ``mesh=`` on
more than one chip), so that every program is compiled or loaded before the
window opens. The window repeats that fit over the whole stream, each time
to ``block_until_ready`` of the bank on every device, and ends at a pass
boundary once ``--seconds`` have passed: ``train_rows_per_s`` is the rows of
all passes over the time of all passes.

The check takes one pass of the window, drawn from the seed, and compares
its bank (w, r, xi2, m) for a sample of models with the float64 reference:
one Algorithm-1 fit per stream shard, folded in shard order.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.chip import harness
from benchmarks.chip.refs import bank_ref


class Cell:
    def __init__(self, ctx: harness.Context):
        self.ctx = ctx
        cfg = ctx.cfg
        self.n, self.d = cfg["n_rows"], cfg["n_features"]
        self.k, self.c_grid = cfg["n_classes"], cfg["c_grid"]
        self.b = self.k * len(self.c_grid)
        self.kept = None

    def _fit(self):
        import repro.core

        return repro.core.fit_bank(self.X, self.Y, self.cs, **self.kw)

    def setup(self) -> None:
        import jax

        ctx = self.ctx
        sharding = ctx.stream_sharding()
        self.X, self.labels = ctx.gen.stream(ctx.cfg, ctx.key(), self.n,
                                             sharding)
        self.Y, self.cs = harness.ovr_signs(self.labels, self.k, self.c_grid,
                                            sharding)
        self.kw = {"stream_dtype": "bf16"} if ctx.control else {}
        if ctx.chips > 1:
            self.kw["mesh"] = ctx.mesh()
        jax.block_until_ready(self._fit())

    def measure(self, seconds: float) -> harness.Window:
        import jax

        pick = self.ctx.rng(1)
        with self.ctx.window():
            passes, t0 = 0, time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.pass"):
                    bank = jax.block_until_ready(self._fit())
                passes += 1
                if pick.random() * passes < 1.0:  # uniform over the passes
                    self.kept = bank
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
        return harness.Window(
            e2e={"train_rows_per_s": passes * self.n / elapsed},
            counters={"passes": passes, "n_rows": self.n, "n_models": self.b,
                      "n_features": self.d, "elapsed_s": elapsed,
                      "stream_bytes": 2 if self.ctx.control else 4},
            attempted=passes,
        )

    def models(self) -> np.ndarray:
        """The models the check compares: all, or a sample from the seed
        with every C point in it."""
        k_max = self.ctx.traffic["check_models"]
        if self.b <= k_max:
            return np.arange(self.b)
        rng = self.ctx.rng(2)
        per = -(-k_max // len(self.c_grid))
        return np.concatenate([
            g * self.k + np.sort(rng.choice(self.k, per, replace=False))
            for g in range(len(self.c_grid))
        ])

    def check(self) -> dict:
        models = self.models()
        got = tuple(np.asarray(v)[models] for v in self.kept)
        X = np.asarray(self.X)
        labels = np.asarray(self.labels)
        del self.kept, self.X, self.Y, self.labels, self.cs
        Y, cs = harness.ovr_signs_host(
            labels, models % self.k,
            np.asarray(self.c_grid, np.float64)[models // self.k])
        ref = bank_ref.sharded_ref(X, Y, cs, self.ctx.chips)
        return bank_ref.fit_errors(got, ref)

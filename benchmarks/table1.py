"""Paper Table 1: single-pass accuracies across 8 datasets x 7 algorithms.

Columns match the paper: libSVM(batch) | Perceptron | Pegasos k=1 | Pegasos
k=20 | LASVM | StreamSVM Algo-1 | StreamSVM Algo-2 (L~10). Results are
averaged over `--runs` random stream orders (paper: 20; default here 5 for
CI time). The paper's own numbers print alongside for comparison.

The C-grid model selection trains every grid point in ONE stream pass via the
multi-ball Pallas engine (fit_c_grid -> streamsvm_fit_many) and reports the
measured speedup over a per-model loop of one-model bank fits, which
re-reads the stream once per C.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines import (
    fit_batch_l2svm,
    fit_lasvm,
    fit_pegasos,
    fit_perceptron,
)
from repro.core import fit, fit_bank, fit_c_grid, fit_lookahead
from repro.data import PAPER_TABLE1, load_dataset, preprocess_for
from repro.data.stream import permuted

C_GRID = (1.0, 10.0, 100.0)


def _acc(w, Xte, yte):
    return float(np.mean(np.sign(Xte @ np.asarray(w)) == yte)) * 100.0


def _pick_c(Xj, yj, Xva, yva):
    """Validate C over the grid with ONE pass of the multi-ball engine.

    Returns (c_star, onepass_seconds, permodel_loop_seconds): both paths are
    warmed up first so the timings compare steady-state stream passes (bank
    engine: one data read for the whole grid; loop: one read per grid point).
    """
    grid = jnp.asarray(C_GRID, jnp.float32)

    bank = fit_c_grid(Xj, yj, grid)  # warmup/compile
    jax.block_until_ready(bank.w)
    t0 = time.perf_counter()
    bank = fit_c_grid(Xj, yj, grid)
    jax.block_until_ready(bank.w)
    t_bank = time.perf_counter() - t0

    for c in C_GRID:  # warmup/compile the per-model loop
        jax.block_until_ready(fit_bank(Xj, yj[None], c).w)
    t0 = time.perf_counter()
    for c in C_GRID:
        jax.block_until_ready(fit_bank(Xj, yj[None], c).w)
    t_loop = time.perf_counter() - t0

    accs = [_acc(bank.w[i], Xva, yva) for i in range(len(C_GRID))]
    return C_GRID[int(np.argmax(accs))], t_bank, t_loop


def run(runs: int = 5, datasets=None, lasvm_cap: int = 8000, seed: int = 0):
    """Returns list of row dicts; one per dataset."""
    rows = []
    names = datasets or list(PAPER_TABLE1)
    for name in names:
        Xtr0, ytr0, Xte, yte = load_dataset(name, seed=seed)
        Xtr0, Xte = preprocess_for(name, Xtr0, Xte)
        n_val = max(500, len(ytr0) // 10)
        Xva, yva = Xtr0[-n_val:], ytr0[-n_val:]

        Xj = jnp.asarray(Xtr0)
        yj = jnp.asarray(ytr0)
        c_star, t_grid_onepass, t_grid_loop = _pick_c(Xj, yj, Xva, yva)
        lam = 1.0 / (c_star * len(ytr0))

        accs = {k: [] for k in
                ("perceptron", "pegasos1", "pegasos20", "lasvm", "algo1", "algo2")}
        t0 = time.time()
        for r in range(runs):
            Xp, yp = permuted(Xtr0, ytr0, seed=seed * 1000 + r)
            Xpj, ypj = jnp.asarray(Xp), jnp.asarray(yp)
            wp, _ = fit_perceptron(Xpj, ypj)
            accs["perceptron"].append(_acc(wp, Xte, yte))
            accs["pegasos1"].append(_acc(fit_pegasos(Xpj, ypj, lam, k=1), Xte, yte))
            accs["pegasos20"].append(_acc(fit_pegasos(Xpj, ypj, lam, k=20), Xte, yte))
            if r == 0:  # LASVM is O(N |S| D) python: once per dataset
                # LASVM needs its own C: single-pass online SMO degenerates at
                # large C (one REPROCESS/example cannot unwind saturated
                # alphas), so validate over a small C grid on a prefix.
                best_l = -1.0
                for c_l in (1.0, 10.0):
                    w_try, b_try, _ = fit_lasvm(
                        Xp[: min(2000, lasvm_cap)], yp[: min(2000, lasvm_cap)],
                        C=c_l, return_bias=True,
                    )
                    a_try = float(np.mean(np.sign(Xva @ w_try + b_try) == yva)) * 100
                    if a_try > best_l:
                        best_l, c_lasvm = a_try, c_l
                wl, bl, _ = fit_lasvm(
                    Xp[:lasvm_cap], yp[:lasvm_cap], C=c_lasvm, return_bias=True
                )
                accs["lasvm"].append(
                    float(np.mean(np.sign(Xte @ wl + bl) == yte)) * 100
                )
            accs["algo1"].append(_acc(fit(Xpj, ypj, c_star).w, Xte, yte))
            accs["algo2"].append(
                _acc(fit_lookahead(Xpj, ypj, c_star, 10).w, Xte, yte)
            )
        wbatch, _ = fit_batch_l2svm(Xj, yj, c_star, iters=2000)
        row = {
            "dataset": name,
            "C": c_star,
            "batch": _acc(wbatch, Xte, yte),
            **{k: float(np.mean(v)) for k, v in accs.items()},
            "paper": PAPER_TABLE1[name],
            "seconds": round(time.time() - t0, 1),
            "grid_onepass_s": round(t_grid_onepass, 3),
            "grid_loop_s": round(t_grid_loop, 3),
            "grid_speedup": round(t_grid_loop / max(t_grid_onepass, 1e-9), 2),
        }
        rows.append(row)
    return rows


def main():
    rows = run()
    hdr = ("dataset", "batch", "perceptron", "pegasos1", "pegasos20",
           "lasvm", "algo1", "algo2")
    print(",".join(hdr) + ",paper_batch,paper_algo1,paper_algo2")
    for r in rows:
        p = r["paper"]
        print(
            f'{r["dataset"]},{r["batch"]:.2f},{r["perceptron"]:.2f},'
            f'{r["pegasos1"]:.2f},{r["pegasos20"]:.2f},{r["lasvm"]:.2f},'
            f'{r["algo1"]:.2f},{r["algo2"]:.2f},{p[0]},{p[5]},{p[6]}'
        )
    print()
    print("# C-grid model selection: multi-ball engine (one stream pass for "
          f"{len(C_GRID)} C values) vs a per-model loop of one-model banks")
    for r in rows:
        print(
            f'# {r["dataset"]}: one-pass {r["grid_onepass_s"]:.3f}s, '
            f'loop {r["grid_loop_s"]:.3f}s, speedup {r["grid_speedup"]:.2f}x'
        )


if __name__ == "__main__":
    main()

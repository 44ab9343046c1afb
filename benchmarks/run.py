"""Benchmark aggregator: one function per paper table/figure + system benches.

``PYTHONPATH=src python -m benchmarks.run [--fast]``
Prints ``name,value,derived`` CSV sections.
"""
from __future__ import annotations

import argparse
import time


def _section(title):
    print(f"\n# === {title} ===", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="reduced runs for CI")
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args()
    runs = 3 if args.fast else 5

    t0 = time.time()

    _section("Table 1: single-pass accuracies (ours vs paper)")
    from benchmarks import table1

    rows = table1.run(runs=runs, lasvm_cap=4000 if args.fast else 8000)
    print("dataset,C,batch,perceptron,pegasos_k1,pegasos_k20,lasvm,algo1,algo2,"
          "paper_batch,paper_algo1,paper_algo2")
    for r in rows:
        p = r["paper"]
        print(
            f'{r["dataset"]},{r["C"]},{r["batch"]:.2f},{r["perceptron"]:.2f},'
            f'{r["pegasos1"]:.2f},{r["pegasos20"]:.2f},{r["lasvm"]:.2f},'
            f'{r["algo1"]:.2f},{r["algo2"]:.2f},{p[0]},{p[5]},{p[6]}'
        )

    _section("Fig 2: CVM passes vs one StreamSVM pass")
    from benchmarks import fig2_cvm

    out = fig2_cvm.run(max_passes=16 if args.fast else 32)
    for i, a in enumerate(out["cvm_curve"]):
        print(f"cvm_pass_{i + 1},{a:.2f},acc%")
    print(f"streamsvm_algo2_single_pass,{out['streamsvm_algo2_1pass']:.2f},acc%")
    print(f"cvm_passes_to_match,{out['cvm_passes_to_match_algo2']},passes")

    _section("Fig 3: lookahead vs accuracy/std over stream orders")
    from benchmarks import fig3_lookahead

    for r in fig3_lookahead.run(runs=8 if args.fast else 20):
        print(f'lookahead_L{r["L"]},{r["mean"]:.2f},acc% (std {r["std"]:.3f})')

    _section("Beyond-paper: multi-ball (Sec 4.3) + RBF kernelized (Sec 4.2)")
    from benchmarks import beyond

    for name, val, unit in beyond.run():
        print(f"{name},{val:.2f},{unit}")

    if not args.skip_roofline:
        _section("Roofline (single-pod, from dry-run artifacts)")
        try:
            from benchmarks import roofline

            for r in roofline.analyze():
                if r["status"] == "SKIP":
                    print(f'{r["arch"]}__{r["shape"]},SKIP,{r["why"]}')
                else:
                    print(
                        f'{r["arch"]}__{r["shape"]},{r["dominant"]},'
                        f'comp={r["t_compute_s"]:.4g}s mem={r["t_memory_s"]:.4g}s '
                        f'coll={r["t_collective_s"]:.4g}s frac={r["roofline_frac"]:.3f}'
                    )
        except Exception as e:  # dry-run artifacts may not exist yet
            print(f"roofline_skipped,0,{type(e).__name__}: {e}")

    print(f"\n# total benchmark time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()

"""Benchmark driver — one section per paper table/figure plus the
beyond-paper additions. Prints ``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import sys

from benchmarks import (blocked_smo_scaling, fig_slab_recovery,
                        kernel_microbench, roofline_report,
                        serving_latency, smo_pod_scale,
                        table1_training_time)


def main() -> None:
    quick = "--quick" in sys.argv
    print("# === paper Table 1: training time & MCC vs m ===")
    if quick:
        for r in table1_training_time.run(sizes=(500, 1000)):
            print(f"table1,m={r['m']},paper_smo={r['paper_smo_s']*1e6:.0f}us,"
                  f"mcc={r['paper_smo_mcc']:.3f}")
    else:
        table1_training_time.main([])
    print("# === paper Figs 1-2: slab recovery ===")
    fig_slab_recovery.main()
    print("# === beyond-paper: blocked-SMO scaling ===")
    if not quick:
        blocked_smo_scaling.main()
    print("# === serving: warm cache + bucketed Pallas scoring ===")
    serving_latency.main(["--reduced"] if quick else [])
    print("# === Pallas kernel microbench (interpret mode) ===")
    kernel_microbench.main()
    print("# === the paper's solver at pod scale (m=1M, 256/512 chips) ===")
    smo_pod_scale.main()
    print("# === roofline table from the dry-run sweep ===")
    roofline_report.main()


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()

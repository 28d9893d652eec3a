"""Pallas kernel-autotune sweep: produce BENCH_autotune.json (+ the table).

    PYTHONPATH=src python benchmarks/autotune_kernels.py --quick \
        --precisions f32,bf16 --json BENCH_autotune.json
    PYTHONPATH=src python benchmarks/autotune_kernels.py --quick \
        --precisions f32,bf16 --update-table   # refresh the committed table

Sweeps (block_m, block_n, block_k, depth) per (family, shape, precision)
cell through ``repro.kernels.autotune`` and writes candidate + winner
rows (each with its roofline DMA-vs-compute classification) in the
``results/BENCH_autotune.json`` schema. ``--update-table`` additionally
merges the winners into ``src/repro/kernels/tuned_configs.json`` — the
committed table ``kernels/tiling.resolve_tiles`` consults at trace time.

CI runs ``--quick`` in the kernels-interpret job and diffs the fresh
rows against the committed ``results/BENCH_autotune.json`` baseline via
``benchmarks/check_regression.py`` (>25% slowdown on a gated row, or a
dropped row, fails the job). On CPU the kernels run in interpret mode:
wall numbers are emulation-regression canaries, not TPU projections —
re-run on real hardware to grow the table's "tpu" backend rows
(docs/kernels.md walks through the workflow).
"""
from __future__ import annotations

import argparse
import json

from repro.kernels.autotune import (FULL_CELLS, QUICK_CELLS, sweep,
                                    winners_to_entries, write_table)
from repro.kernels.precision import parse_precisions
from repro.kernels.tiling import TUNED_TABLE_PATH


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--quick", action="store_true",
                      help="CI-sized sweep (default): the tier-1 shapes")
    mode.add_argument("--full", action="store_true",
                      help="larger m / wider d cells for nearest-shape "
                           "interpolation")
    ap.add_argument("--precisions", default="f32",
                    help="comma list of tile precisions (default f32)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="timing repeats per candidate; min is kept "
                         "(default 5)")
    ap.add_argument("--json", default="BENCH_autotune.json",
                    help="where to write the sweep JSON")
    ap.add_argument("--update-table", nargs="?", const=str(TUNED_TABLE_PATH),
                    default=None, metavar="PATH",
                    help="merge winners into the committed tuned table "
                         f"(default path: {TUNED_TABLE_PATH})")
    args = ap.parse_args(argv)

    mode_name = "full" if args.full else "quick"
    cells = FULL_CELLS if args.full else QUICK_CELLS
    result = sweep(cells, mode=mode_name,
                   precisions=parse_precisions(args.precisions),
                   repeats=args.repeats, progress=print)
    with open(args.json, "w") as fh:
        json.dump(result, fh, indent=1)
        fh.write("\n")
    print(f"autotune,{mode_name},backend={result['backend']},"
          f"candidates={len(result['candidates'])},"
          f"winners={len(result['winners'])},json={args.json}")

    if args.update_table is not None:
        doc = write_table(winners_to_entries(result), args.update_table)
        print(f"autotune,table={args.update_table},"
              f"entries={len(doc['entries'])}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())

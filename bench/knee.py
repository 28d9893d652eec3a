#!/usr/bin/env python3
"""Sweep of offered rates for an open-loop cell, to find its knee once:

    python3 bench/knee.py --workload fraud.events --seconds 10 \\
        --rates 500,1000,2000,4000 --seed 3

Runs the cell's own set-up and window at each rate in turn, in one
process, and prints per rate: p50, p95 and p99 latency, the generator's p99
lag, rows per launch, and whether a backlog grew (the last tenth of the
requests waited longer than the first tenth by more than the p50). The
knee is the highest rate whose p99 stays within the limit with no
growing backlog and a small generator lag. Not part of a benchmark
run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402
from bench.lib import catalog  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench, wl, devices = run.prepare(args.workload)
    mix = catalog.traffic(wl["traffic"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        ctx = run.run_cell(bench, wl, seed=args.seed + i,
                           seconds=args.seconds, trace=False,
                           devices=devices, traffic=dict(mix, rate=rate),
                           t_start=time.perf_counter())
        c = ctx.counters
        lat = ctx.kept["latency_ms"]
        tenth = max(1, len(lat) // 10)
        grew = float(sum(lat[-tenth:]) / tenth - sum(lat[:tenth]) / tenth)
        print(json.dumps({
            "rate": rate, "p50_ms": c["p50_ms"],
            "p95_ms": ctx.e2e["score_p95_ms"], "p99_ms": c["p99_ms"],
            "gen_lag_p99_ms": c["gen_lag_p99_ms"],
            "rows_per_launch": c["rows_scored"] / max(1, c["launches"]),
            "backlog_growth_ms": grew, "missing": ctx.failed,
            "correct": ctx.correct}), flush=True)


if __name__ == "__main__":
    main()

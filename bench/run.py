#!/usr/bin/env python3
"""The chip benchmark: one cell, one run, one JSON line.

    python3 bench/run.py --workload fraud.fit --seed 7 --seconds 40 --trace 0

The cell (``--workload``) names a configuration and a traffic mix in
``BENCHMARK.json``; their files under ``bench/configs`` and
``bench/traffic`` say what to build and how to drive it. The run makes
its data from ``--seed``, warms up every shape (set-up), measures for
``--seconds``, then compares what the program produced with the plain
reference. ``--trace 1`` traces the window and reports the cell's
per-layer metrics instead of its end-to-end ones.

The last line of stdout is the result object; the numbers compared, each
with its limit, are the last lines of stderr. A run that finds no TPU,
or fewer chips than the cell asks for, exits non-zero and prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.lib import catalog, gate  # noqa: E402
from bench.lib.result import result_line, print_result  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, holding every program so only a checkout's first run
    compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def prepare(workload: str):
    """BENCHMARK.json, the cell's entry, its chips (or ``NoChip``), and
    the compile cache."""
    bench = catalog.load_benchmark(ROOT)
    wl = catalog.workload(bench, workload)
    devices = gate.devices(int(wl["chips"]))
    use_cache()
    return bench, wl, devices


def main(argv=None) -> None:
    args = parse(argv)
    bench, wl, devices = prepare(args.workload)
    ctx = run_cell(bench, wl, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices)
    print_result(result_line(ctx, bench, wl))


def run_cell(bench, wl, *, seed, seconds, trace, devices, config=None,
             traffic=None, t_start=None):
    """Everything of a run after the device gate. Tests call it with a
    small configuration and the CPU's devices."""
    from bench.lib.cells import RUNNERS
    from bench.lib.context import Context
    cfg = config or catalog.config(bench, wl["config"], ROOT)
    mix = traffic or catalog.traffic(wl["traffic"])
    ctx = Context(name=wl["name"], config=cfg, traffic=mix, seed=seed,
                  seconds=seconds, trace=trace, devices=devices,
                  t_start=T_START if t_start is None else t_start)
    ctx.say(f"device {devices[0].platform} {devices[0].device_kind} "
            f"x{len(devices)}; config {wl['config']}, traffic "
            f"{wl['traffic']}, seed {seed}, {seconds} s")
    RUNNERS[mix["kind"]](ctx)
    return ctx


if __name__ == "__main__":
    main()

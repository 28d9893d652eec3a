#!/usr/bin/env python3
"""One traced run of a cell, reduced by the program's own spans.

    python3 bench/span_report.py --workload fraud.events --seed 7 \
        --seconds 40 --out spans-events.json

Runs the cell as ``bench/run.py --trace 1`` does, and before the trace
is deleted reduces it with ``bench/lib/spans.py`` (in a fit cell over
the window cut to the device record, as the run's own reduction): per
program span (``fit``, ``fit.*``, ``serve.*``) its count, total, self
and idle seconds, and the idle gaps of at least 30 ms with the span that covers
each. In a fit cell it also sums the device time of every operation
inside each fit's n-th ``fit.solve`` span, by operation name, with one
instruction text of each (to match names to a compiled module). Writes
one JSON object to ``--out`` and prints a summary to stderr. It reports
no benchmark metric and is not run by the driver.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import run  # noqa: E402  (bench/run.py: its clock starts at import)
from bench.lib import spans, trace as tracing  # noqa: E402
from bench.lib.result import result_line  # noqa: E402

GAP_S = 0.030


def solve_ops(tr: tracing.Trace, path: str) -> dict:
    """Device time by operation name inside each fit's n-th ``fit.solve``
    span: {n: {name: [seconds, launches, instruction text]}}."""
    from jax.profiler import ProfileData
    fits = sorted((e for e in tr.host if e.name == "fit"),
                  key=lambda e: e.start)
    solves = sorted((e for e in tr.host if e.name == "fit.solve"),
                    key=lambda e: e.start)
    ordinal, k = [], 0
    for s in solves:
        while k + 1 < len(fits) and fits[k + 1].start <= s.start:
            k += 1
        prev = [o for o in solves if fits[k].start <= o.start < s.start]
        ordinal.append(len(prev))
    starts = [s.start for s in solves]
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        for line in plane.lines:
            if line.name not in tracing.OP_LINES:
                continue
            for e in line.events:
                mid = e.start_ns + 0.5 * e.duration_ns
                i = bisect.bisect_right(starts, mid) - 1
                if i < 0 or mid > solves[i].end:
                    continue
                name = tracing.op_name(e.name)
                row = out.setdefault(ordinal[i], {}).setdefault(
                    name, [0.0, 0, e.name[:400]])
                row[0] += e.duration_ns * 1e-9
                row[1] += 1
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    found: dict = {}
    load, reduce = tracing.load, tracing.reduce

    def load_and_keep(path):
        found["path"] = path
        return load(path)

    def reduce_and_keep(tr, **kw):
        threads = tracing.Trace(tr.devices, spans.host_events(found["path"]))
        win = tracing.recorded_window(tr, kw["span"]) if kw.get("span") \
            else tr.window()
        found["spans"] = spans.reduce(threads, win)
        found["gaps"] = spans.idle_gaps(tr, GAP_S, win)
        if any(e.name == "fit.solve" for e in tr.host):
            found["solve_ops"] = solve_ops(tr, found["path"])
        return reduce(tr, **kw)

    tracing.load, tracing.reduce = load_and_keep, reduce_and_keep
    bench, wl, devices = run.prepare(args.workload)
    ctx = run.run_cell(bench, wl, seed=args.seed, seconds=args.seconds,
                       trace=True, devices=devices)
    r = ctx.reduced
    out = {"workload": args.workload, "seed": args.seed,
           "correct": ctx.correct, "e2e": ctx.e2e,
           "metrics": result_line(ctx, bench, wl)["metrics"],
           "counters": {k: v for k, v in ctx.counters.items()
                        if isinstance(v, (int, float))},
           "window_s": r.window_s, "busy_s": r.busy_s,
           "idle_gaps_reduced": r.idle_gaps,
           "spans": {k: dataclasses.asdict(v)
                     for k, v in found["spans"].items()},
           "gaps_30ms": found["gaps"],
           "solve_ops": found.get("solve_ops", {})}
    st = found["spans"]
    out["readings"] = {
        "fit_solve_iter_ms": spans.fit_solve_iter_ms(
            st, ctx.counters.get("fit_iters", 0)),
        "fit_driver_ms": spans.fit_driver_ms(st),
        "host_io_ms": spans.host_io_ms(st)}
    model = ctx.kept.get("model")
    if model is not None:
        # totals since start-up: they include the few warm-up requests
        w = model.ctrl.stats_dict()[model.NAME]["windows"]
        out["windows"] = w
        if w.get("flushed_requests") and "wait_s" in w:
            out["readings"]["queue_wait_ms"] = \
                w["wait_s"] / w["flushed_requests"] * 1e3
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out))

    say = ctx.say
    say(f"per-layer {out['metrics']}")
    say(f"e2e {ctx.e2e}; correct {ctx.correct}; idle "
        f"{r.window_s - r.busy_s:.4f} of {r.window_s:.4f} s")
    for name, s in sorted(found["spans"].items(),
                          key=lambda kv: -kv[1].idle_s):
        say(f"span {name}: n {s.count}, total {s.total_s:.6f} s, self "
            f"{s.self_s:.6f} s, idle {s.idle_s:.6f} s")
    for at, secs, name in found["gaps"]:
        say(f"gap at {at:.4f} s: {secs * 1e3:.3f} ms in {name}")
    if "windows" in out:
        say(f"windows {out['windows']}")
    say(f"readings {out['readings']}")


if __name__ == "__main__":
    main()

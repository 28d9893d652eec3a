"""The result object: the last line of stdout, by the benchmark's
contract, and the numbers compared as the last lines of stderr."""
from __future__ import annotations

import json
import math
import sys

from bench.lib import catalog
from bench.lib.peaks import peaks_for


class Readout:
    """What a per-layer reader sees: the run's counters, its reduced
    trace (None without ``--trace 1``), the chip's peaks and the cell."""

    def __init__(self, ctx):
        self.counters = ctx.counters
        self.e2e = ctx.e2e
        self.trace = ctx.reduced
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.window_s = ctx.window_s
        self.peaks = peaks_for(ctx.devices[0].device_kind)


def _num(x: float) -> float:
    x = float(x)
    if not math.isfinite(x):
        raise ValueError(f"metric value {x} is not finite")
    return x


def result_line(ctx, bench: dict, wl: dict) -> dict:
    dev = ctx.devices[0]
    metrics = {}
    if ctx.trace:
        ro = Readout(ctx)
        wanted = catalog.per_layer(bench, wl["name"])
        for m in wanted:
            v = catalog.reader(m["name"])(ro)
            if v is not None:
                metrics[m["name"]] = {"value": _num(v), "unit": m["unit"]}
    else:
        for m in catalog.end_to_end(bench, wl["name"]):
            # ``<quantity>.<qualifier>`` is a runner's ``<quantity>`` under
            # a name, and a bound, of one cell's own (``fit_s.embed``)
            v = ctx.setup_s if m["name"] == "setup_s" else ctx.e2e.get(
                m["name"], ctx.e2e.get(m["name"].split(".")[0]))
            if v is not None:
                metrics[m["name"]] = {"value": _num(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    out = {"correct": ctx.correct, "attempted": ctx.attempted,
           "failed": ctx.failed, "metrics": metrics, "device": device}
    if ctx.reduced is not None:
        r = ctx.reduced
        device.update(busy_s=r.busy_s, window_s=r.window_s)
        out["breakdown"] = {"device_ops": [list(x) for x in r.device_ops],
                            "idle_gaps": [list(x) for x in r.idle_gaps]}
    out["checks"] = {n: {"value": v, "limit": lim}
                     for n, v, lim in ctx.checks}
    return out


def print_result(out: dict) -> None:
    for n, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {n} = {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)

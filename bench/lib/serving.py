"""Arithmetic shared by the serving cells' per-layer readers."""
from __future__ import annotations

from bench.lib import costs


def _launch_costs(ro):
    c = ro.counters
    for launches, rows in c["buckets"].values():
        if launches:
            yield launches, costs.decision(rows / launches, c["n_sv"],
                                           c["d"])


def decision_roofline(ro):
    t = ro.trace
    if t is None or not t.kernel_s.get("decision_packed"):
        return None
    least = sum(n * one.least_s(ro.peaks) for n, one in _launch_costs(ro))
    return 100.0 * least / t.kernel_s["decision_packed"]


def window_mfu(ro):
    if not ro.counters.get("launches"):
        return None
    flops = sum(n * one.flops for n, one in _launch_costs(ro))
    return 100.0 * flops / (ro.window_s * ro.peaks.flops_per_s)

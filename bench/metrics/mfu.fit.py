"""The whole fit's share of the chip's peak FLOP/s, in %: the logical
FLOPs of the traced fits (one K @ gamma initial scoring pass each,
2 m^2 d, plus the rank-2P update of every traced ``fupdate`` launch at
its own rows) over those fits' own seconds. The fits are the
``bench.fit`` host spans inside the reduced window, which stops where
the device record does (``trace.recorded_window``), so a fit the trace
lost counts neither its FLOPs nor its time. Bounds
``fupdate_roofline.fit`` from the side of the end-to-end time: a change
that takes a kernel off the path leaves its roofline silent, not this.
Moves ``fit_s``."""
from bench.lib import costs


def read(ro):
    c, t = ro.counters, ro.trace
    if t is None or not t.span_count:
        return None
    m, d = c["m"], c["d"]
    flops = t.span_count * 2.0 * m * m * d + sum(
        n * costs.fupdate(rows, d, 2 * c["pairs"]).flops
        for rows, n in costs.fupdate_calls(t, m))
    return 100.0 * flops / (t.span_s * ro.peaks.flops_per_s)

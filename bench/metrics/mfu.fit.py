"""The whole fit's share of the chip's peak FLOP/s, in %: the logical
FLOPs of each fit (one K @ gamma initial scoring pass, 2 m^2 d, plus the
rank-2P update of every traced ``fupdate`` launch at its own rows) over
the fits' wall time. Bounds ``fupdate_roofline.fit`` from the side of
the end-to-end time: a change that takes a kernel off the path leaves
its roofline silent, not this. Moves ``fit_s``."""
from bench.lib import costs


def read(ro):
    c, t = ro.counters, ro.trace
    if t is None or not c.get("fits"):
        return None
    m, d = c["m"], c["d"]
    flops = c["fits"] * 2.0 * m * m * d + sum(
        n * costs.fupdate(rows, d, 2 * c["pairs"]).flops
        for rows, n in costs.fupdate_calls(t, m))
    return 100.0 * flops / (c["fit_seconds"] * ro.peaks.flops_per_s)

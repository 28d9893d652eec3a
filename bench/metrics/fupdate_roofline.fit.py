"""Share of its roofline that the ``fupdate`` kernel reaches in the fit
cell: the least time of its launches over their summed device time in
the trace. Each launch counts as the logical work of its own rows (read
from the launch's operand shapes, so launches on a shrunken active set
count as such) against the 2P rows of an iteration's pairs
(``bench/lib/costs.py``); P is ``repro.fit``'s own, since the launch pads
its selected block to a tile and its shape cannot tell live rows from
padding. Moves ``fit_s``."""
from bench.lib import costs


def read(ro):
    t = ro.trace
    if t is None or not t.kernel_s.get("fupdate"):
        return None
    c = ro.counters
    least = sum(
        n * costs.fupdate(rows, c["d"], 2 * c["pairs"]).least_s(ro.peaks)
        for rows, n in costs.fupdate_calls(t, c["m"]))
    return 100.0 * least / t.kernel_s["fupdate"] if least else None

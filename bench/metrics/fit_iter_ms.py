"""Fit wall time per solver iteration, in ms: the window's summed fit
seconds over its summed iterations. Moves ``fit_s``."""


def read(ro):
    c = ro.counters
    return c["fit_seconds"] / c["fit_iters"] * 1e3 \
        if c.get("fit_iters") else None

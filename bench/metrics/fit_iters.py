"""Solver iterations per cold fit (``SMOResult.iters``), mean over the
window's fits. Moves ``fit_s``."""


def read(ro):
    c = ro.counters
    return c["fit_iters"] / c["fits"] if c.get("fits") else None

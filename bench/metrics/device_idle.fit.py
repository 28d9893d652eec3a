"""Share of the traced window in which no operation ran on the device,
fit cell: the window cut where the device record stops, at the end of
the last fit it holds whole (``trace.recorded_window``). Moves
``fit_s``."""


def read(ro):
    return None if ro.trace is None else 100.0 * ro.trace.idle_share

"""Share of the traced window in which no operation ran on the device,
batch cell. Moves ``score_rows_per_s``."""


def read(ro):
    return None if ro.trace is None else 100.0 * ro.trace.idle_share

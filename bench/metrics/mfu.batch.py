"""The serving window's share of the chip's peak FLOP/s, in %: logical
FLOPs of every launch's live rows against the support set, over the
window's wall time. Moves ``score_rows_per_s``."""
from bench.lib.serving import window_mfu


def read(ro):
    return window_mfu(ro)

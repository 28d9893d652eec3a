"""Share of its roofline that the ``decision_packed`` kernel reaches in
the batch cell: the least time of the window's launches (logical FLOPs
and bytes of each launch's live rows against the support set,
``bench/lib/costs.py``) over the kernel's summed device time in the
trace. Moves ``score_rows_per_s``."""
from bench.lib.serving import decision_roofline


def read(ro):
    return decision_roofline(ro)

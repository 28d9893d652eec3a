"""Mean host time of one flush outside its kernel launches, in ms
(``ScoringService`` flush overhead: concatenation, transfer, scatter,
callbacks), over the window's flushes. Moves ``score_p95_ms``."""


def read(ro):
    c = ro.counters
    return c["flush_overhead_s"] / c["flush_groups"] * 1e3 \
        if c.get("flush_groups") else None

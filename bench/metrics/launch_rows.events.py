"""Live query rows per kernel launch in the events window (program
counters ``BucketStats``): how much the admission windows coalesce.
Moves ``score_p95_ms``."""


def read(ro):
    c = ro.counters
    return c["rows_scored"] / c["launches"] if c.get("launches") else None

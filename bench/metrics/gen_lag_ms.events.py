"""99th percentile of how late the load generator submitted a request
after its due time, in ms. A starved generator shows here, not as a fast
server. Moves ``score_p95_ms``."""


def read(ro):
    return ro.counters.get("gen_lag_p99_ms")

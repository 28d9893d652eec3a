"""The reduction of the program's own spans (``bench/lib/spans.py``), on
a synthetic trace whose answer is worked by hand, and on the committed
fixture, recorded before the program had spans."""
from pathlib import Path

import pytest

from bench.lib import spans, trace
from bench.lib.spans import UNCOVERED, SpanStats
from bench.lib.trace import HostEvent, Op, Trace

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_fupdate_decision.xplane.pb"
NS = 1e-9


def synthetic(extra_devices=0):
    """Window [0, 1000] ns. The device is busy in [100, 200] and
    [400, 700]: idle [0, 100], [200, 400], [700, 1000], 600 ns. On one
    thread a fit holds three spans; on another a park opens before the
    window and a fetch closes after it."""
    host = [
        HostEvent("bench.window", 0, 1000, "main"),
        HostEvent("bench.gen", 0, 1000, "main"),         # not the program's
        HostEvent("fit", 50, 900, "main"),
        HostEvent("fit.solve", 60, 450, "main"),
        HostEvent("fit.repack", 450, 500, "main"),
        HostEvent("fit.rescore", 500, 880, "main"),
        HostEvent("serve.park", -100, 300, "driver"),
        HostEvent("serve.fetch", 950, 1200, "driver"),
    ]
    devices = {"/device:TPU:0": [Op("a", 100, 200), Op("b", 400, 700)]}
    for i in range(extra_devices):
        devices[f"/device:TPU:{i + 1}"] = [Op("c", -5, 1005)]
    return Trace(devices=devices, host=host)


def test_counts_totals_and_self_times_are_clipped_to_the_window():
    st = spans.reduce(synthetic())
    assert {k: v.count for k, v in st.items() if k != UNCOVERED} == {
        "fit": 1, "fit.solve": 1, "fit.repack": 1, "fit.rescore": 1,
        "serve.park": 1, "serve.fetch": 1}
    total = {k: round(v.total_s / NS) for k, v in st.items()}
    assert total == {"fit": 850, "fit.solve": 390, "fit.repack": 50,
                     "fit.rescore": 380, "serve.park": 300,
                     "serve.fetch": 50, UNCOVERED: 0}
    own = {k: round(v.self_s / NS) for k, v in st.items()}
    assert own["fit"] == 850 - 390 - 50 - 380
    assert own["fit.solve"] == 390 and own["serve.park"] == 300


def test_idle_time_goes_to_the_span_that_started_last_and_sums_up():
    st = spans.reduce(synthetic())
    idle = {k: round(v.idle_s / NS) for k, v in st.items() if v.idle_s}
    assert idle == {"serve.park": 50, "fit": 10 + 20, "fit.solve": 40 + 200,
                    "fit.rescore": 180, UNCOVERED: 50, "serve.fetch": 50}
    assert sum(v.idle_s for v in st.values()) == pytest.approx(600 * NS)


def test_idle_parts_average_over_devices_like_busy_time():
    tr = synthetic(extra_devices=1)        # the second device never idles
    st = spans.reduce(tr)
    r = trace.reduce(tr)
    assert sum(v.idle_s for v in st.values()) == pytest.approx(
        r.window_s - r.busy_s)
    assert st["fit.solve"].idle_s == pytest.approx(120 * NS)


def test_long_idle_gaps_name_the_span_covering_most_of_them():
    tr = synthetic()
    gaps = spans.idle_gaps(tr)
    assert [(round(at / NS), round(s / NS), name) for at, s, name in gaps] \
        == [(700, 300, "fit.rescore"), (200, 200, "fit.solve"),
            (0, 100, "serve.park")]
    assert len(spans.idle_gaps(tr, min_s=150 * NS)) == 2


def test_timeline_prefers_the_inner_span_of_two_that_start_together():
    a = HostEvent("fit", 0, 100, "t")
    b = HostEvent("fit.solve", 0, 40, "t")
    assert spans.timeline([a, b]) == [(0, 40, "fit.solve"),
                                      (40, 100, "fit")]


def test_a_trace_without_program_spans_reduces_to_uncovered_idle_only():
    """The committed fixture predates the program's spans: everything
    idle is uncovered, and it agrees with the trace reduction."""
    tr = trace.load(str(FIXTURE))
    st = spans.reduce(tr)
    r = trace.reduce(tr, kernels=("fupdate", "decision_packed"))
    assert list(st) == [UNCOVERED]
    assert st[UNCOVERED].idle_s == pytest.approx(r.window_s - r.busy_s,
                                                 rel=1e-9)
    assert spans.fit_driver_ms(st) is None
    assert spans.host_io_ms(st) is None
    assert spans.fit_solve_iter_ms(st, 100) is None


def test_readings_are_none_without_their_spans_and_exact_with_them():
    assert spans.fit_solve_iter_ms({}, 10) is None
    assert spans.fit_driver_ms({}) is None
    assert spans.host_io_ms({}) is None
    st = {"fit": SpanStats(count=2, total_s=12.0),
          "fit.solve": SpanStats(count=4, total_s=11.5),
          "serve.launch": SpanStats(count=4, idle_s=9.0),
          "serve.pad": SpanStats(count=4, idle_s=0.25),
          "serve.fetch": SpanStats(count=4, idle_s=0.75)}
    assert spans.fit_solve_iter_ms(st, 0) is None
    assert spans.fit_solve_iter_ms(st, 2300) == 11.5 / 2300 * 1e3
    assert spans.fit_driver_ms(st) == 250.0
    assert spans.host_io_ms(st) == 250.0

"""The reduction from a profiler trace to device metrics.

The fixture is a trace recorded on one TPU v5 lite chip: inside a host
span ``bench.window``, three ``fupdate`` launches at 65,536 x 30 rows
(one of them starts before the span on the device's clock) and three
64-row ``decision_packed`` launches against 284,807 support rows, then
10 ms of host sleep.
"""
from pathlib import Path

import pytest

from bench.lib import trace

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_fupdate_decision.xplane.pb"
KERNELS = ("fupdate", "decision_packed")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(FIXTURE))


def test_recorded_trace_has_one_tpu_and_the_window(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    lo, hi = recorded.window()
    assert hi - lo == pytest.approx(23.130489e6)
    assert sum(e.name == "bench.fupdate" for e in recorded.host) == 3


def test_kernel_launches_are_found_by_their_custom_call_name(recorded):
    ops = recorded.devices["/device:TPU:0"]
    fu = trace.kernel_ops(ops, "fupdate")
    dec = trace.kernel_ops(ops, "decision_packed")
    assert [o.name for o in fu] == ["fupdate.1"] * 3
    assert [o.name for o in dec] == ["decision_packed.1"] * 3
    # the decision program's other custom call (a concatenation) is not
    # counted as the kernel
    assert any(o.name == "custom-call" for o in ops)
    assert all(361_000 < o.end - o.start < 362_000 for o in fu)


def test_reduce_kernel_time_busy_idle_and_breakdown(recorded):
    r = trace.reduce(recorded, kernels=KERNELS)
    assert r.window_s == pytest.approx(0.023130489)
    assert r.kernel_calls == {"fupdate": 2, "decision_packed": 3}
    assert r.kernel_s["fupdate"] == pytest.approx(0.000723091)
    assert r.kernel_s["decision_packed"] == pytest.approx(0.001841788)
    assert r.busy_s == pytest.approx(0.004542561)
    assert r.idle_share == pytest.approx(1 - 0.004542561 / 0.023130489)
    names = [n for n, _ in r.device_ops]
    assert names[0] == "decision_packed.1" and "fupdate.1" in names
    assert len(r.device_ops) <= 10 and len(r.idle_gaps) <= 10
    # the longest hole is the 10 ms sleep at the end of the window
    label, secs = r.idle_gaps[0]
    assert label == "$time sleep" or secs > 0.010
    assert secs == pytest.approx(0.012667766)
    assert all(a[1] >= b[1] for a, b in zip(r.idle_gaps, r.idle_gaps[1:]))


def test_launch_operand_shapes_are_read_from_the_custom_call(recorded):
    r = trace.reduce(recorded, kernels=KERNELS)
    assert r.kernel_shapes["fupdate"] == {
        ((65536, 1), (128, 1), (128, 1), (65536, 1), (65536, 512),
         (128, 512)): 2}
    assert r.kernel_shapes["decision_packed"] == {
        ((1, 2), (64, 1), (285184, 1), (285184, 1), (64, 128),
         (285184, 128)): 3}


def test_operand_shapes_stop_at_the_call_and_skip_layouts():
    text = ("%fupdate.7 = f32[4096,1]{1,0:T(8,128)S(1)} custom-call("
            "f32[4096,1]{1,0:T(8,128)} %a, bf16[128,512]{1,0} %b), "
            "custom_call_target=\"tpu_custom_call\", "
            "operand_layout_constraints={f32[4096,1]{1,0}}")
    assert trace.operand_shapes(text) == ((4096, 1), (128, 512))
    assert trace.operand_shapes("%f = f32[] fusion(x)") == ()


def _op(name, s, e, text=None):
    return trace.Op(name, s, e, text or f"%{name} = f32[1] fusion(x)")


def test_union_gaps_and_clipping():
    ops = [_op("a", 0, 10), _op("b", 5, 20), _op("c", 30, 40),
           _op("d", 38, 45), _op("e", 90, 120)]
    assert trace.union(((o.start, o.end) for o in ops), 2, 100) == [
        (2, 20), (30, 45), (90, 100)]
    assert trace.busy_ns(ops, 2, 100) == 18 + 15 + 10
    assert trace.gaps(ops, 2, 100) == [(20, 30), (45, 90)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def test_gap_label_is_the_innermost_covering_host_event():
    host = [trace.HostEvent(trace.WINDOW_SPAN, 0, 100, "python"),
            trace.HostEvent("bench.fit", 10, 90, "python"),
            trace.HostEvent("Execute", 40, 60, "worker")]
    assert trace.label(45, 55, host) == "Execute"
    assert trace.label(20, 30, host) == "bench.fit"
    assert trace.label(95, 99, host) == "none"


def test_reduce_averages_busy_over_devices_and_skips_containers():
    tr = trace.Trace(
        devices={"/device:TPU:0": [
            _op("while.1", 0, 100, "%while.1 = (f32[1]) while(x), body=b"),
            _op("fupdate.3", 10, 30,
                "%fupdate.3 = f32[8,1] custom-call(x), custom_call_target="),
        ], "/device:TPU:1": [_op("fusion.2", 0, 50)]},
        host=[trace.HostEvent(trace.WINDOW_SPAN, 0, 100, "python")])
    r = trace.reduce(tr, kernels=("fupdate",))
    assert r.busy_s == pytest.approx(75e-9)
    assert r.kernel_calls == {"fupdate": 1}
    assert r.kernel_s["fupdate"] == pytest.approx(20e-9)
    assert [n for n, _ in r.device_ops] == ["fusion.2", "fupdate.3"]


def test_op_name_parses_hlo_instructions():
    assert trace.op_name("%fusion.12 = f32[2]{0} fusion(x)") == "fusion.12"
    assert trace.op_name("jit_f(123)") == "jit_f(123)"


def test_trace_without_window_or_devices_is_refused():
    with pytest.raises(ValueError):
        trace.Trace().window()
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace(host=[trace.HostEvent(
            trace.WINDOW_SPAN, 0, 1, "python")]))


FIT = "bench.fit"
FUPDATE = ("%fupdate.6 = f32[1,512]{1,0} custom-call(f32[512,8]{1,0} "
           "%x, f32[16,8]{1,0} %s), custom_call_target=\"tpu_custom_call\"")


def fit_trace(last_op: float) -> trace.Trace:
    """A fit cell's window [0, 1000] ns: five fits of 190 ns, each after
    a 10 ns generation of its rows. The device runs a 50 ns ``fupdate``
    launch and a 30 ns fusion in each fit, and its record stops at
    ``last_op``."""
    host = [trace.HostEvent(trace.WINDOW_SPAN, 0, 1000, "python")]
    ops = []
    for i in range(5):
        t = 200 * i
        host += [trace.HostEvent("bench.gen", t, t + 10, "python"),
                 trace.HostEvent(FIT, t + 10, t + 200, "python")]
        ops += [_op("fupdate.6", t + 20, t + 70, FUPDATE),
                _op("fusion.1", t + 150, t + 180)]
    return trace.Trace(devices={"/device:TPU:0": [o for o in ops
                                                  if o.end <= last_op]},
                       host=host)


def _mfu(r):
    from bench.lib.peaks import peaks_for
    from bench.lib.catalog import reader

    class Ro:
        counters = {"m": 512, "d": 8, "pairs": 8}
        trace = r
        peaks = peaks_for("TPU v5 lite")
    return reader("mfu.fit")(Ro())


def test_a_record_that_stops_mid_window_cuts_the_fit_window():
    # the record stops inside the third fit, after its fupdate launch
    tr = fit_trace(last_op=480)
    assert trace.recorded_window(tr, FIT) == (0, 400)
    r = trace.reduce(tr, kernels=("fupdate",), span=FIT)
    assert r.window_s == pytest.approx(400e-9)
    assert r.recorded_share == pytest.approx(0.4)
    assert (r.span_count, r.span_s) == (2, pytest.approx(380e-9))
    assert r.busy_s == pytest.approx(2 * 80e-9)
    assert r.kernel_calls == {"fupdate": 2}
    assert r.kernel_s["fupdate"] == pytest.approx(100e-9)
    assert r.idle_share == pytest.approx(1 - 160 / 400)
    # no gap runs on into the part the record lost
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(240e-9)
    assert max(s for _, s in r.idle_gaps) == pytest.approx(80e-9)
    # mfu.fit counts the two recorded fits over their own 380 ns
    m, d, s = 512, 8, 16
    flops = 2 * (2.0 * m * m * d) + 2 * (2.0 * m * s * d + 2.0 * m * s)
    assert _mfu(r) == pytest.approx(100 * flops / (380e-9 * 197e12))
    # without a span the window is the whole traced one, as before
    assert trace.reduce(tr, kernels=("fupdate",)).window_s == \
        pytest.approx(1000e-9)


def test_a_whole_record_reduces_as_it_did_without_the_cut(recorded):
    # the last operation ends before the last fit's host span closes
    tr = fit_trace(last_op=1000)
    assert trace.recorded_window(tr, FIT) == (0, 1000)
    cut = trace.reduce(tr, kernels=("fupdate",), span=FIT)
    whole = trace.reduce(tr, kernels=("fupdate",))
    assert (cut.span_count, cut.span_s) == (5, pytest.approx(950e-9))
    assert cut.recorded_share == 1.0
    cut.span_count, cut.span_s = whole.span_count, whole.span_s
    assert cut == whole
    # a trace with no fit spans (the fixture) keeps its whole window
    assert trace.reduce(recorded, kernels=KERNELS, span=FIT) == \
        trace.reduce(recorded, kernels=KERNELS)

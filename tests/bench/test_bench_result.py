"""The last line of stdout and the checks on stderr, by the contract."""
import json
from types import SimpleNamespace

import pytest

from bench.lib import catalog, trace
from bench.lib.context import Context
from bench.lib.result import print_result, result_line

ROOT = catalog.ROOT


class Dev(SimpleNamespace):
    def memory_stats(self):
        return {"peak_bytes_in_use": 1234}


TPU = Dev(platform="tpu", device_kind="TPU v5 lite")


# the operands of one fupdate launch at 65,536 x 30 rows, as traced
FUPDATE_SHAPES = ((65_536, 1), (128, 1), (128, 1), (65_536, 1),
                  (65_536, 512), (128, 512))


def _ctx(name, trace_on):
    bench = catalog.load_benchmark(ROOT)
    wl = catalog.workload(bench, name)
    ctx = Context(name=name, config=catalog.config(bench, wl["config"]),
                  traffic=catalog.traffic(wl["traffic"]), seed=1,
                  seconds=1.0, trace=trace_on, devices=[TPU], t_start=0.0)
    ctx.setup_s, ctx.window_s, ctx.memory_peak_bytes = 12.5, 1.0, 1234
    ctx.attempted = 3
    ctx.e2e["fit_s"] = 6.25
    ctx.counters.update(fits=3, fit_seconds=18.75, fit_iters=20_700,
                        m=65_536, d=30, pairs=8)
    ctx.check("window_compiles", 0, 0)
    ctx.check("kkt_max", 5e-4, 2e-3)
    if trace_on:
        ctx.reduced = trace.Reduced(
            window_s=20.0, busy_s=19.5, kernel_s={"fupdate": 9.0},
            kernel_calls={"fupdate": 20_700},
            kernel_shapes={"fupdate": {FUPDATE_SHAPES: 20_700}},
            device_ops=[("fupdate.6", 9.0)], idle_gaps=[("none", 0.25)],
            span_count=3, span_s=18.75)
    return bench, wl, ctx


@pytest.mark.parametrize("trace_on", [False, True])
def test_result_line_schema(trace_on, capsys):
    bench, wl, ctx = _ctx("fraud.fit", trace_on)
    out = result_line(ctx, bench, wl)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["attempted"] == 3
    dev = out["device"]
    assert dev["platform"] == "tpu" and dev["kind"] == "TPU v5 lite"
    assert dev["count"] == 1 and dev["memory_peak_bytes"] == 1234
    if trace_on:
        want = {m["name"] for m in catalog.per_layer(bench, "fraud.fit")}
        assert set(out["metrics"]) == want
        assert dev["busy_s"] == 19.5 and dev["window_s"] == 20.0
        assert out["breakdown"]["device_ops"] == [["fupdate.6", 9.0]]
        roof = out["metrics"]["fupdate_roofline.fit"]
        assert roof["unit"] == "%" and 0 < roof["value"] <= 100
    else:
        assert out["metrics"] == {"fit_s": {"value": 6.25, "unit": "s"},
                                  "setup_s": {"value": 12.5, "unit": "s"}}
        assert "breakdown" not in out
    print_result(out)
    got = capsys.readouterr()
    assert json.loads(got.out.strip().splitlines()[-1]) == out
    last = got.err.strip().splitlines()[-2:]
    assert last[0].startswith("check window_compiles = 0.0 (limit 0.0) ok")
    assert last[1].startswith("check kkt_max = 0.0005 (limit 0.002) ok")


def test_a_failed_check_makes_the_run_incorrect():
    bench, wl, ctx = _ctx("fraud.fit", False)
    ctx.check("f_rel", 1e-3, 2e-6)
    assert result_line(ctx, bench, wl)["correct"] is False
    ctx.checks = [("x", float("nan"), 1.0)]
    assert ctx.correct is False
    ctx.checks = []
    assert ctx.correct is False       # nothing compared is not correct


def test_readers_return_nothing_without_their_source():
    bench, wl, ctx = _ctx("fraud.fit", False)
    from bench.lib.result import Readout
    ro = Readout(ctx)
    for m in catalog.per_layer(bench, "fraud.fit"):
        v = catalog.reader(m["name"])(ro)
        assert (v is None) == (m["source"] == "device_trace")
    ro.counters = {}
    for m in catalog.per_layer(bench, "fraud.events") + catalog.per_layer(
            bench, "embed.batch"):
        assert catalog.reader(m["name"])(ro) is None


def test_a_cell_reads_its_own_fit_metrics_as_the_runner_reports_them():
    """``embed.fit`` reports ``fit_s`` and the fit readers under names of
    its own (``<name>.embed``), each reading what the plain name reads."""
    bench, wl, ctx = _ctx("embed.fit", True)
    _, fwl, fctx = _ctx("fraud.fit", True)
    own = result_line(ctx, bench, wl)["metrics"]
    plain = result_line(fctx, bench, fwl)["metrics"]
    assert set(own) == {m["name"] for m in catalog.per_layer(bench,
                                                             "embed.fit")}
    assert own and all(v == plain[k[:-len(".embed")]]
                       for k, v in own.items())
    ctx.trace, ctx.reduced = False, None
    assert result_line(ctx, bench, wl)["metrics"] == {
        "fit_s.embed": {"value": 6.25, "unit": "s"},
        "setup_s": {"value": 12.5, "unit": "s"}}

"""Each kind of cell driven end to end on the CPU at a small size, past
the device gate: set-up, window, reference check, result line."""
import time

import jax
import pytest

from bench import run
from bench.lib import catalog, result

ROOT = catalog.ROOT
SMALL = {
    "fraud.fit": ({"fit_rows": 1024}, {}),
    "fraud.events": ({"rows": 1024},
                     {"rate": 200.0, "pool_rows": 4096,
                      "compare_requests": 64}),
    "embed.batch": ({"rows": 512},
                    {"rows": 256, "pool_requests": 4,
                     "compare_share": 0.5}),
    # no fit_rows: an uncut configuration fits all its rows; at 512 rows
    # repro.fit takes the blocked solver, which has no repack to warm up
    "embed.fit": ({"rows": 512, "repack_warmup_margin": None}, {}),
}
FIT_LIMITS = {"not_converged", "sum_err", "box_excess", "kkt_max", "f_rel"}


def small_cell(name, seconds=1.0, seed=2**33 + 5, **kw):
    bench = catalog.load_benchmark(ROOT)
    wl = catalog.workload(bench, name)
    cfg = dict(catalog.config(bench, wl["config"]), **SMALL[name][0])
    mix = dict(catalog.traffic(wl["traffic"]), **SMALL[name][1])
    ctx = run.run_cell(bench, wl, seed=seed, seconds=seconds, trace=False,
                       devices=jax.devices(), config=cfg, traffic=mix,
                       t_start=time.perf_counter(), **kw)
    return bench, wl, ctx


@pytest.mark.parametrize("name", sorted(SMALL))
def test_cell_runs_correct_on_the_cpu(name):
    bench, wl, ctx = small_cell(name)
    out = result.result_line(ctx, bench, wl)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in catalog.end_to_end(bench, name)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"]["window_compiles"]["value"] == 0


def test_an_uncut_config_fits_all_its_rows_under_the_fit_limits():
    bench, wl, ctx = small_cell("embed.fit", seconds=0.5)
    assert "fit_rows" not in ctx.config
    assert ctx.counters["m"] == 512 and ctx.counters["d"] == 768
    assert all(X.shape == (512, 768) for X, _ in ctx.kept["fits"])
    checked = {n: (v, lim) for n, v, lim in ctx.checks}
    assert FIT_LIMITS <= set(checked)
    assert all(v <= lim for v, lim in checked.values()), checked
    out = result.result_line(ctx, bench, wl)
    # its fit time under a name and a bound of its own
    assert set(out["metrics"]) == {"fit_s.embed", "setup_s"}
    assert out["metrics"]["fit_s.embed"]["value"] == ctx.e2e["fit_s"]


def test_a_fit_cell_warms_its_repack_path_up_in_set_up(monkeypatch):
    """With ``repack_warmup_margin`` set-up fits its first problem twice,
    the second time with the shrinking driver's margin at that value;
    the window's fits leave the margin to the driver."""
    import numpy as np
    import repro
    real, calls = repro.fit, []

    def fit(X, spec, **kw):
        # at 512 rows repro.fit takes the blocked solver, which has no
        # margin: record it and drop it
        calls.append((np.asarray(X), kw.pop("margin", None)))
        return real(X, spec, **kw)

    monkeypatch.setattr(repro, "fit", fit)
    monkeypatch.setitem(SMALL, "embed.fit",
                        ({"rows": 512, "repack_warmup_margin": 0.5}, {}))
    _, _, ctx = small_cell("embed.fit", seconds=0.5)
    assert ctx.correct, ctx.checks
    n = ctx.attempted
    assert [mg for _, mg in calls[:n + 2]] == [None, 0.5] + [None] * n
    assert np.array_equal(calls[0][0], calls[1][0])
    assert not np.array_equal(calls[1][0], calls[2][0])

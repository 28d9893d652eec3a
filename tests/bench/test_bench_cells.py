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
}


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

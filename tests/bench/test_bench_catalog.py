"""BENCHMARK.json against the contract's schema, and discovery by name:
a configuration, a traffic mix or a per-layer metric is added by adding
a file and an entry, with no edit to the harness."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench.lib import catalog

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return catalog.load_benchmark(ROOT)


def test_benchmark_json_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_every_cell_resolves_and_reports_what_it_must(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    seen = set()
    for w in bench["workloads"]:
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        cfg = catalog.config(bench, w["config"], ROOT)
        mix = catalog.traffic(w["traffic"])
        assert mix["kind"] in ("fit", "open_loop", "closed_loop")
        assert "window_compiles" in cfg["limits"]
        reported = {m["name"] for m in catalog.end_to_end(bench, w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        layer = catalog.per_layer(bench, w["name"])
        assert layer
        for m in layer:
            assert m["moves"] in reported
            assert callable(catalog.reader(m["name"]))


def test_every_fit_cell_has_the_fit_limits(bench):
    """A fit checks each of these numbers against its configuration
    (``cells.check_worst``): a fit cell on a configuration without them
    could not decide ``correct``."""
    fit_limits = {"window_compiles", "not_converged", "sum_err",
                  "box_excess", "kkt_max", "f_rel"}
    cells = [w for w in bench["workloads"]
             if catalog.traffic(w["traffic"])["kind"] == "fit"]
    assert {w["name"] for w in cells} >= {"fraud.fit", "embed.fit"}
    for w in cells:
        cfg = catalog.config(bench, w["config"], ROOT)
        assert fit_limits <= set(cfg["limits"]), w["name"]
        # the exact limits (0) need no source; the others name theirs
        assert fit_limits - {"window_compiles", "not_converged"} <= set(
            cfg["limit_sources"]), w["name"]


def test_configs_name_their_source_and_cuts(bench):
    for c in bench["configs"]:
        f = ROOT / c["file"]
        assert f.is_file() and c["file"].startswith("bench/")
        body = json.loads(f.read_text())
        assert body["name"] == c["name"] and body["assumed"]
        for k in c["reduced"]:
            assert k in body and not k.endswith(("_dim", "_rank"))
        assert c["source"].startswith("https://")


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path, bench):
    """A dummy cell added as files and entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    b = json.loads(json.dumps(bench))
    (tmp_path / "bench/configs/dummy.json").write_text(
        json.dumps({"name": "dummy", "rows": 7}))
    (tmp_path / "bench/traffic/dummy_mix.json").write_text(
        json.dumps({"kind": "open_loop", "rate": 3}))
    (tmp_path / "bench/metrics/dummy_rows.py").write_text(
        "def read(ro):\n    return ro.counters.get('rows')\n")
    b["configs"].append({"name": "dummy", "source": "https://example.org",
                         "file": "bench/configs/dummy.json", "reduced": [],
                         "why": "a test"})
    b["workloads"].append({"name": "dummy.cell", "config": "dummy",
                           "traffic": "dummy_mix", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "dummy_rows", "unit": "rows",
                           "better": "higher", "source": "program_counter",
                           "layer": "l", "moves": "setup_s",
                           "workloads": ["dummy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    b = catalog.load_benchmark(tmp_path)
    w = catalog.workload(b, "dummy.cell")
    assert catalog.config(b, w["config"], tmp_path)["rows"] == 7
    assert catalog.traffic(w["traffic"], tmp_path / "bench")["rate"] == 3
    [m] = catalog.per_layer(b, "dummy.cell")
    read = catalog.reader(m["name"], tmp_path / "bench")

    class Ro:
        counters = {"rows": 11}
    assert read(Ro()) == 11
    # metrics without a workloads list reach every cell, the new one too
    assert [m["name"] for m in catalog.end_to_end(b, "dummy.cell")] == [
        "setup_s"]


def test_names_that_are_not_names_are_refused():
    for bad in ("../x", "a b", "a/b", ""):
        with pytest.raises(ValueError):
            catalog.traffic(bad)
    with pytest.raises(KeyError):
        catalog.workload({"workloads": []}, "nope")

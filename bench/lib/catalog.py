"""Finds a cell's pieces by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic mix and the readers of its per-layer
metrics. Adding a configuration, a mix or a metric is adding a file and
an entry; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _name(s: str) -> str:
    if not isinstance(s, str) or not NAME.match(s):
        raise ValueError(f"not a benchmark name: {s!r}")
    return s


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return json.loads((bench_dir / "traffic" / f"{_name(name)}.json")
                      .read_text())


def end_to_end(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])]


def reader(metric: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = bench_dir / "metrics" / f"{_name(metric)}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


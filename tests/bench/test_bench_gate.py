"""A run measures a TPU or prints nothing."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.lib import catalog, gate

ROOT = catalog.ROOT


def _run(cwd, env_extra, timeout=300):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fraud.fit",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"},
                                 {"JAX_PLATFORMS": " cpu, "},
                                 {"JAX_PLATFORMS": "cpu",
                                  "REPRO_INTERPRET": "1"}])
def test_gate_refuses_cpu_only_and_interpret_mode(env, monkeypatch):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    with pytest.raises(gate.NoChip) as e:
        gate.devices(1)
    assert e.value.code != 0


def test_gate_refuses_interpret_mode_before_looking_for_a_chip(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("REPRO_INTERPRET", "true")
    with pytest.raises(gate.NoChip, match="interpret"):
        gate.devices(1)


def test_gate_refuses_a_cpu_device(monkeypatch):
    # JAX in this process holds only the CPU
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(gate.NoChip, match="not a TPU"):
        gate.devices(1)


def test_run_on_cpu_exits_nonzero_and_prints_no_result():
    out = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator run" in out.stderr


def test_run_from_benchmark_files_alone_exits_nonzero(tmp_path):
    bench = catalog.load_benchmark(ROOT)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.stdout or "x")

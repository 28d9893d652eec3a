"""The device gate of ``chip_smoke.py``: off a TPU it must refuse with its
own message and print no result, whatever ``JAX_PLATFORMS`` says.

Nothing here reaches for a chip: JAX reads ``JAX_PLATFORMS`` once, when
it is imported, so changing the variable afterwards only changes what
the gate reads, while ``jax.devices()`` stays on the CPU.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("env, refusal", [
    # Unset, as on a stock TPU VM: the gate reaches the device check.
    ({}, "no TPU: jax.devices()[0] is cpu"),
    ({"JAX_PLATFORMS": "tpu,cpu"}, "no TPU: jax.devices()[0] is cpu"),
    ({"JAX_PLATFORMS": "cpu"}, "names only the CPU"),
    ({"JAX_PLATFORMS": "tpu", "REPRO_INTERPRET": "1"}, "REPRO_INTERPRET"),
], ids=["unset", "tpu-first", "cpu-only", "interpret"])
def test_device_gate_refuses_off_tpu(smoke, monkeypatch, env, refusal):
    for var in ("JAX_PLATFORMS", "REPRO_INTERPRET"):
        monkeypatch.delenv(var, raising=False)
    for var, value in env.items():
        monkeypatch.setenv(var, value)
    with pytest.raises(smoke.SmokeFailure) as exc:
        smoke.device_gate(1)
    assert refusal in str(exc.value.code)


def test_script_exits_nonzero_with_no_result_on_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("REPRO_INTERPRET", None)
    proc = subprocess.run([sys.executable, str(SCRIPT)], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "names only the CPU" in proc.stderr

"""Shared test plumbing.

``run_forced_devices`` is the one subprocess harness for everything that
needs >1 jax device: jax pins the device count at first import, and the
main pytest process must stay single-device, so multi-device cells run
their payload in a child python with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and report back as
a JSON line on stdout. The child is pinned to the CPU: on a machine
with a chip it must never reach for the device the parent may hold.
"""
import json
import os
import subprocess
import sys
import textwrap

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_forced_devices(code: str, devices: int = 4,
                       timeout: int = 900) -> dict:
    """Run ``code`` in a child python with ``devices`` forced host
    devices; returns the JSON object printed on its last stdout line."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])

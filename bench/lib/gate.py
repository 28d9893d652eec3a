"""The device gate: a run measures a TPU or nothing."""
from __future__ import annotations

import os


class NoChip(SystemExit):
    """Exits non-zero with the reason; the run prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: no accelerator run: {msg}")


def devices(need: int):
    """The first ``need`` TPU devices, or ``NoChip``: JAX_PLATFORMS
    naming only the CPU, Pallas forced into interpret mode, a first
    device that is not a TPU, or fewer chips than the cell asks for."""
    env = os.environ.get("JAX_PLATFORMS", "")
    if {p.strip() for p in env.split(",") if p.strip()} == {"cpu"}:
        raise NoChip(f"JAX_PLATFORMS={env!r} names only the CPU")
    if os.environ.get("REPRO_INTERPRET", "").strip().lower() in (
            "1", "true", "on"):
        raise NoChip("REPRO_INTERPRET forces Pallas interpret mode")
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"jax.devices()[0] is {devs[0].platform}, not a TPU")
    if len(devs) < need:
        raise NoChip(f"the cell needs {need} chips, JAX sees {len(devs)}")
    return devs[:need]

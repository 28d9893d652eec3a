"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state. Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod: (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis composes with "data" for batch sharding; only DP-style
all-reduces cross the inter-pod links.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis ``Auto``: since jax 0.9 the
    default is ``Explicit``, which makes eager indexing of a
    mesh-sharded array demand an ``out_sharding`` (the shrinking
    driver's active-row gather) — the solver and scorer place their
    operands themselves and want the compiler to propagate layouts."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count "
            "before importing jax (dryrun.py does this)")
    return _auto_mesh(shape, axes, devices[:n])


def make_test_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Small meshes for CPU tests (e.g. (1,1) or (2,2))."""
    n = math.prod(shape)
    return _auto_mesh(shape, axes, jax.devices()[:n])


def make_solver_mesh(*, multi_pod: bool = False, devices=None):
    """Mesh + row-sharding axes for the distributed OCSSVM solver.

    This is how ``repro.fit(strategy="sharded")`` gets its mesh from the
    launch layer instead of hand-rolling one: a fleet that matches the
    production pod topology gets exactly ``make_production_mesh``
    ((16, 16) single-pod / (2, 16, 16) multi-pod), and anything smaller —
    CPU CI under ``--xla_force_host_platform_device_count``, a dev box
    with a handful of chips — gets the SAME axis structure scaled down to
    the available devices, so solver code and tests never see different
    axis names between CI and a pod.

    Returns ``(mesh, data_axes)``: the solver row-shards X/gamma/f over
    ``data_axes`` (("pod", "data") multi-pod, ("data",) otherwise); the
    "model" axis, when present, is untouched by the solver (its arrays
    are replicated over it).
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    if len(devices) >= math.prod((2, 16, 16) if multi_pod else (16, 16)):
        return make_production_mesh(multi_pod=multi_pod), data_axes
    n = len(devices)
    if multi_pod:
        if n < 2 or n % 2:
            raise RuntimeError(
                f"multi_pod solver mesh needs an even device count >= 2, "
                f"found {n}")
        mesh = _auto_mesh((2, n // 2, 1), ("pod", "data", "model"),
                          devices)
    else:
        mesh = _auto_mesh((n, 1), ("data", "model"), devices)
    return mesh, data_axes

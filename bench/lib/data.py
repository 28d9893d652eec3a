"""Seeded data: slab-band rows with background anomalies, made on device.

The rows follow ``make_toy`` of the program (``repro.data``): target rows
spread along the unit diagonal direction and tight across it, anomalies
uniform over a box covering the scene. One departure, so that a
deployment's anomaly rate is a parameter: each row is an anomaly with
probability ``anomaly_rate`` (not an exact count followed by a
permutation).

Every array is made by one jitted call from a key; nothing is read from
disk or the network.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BAND_WIDTH = 0.35
BOX = (-4.0, 10.0)


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may exceed 32 bits): the
    low and high 32-bit words are folded in turn."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key


def key_for(seed: int, *path: int) -> jax.Array:
    key = root_key(seed)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def _rows(key, n: int, d: int, anomaly_rate: float):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    w = jnp.ones((d,), jnp.float32) / jnp.sqrt(jnp.float32(d))
    along = jax.random.normal(k1, (n, 1)) * 2.0 + 3.0
    across = jax.random.normal(k2, (n, d)) * BAND_WIDTH
    across = across - jnp.dot(across, w, precision="highest")[:, None] \
        * w[None, :]
    target = along * w[None, :] + across
    anomaly = jax.random.uniform(k3, (n, d), minval=BOX[0], maxval=BOX[1])
    is_anomaly = jax.random.uniform(k4, (n, 1)) < anomaly_rate
    return jnp.where(is_anomaly, anomaly, target).astype(jnp.float32)


slab_rows = jax.jit(_rows, static_argnums=(1, 2, 3))
slab_rows.__doc__ = """(n, d) f32 rows from ``key``; see the module doc."""


def feasible_gamma(key, m: int, *, total: float, lo: float, hi: float):
    """Dual coefficients that meet the slab's box and equality with every
    row a support vector: total/m times weights in [0.5, 1.5] normalised
    to mean 1 (all positive, as the chip fit gives at these nu)."""
    w = jax.random.uniform(key, (m,), minval=0.5, maxval=1.5)
    g = (w / jnp.mean(w)) * (total / m)
    if float(jnp.max(g)) > hi or float(jnp.min(g)) < lo:
        raise ValueError(f"drawn gamma leaves the box [{lo}, {hi}]")
    return g.astype(jnp.float32)


def poisson_gaps(n: int, rate: float) -> np.ndarray:
    """n inter-arrival gaps whose empirical distribution is exactly the
    exponential at ``rate``: its quantiles at (i + 0.5)/n. Every seed gets
    this same set, in an order of its own."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate

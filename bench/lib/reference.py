"""The plain reference: kernel rows, f = K @ gamma, slab decision values
and the KKT violation, written from the paper's equations with nothing
of the program imported.

Contractions run at a stated number of passes:

* ``"highest"``: ``Precision.HIGHEST`` (full f32 on a TPU; exact f32 on
  the CPU) — the reference itself;
* ``3``: bf16x3, each operand split into a bf16 high part and a bf16
  low part, and the three products hi*hi + hi*lo + lo*hi summed — what
  ``Precision.HIGH`` does on a TPU;
* ``1``: one bf16 pass (operands rounded to bf16) — XLA's and Mosaic's
  default for f32 on a TPU.

The lower two are emulated explicitly (every bf16 x bf16 product is exact
in f32), so the controls read the same on the CPU and on the chip.
Rounding to bf16 is done on the bits: with XLA's excess precision
allowed, a TPU compile dropped the low-part products of an emulation
written with dtype casts, and bf16x3 then read like one pass.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(a):
    """``a`` rounded to the nearest bf16 value (ties to even), kept in f32.
    Integer arithmetic on the bits: a compiler allowed excess precision
    may drop an f32 -> bf16 -> f32 round trip, but not this."""
    u = jax.lax.bitcast_convert_type(a, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def contract(a, b, passes, dims):
    """``dot_general(a, b, dims)`` at ``passes`` (see the module doc)."""
    def dg(x, y):
        return jax.lax.dot_general(x, y, dims, precision=HIGHEST,
                                   preferred_element_type=jnp.float32)
    if passes == "highest":
        return dg(a, b)
    ah, bh = _bf16(a), _bf16(b)
    if passes == 1:
        return dg(ah, bh)
    if passes == 3:
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return dg(ah, bh) + (dg(ah, bl) + dg(al, bh))
    raise ValueError(f"passes must be 'highest', 3 or 1, got {passes!r}")


_NT = (((1,), (1,)), ((), ()))      # (n, d) x (k, d) -> (n, k)
_MV = (((1,), (0,)), ((), ()))      # (n, k) x (k,)   -> (n,)


def rbf_rows(q, t, t_norms, kernel_gamma: float, passes):
    """exp(-g ||q - t||^2) for q (n, d) against t (k, d); norms in f32."""
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    sq = qn + t_norms[None, :] - 2.0 * contract(q, t, passes, _NT)
    return jnp.exp(-kernel_gamma * jnp.maximum(sq, 0.0))


@partial(jax.jit, static_argnames=("kernel_gamma", "passes", "block"))
def _scores_chunk(qc, t, gamma, *, kernel_gamma, passes, block):
    tn = jnp.sum(t * t, axis=1)

    def one(qb):
        k = rbf_rows(qb, t, tn, kernel_gamma, passes)
        return contract(k, gamma, passes, _MV)

    nb = qc.shape[0] // block
    return jax.lax.map(one, qc.reshape(nb, block, qc.shape[1])).reshape(-1)


def raw_scores(q, t, gamma, kernel_gamma: float, passes="highest",
               block: int = 256, chunk: int = 16384) -> np.ndarray:
    """s(q) = k(q, t) @ gamma for every query row, in blocks of ``block``
    rows so that no (n, k) kernel block is ever whole in memory. One
    compiled program per (chunk, t) shape; queries are padded to it."""
    q = jnp.asarray(q, jnp.float32)
    t = jnp.asarray(t, jnp.float32)
    gamma = jnp.asarray(gamma, jnp.float32)
    n = q.shape[0]
    chunk = min(chunk, -(-n // block) * block)
    out = []
    for i in range(0, n, chunk):
        qc = q[i:i + chunk]
        if qc.shape[0] < chunk:
            qc = jnp.pad(qc, ((0, chunk - qc.shape[0]), (0, 0)))
        out.append(np.asarray(_scores_chunk(
            qc, t, gamma, kernel_gamma=float(kernel_gamma), passes=passes,
            block=block)))
    return np.concatenate(out)[:n]


def decision(q, t, gamma, rho1: float, rho2: float, kernel_gamma: float,
             passes="highest") -> np.ndarray:
    """Slab decision values (s - rho1) * (rho2 - s)."""
    s = raw_scores(q, t, gamma, kernel_gamma, passes).astype(np.float64)
    return ((s - rho1) * (rho2 - s)).astype(np.float32)


def kkt_violation(gamma, f, rho1: float, rho2: float, *, hi: float,
                  lo: float, bound_tol: float = 1e-8) -> np.ndarray:
    """Per-row KKT violation of the slab dual (the paper's five cases):

        gamma = 0        -> rho1 <= f <= rho2
        0 < gamma < hi   -> f = rho1
        gamma = hi       -> f <= rho1
        lo < gamma < 0   -> f = rho2
        gamma = lo       -> f >= rho2

    A coefficient within ``bound_tol * m`` of the box size of a bound
    counts as at that bound."""
    g = np.asarray(gamma, np.float64)
    f = np.asarray(f, np.float64)
    m = g.shape[0]
    bt_hi, bt_lo = hi * bound_tol * m, -lo * bound_tol * m
    at_zero = np.abs(g) <= min(bt_hi, bt_lo)
    at_hi = g >= hi - bt_hi
    at_lo = g <= lo + bt_lo
    free_pos = ~at_zero & ~at_hi & (g > 0)
    free_neg = ~at_zero & ~at_lo & (g < 0)
    v = np.zeros(m)
    v = np.where(at_zero, np.maximum(np.maximum(rho1 - f, f - rho2), 0), v)
    v = np.where(free_pos, np.abs(f - rho1), v)
    v = np.where(at_hi, np.maximum(f - rho1, 0), v)
    v = np.where(free_neg, np.abs(f - rho2), v)
    v = np.where(at_lo, np.maximum(rho2 - f, 0), v)
    return v

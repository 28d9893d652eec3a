"""Tiled Gram-matrix Pallas kernel: K = k(X, Y) block by block.

Grid: (m/TM, n/TN, d/TK), k innermost. The (TM, TN) output tile is revisited
across the k axis and accumulates X_tile @ Y_tile^T on the MXU
(f32 accumulation); the kernel-function epilogue (RBF exponential / poly
power) runs once on the last k step, on the VPU, while the tile is still in
VMEM — no second HBM pass.

VMEM per step ~ TM*TK + TN*TK + TM*TN floats; defaults (256, 256, 512) give
~0.9 MB, comfortably inside the ~16 MB/core v5e VMEM with double buffering.
All tile dims are multiples of 128 to keep MXU matmuls hardware-aligned.

Mixed precision: the x/y data tiles may arrive in bf16/f16 (ops.py casts
them once, halving the HBM stream); ``dot_general`` still accumulates via
``preferred_element_type=jnp.float32``, and the norm operands, accumulator
and epilogue are always f32 — only the streamed bytes shrink.
f32 operands contract at full f32 (``mxu_precision``): Mosaic's default
is one bf16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.precision import mxu_precision


def _gram_kernel(xn_ref, yn_ref, x_ref, y_ref, out_ref, *, nk: int,
                 kind: str, gamma: float, coef0: float, degree: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]
    y = y_ref[...]
    out_ref[...] += jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())),
        precision=mxu_precision(x.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        dot = out_ref[...]
        if kind == "rbf":
            sq = xn_ref[...] + yn_ref[...].T - 2.0 * dot
            out_ref[...] = jnp.exp(-gamma * jnp.maximum(sq, 0.0))
        elif kind == "poly":
            out_ref[...] = (gamma * dot + coef0) ** degree
        # linear: accumulated dot is already the answer.


def gram_pallas(x, y, xn, yn, *, kind: str, gamma: float, coef0: float,
                degree: int, tm: int = 256, tn: int = 256, tk: int = 512,
                interpret: bool = False):
    """x: (M, D), y: (N, D), xn/yn: (M,1)/(N,1) squared norms (RBF only).

    Shapes must already be padded to tile multiples (ops.py does that).
    """
    M, D = x.shape
    N, _ = y.shape
    nk = D // tk
    grid = (M // tm, N // tn, nk)
    kernel = functools.partial(_gram_kernel, nk=nk, kind=kind, gamma=gamma,
                               coef0=coef0, degree=degree)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((tn, 1), lambda i, j, k: (j, 0)),
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((tn, tk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(xn, yn, x, y)

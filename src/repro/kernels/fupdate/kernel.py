"""Fused SMO f-cache update Pallas kernel.

Computes   f_new = f + k(X, X_sel) @ delta   in ONE pass over X:
the 2P selected rows and the delta vector live in VMEM for the whole grid;
each (TM, TK) tile of X streams HBM->VMEM once, accumulates the partial
dot X_tile @ X_sel_tile^T into a (TM, 2P) VMEM scratch, and on the last k
step applies the kernel epilogue + the rank-2P matvec into f.

This is the TPU-native replacement for the paper's per-row Gram cache: at
2d FLOPs per d*4 streamed bytes *per selected column*, a 2P = 16..64 block
turns the memory-bound AXPY of scalar SMO into an MXU matmul.

Grid: (M/TM, D/TK), k innermost. VMEM: TM*TK + 2P*TK + TM*2P + TM floats.

Mixed precision: the x / x_sel data tiles may arrive in bf16/f16 (ops.py
casts them once — the X stream is the whole per-iteration HBM bill);
``dot_general`` accumulates via ``preferred_element_type=jnp.float32`` and
the norms, delta/f operands, scratch accumulator and epilogue stay f32.
f32 operands contract at full f32 (``mxu_precision``): Mosaic's default
is one bf16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.precision import mxu_precision


def _fupdate_kernel(xn_ref, seln_ref, delta_ref, f_ref, x_ref, xsel_ref,
                    out_ref, acc_ref, *, nk: int, kind: str, gamma: float,
                    coef0: float, degree: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]          # (TM, TK)
    xs = xsel_ref[...]      # (2P, TK)
    acc_ref[...] += jax.lax.dot_general(
        x, xs, (((1,), (1,)), ((), ())),
        precision=mxu_precision(x.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        dot = acc_ref[...]                          # (TM, 2P)
        if kind == "rbf":
            sq = xn_ref[...] + seln_ref[...].T - 2.0 * dot
            krows = jnp.exp(-gamma * jnp.maximum(sq, 0.0))
        elif kind == "poly":
            krows = (gamma * dot + coef0) ** degree
        else:
            krows = dot
        out_ref[...] = f_ref[...] + jnp.dot(
            krows, delta_ref[...], precision=mxu_precision(jnp.float32))


def fupdate_pallas(x, xsel, delta, f, xn, seln, *, kind: str, gamma: float,
                   coef0: float, degree: int, tm: int = 512, tk: int = 512,
                   interpret: bool = False):
    """x: (M, D); xsel: (S, D); delta: (S, 1); f, xn: (M, 1); seln: (S, 1).

    Returns f + k(x, xsel) @ delta, shape (M, 1). Shapes pre-padded.
    """
    M, D = x.shape
    S, _ = xsel.shape
    nk = D // tk
    grid = (M // tm, nk)
    kernel = functools.partial(_fupdate_kernel, nk=nk, kind=kind,
                               gamma=gamma, coef0=coef0, degree=degree)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, 1), lambda i, k: (i, 0)),    # xn
            pl.BlockSpec((S, 1), lambda i, k: (0, 0)),     # seln
            pl.BlockSpec((S, 1), lambda i, k: (0, 0)),     # delta
            pl.BlockSpec((tm, 1), lambda i, k: (i, 0)),    # f
            pl.BlockSpec((tm, tk), lambda i, k: (i, k)),   # x
            pl.BlockSpec((S, tk), lambda i, k: (0, k)),    # xsel
        ],
        out_specs=pl.BlockSpec((tm, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tm, S), jnp.float32)],
        interpret=interpret,
    )(xn, seln, delta, f, x, xsel)

"""Slab decision-function Pallas kernel (the serving hot path).

For query tile Q (TM, D) and training tiles T_j (TN, D), accumulates
s = sum_j k(Q, T_j) @ gamma_j in VMEM scratch, then applies the slab rule
(s - rho1) * (rho2 - s) in the epilogue. One HBM pass over the support set
per query tile; D is kept resident (the OCSSVM feature dim is small —
d_model-sized at most after the head pooling).

Grid: (NQ/TM, M/TN), j innermost.

Mixed precision: the q / t data tiles may arrive in bf16/f16 (ops.py casts
queries per request; the support block is packed in the serving dtype once
at model-pack time); ``dot_general`` accumulates via
``preferred_element_type=jnp.float32`` and gamma, the norms, the VMEM
accumulator and the slab epilogue stay f32.
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


def _decision_kernel(rho_ref, qn_ref, tn_ref, gamma_ref, q_ref, t_ref,
                     out_ref, acc_ref, *, nj: int, kind: str, gamma: float,
                     coef0: float, degree: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]          # (TM, D)
    t = t_ref[...]          # (TN, D)
    dot = jax.lax.dot_general(q, t, (((1,), (1,)), ((), ())),
                              precision=mxu_precision(q.dtype),
                              preferred_element_type=jnp.float32)
    if kind == "rbf":
        sq = qn_ref[...] + tn_ref[...].T - 2.0 * dot
        krows = jnp.exp(-gamma * jnp.maximum(sq, 0.0))
    elif kind == "poly":
        krows = (gamma * dot + coef0) ** degree
    else:
        krows = dot
    acc_ref[...] += jnp.dot(krows, gamma_ref[...],
                            precision=mxu_precision(jnp.float32))

    @pl.when(j == nj - 1)
    def _epilogue():
        s = acc_ref[...]
        rho1 = rho_ref[0, 0]
        rho2 = rho_ref[0, 1]
        out_ref[...] = (s - rho1) * (rho2 - s)


def decision_pallas(q, t, gamma_vec, rho, qn, tn_, *, kind: str,
                    gamma: float, coef0: float, degree: int,
                    tm: int = 256, tn: int = 512, interpret: bool = False):
    """q: (NQ, D); t: (M, D); gamma_vec: (M, 1); rho: (1, 2);
    qn: (NQ, 1); tn_: (M, 1). Returns slab decision values (NQ, 1)."""
    NQ, D = q.shape
    M, _ = t.shape
    nj = M // tn
    grid = (NQ // tm, nj)
    kernel = functools.partial(_decision_kernel, nj=nj, kind=kind,
                               gamma=gamma, coef0=coef0, degree=degree)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),      # rho
            pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),     # qn
            pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),     # tn
            pl.BlockSpec((tn, 1), lambda i, j: (j, 0)),     # gamma
            pl.BlockSpec((tm, D), lambda i, j: (i, 0)),     # q
            pl.BlockSpec((tn, D), lambda i, j: (j, 0)),     # t
        ],
        out_specs=pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((NQ, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tm, 1), jnp.float32)],
        interpret=interpret,
    )(rho, qn, tn_, gamma_vec, q, t)

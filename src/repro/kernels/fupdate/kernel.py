"""Fused SMO f-cache update Pallas kernel.

Computes   f_new = f + k(X, X_sel) @ delta   in ONE pass over X:
the S selected rows and the delta vector live in VMEM for the whole grid;
each (TM, TK) tile of X streams HBM->VMEM once and accumulates the
transposed partial block  K^T (S, TM) += X_sel_tile . X_tile^T  (the NT
contraction attention kernels use for q.k^T) in a VMEM scratch. On the
last k step the epilogue applies the kernel function and the rank-S
matvec  f (1, TM) += delta^T . K^T.

Every per-row vector — the norms of X, f and the output — has TM on the
lane axis, (1, TM), so it is exchanged with HBM lane-dense: the solver's
(m,) f-cache reaches the kernel by a reshape.

This is the TPU-native replacement for the paper's per-row Gram cache: at
2d FLOPs per d*4 streamed bytes *per selected row*, a 2P = 16..64 block
turns the memory-bound AXPY of scalar SMO into an MXU matmul.

Grid: (M/TM, D/TK), k innermost (a last row block may be partial: its
rows past M are never written). VMEM: TM*TK + S*TK + S*TM + 3*TM floats.

Mixed precision: the x / x_sel data tiles may arrive in bf16/f16 (ops.py
casts them once — the X stream is the whole per-iteration HBM bill);
``dot_general`` accumulates via ``preferred_element_type=jnp.float32`` and
the norms, delta/f operands, scratch accumulator and epilogue stay f32.
f32 operands contract at full f32 (``mxu_precision``): Mosaic's default
is one bf16 pass. The rank-S matvec runs on the VPU in f32: products are
exact, and the sum over S adds one 8-row group at a time, then the 8
rows, so padded selected rows (masked to exactly 0) never change a bit
of f.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.precision import mxu_precision

GROUP = 8   # f32 sublanes: the rank-S matvec sums S in groups of 8 rows


def _fupdate_kernel(xn_ref, seln_ref, delta_ref, f_ref, x_ref, xsel_ref,
                    out_ref, acc_ref, *, nk: int, s_live: int, kind: str,
                    gamma: float, coef0: float, degree: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xs = xsel_ref[...]      # (S, TK)
    x = x_ref[...]          # (TM, TK)
    acc_ref[...] += jax.lax.dot_general(
        xs, x, (((1,), (1,)), ((), ())),
        precision=mxu_precision(x.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        tm = acc_ref.shape[1]
        xn = xn_ref[...]                            # (1, TM)

        def group(g, total):
            r = pl.multiple_of(g * GROUP, GROUP)
            dot = acc_ref[pl.ds(r, GROUP), :]       # (8, TM)
            if kind == "rbf":
                sq = xn + seln_ref[pl.ds(r, GROUP), :] - 2.0 * dot
                kt = jnp.exp(-gamma * jnp.maximum(sq, 0.0))
            elif kind == "poly":
                kt = (gamma * dot + coef0) ** degree
            else:
                kt = dot
            # Padded selected rows: rbf's exp(-gamma |x|^2) and poly's
            # coef0^degree are nonzero there, so mask them to exactly 0.
            row = r + jax.lax.broadcasted_iota(jnp.int32, (GROUP, tm), 0)
            kt = jnp.where(row < s_live, kt, 0.0)
            return total + delta_ref[pl.ds(r, GROUP), :] * kt

        n_groups = acc_ref.shape[0] // GROUP
        total = jax.lax.fori_loop(0, n_groups, group,
                                  jnp.zeros((GROUP, tm), jnp.float32),
                                  )
        out_ref[...] = f_ref[...] + jnp.sum(total, axis=0, keepdims=True)


def fupdate_pallas(x, xsel, delta, f, xn, seln, *, s_live: int, kind: str,
                   gamma: float, coef0: float, degree: int, tm: int,
                   tk: int, interpret: bool = False):
    """x: (M, D); xsel: (S, D); delta, seln: (S, 1); f, xn: (1, M).

    Returns f + k(x, xsel[:s_live]) @ delta[:s_live], shape (1, M).
    D is a multiple of tk, S of 8; M need not be a multiple of tm.
    """
    M, D = x.shape
    S, _ = xsel.shape
    nk = D // tk
    grid = (pl.cdiv(M, tm), nk)
    kernel = functools.partial(_fupdate_kernel, nk=nk, s_live=s_live,
                               kind=kind, gamma=gamma, coef0=coef0,
                               degree=degree)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tm), lambda i, k: (0, i)),    # xn
            pl.BlockSpec((S, 1), lambda i, k: (0, 0)),     # seln
            pl.BlockSpec((S, 1), lambda i, k: (0, 0)),     # delta
            pl.BlockSpec((1, tm), lambda i, k: (0, i)),    # f
            pl.BlockSpec((tm, tk), lambda i, k: (i, k)),   # x
            pl.BlockSpec((S, tk), lambda i, k: (0, k)),    # xsel
        ],
        out_specs=pl.BlockSpec((1, tm), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, M), jnp.float32),
        scratch_shapes=[pltpu.VMEM((S, tm), jnp.float32)],
        # f is updated in place: block i of f is read before block i of
        # the output is written, and no other block reads it.
        input_output_aliases={3: 0},
        interpret=interpret,
    )(xn, seln, delta, f, x, xsel)

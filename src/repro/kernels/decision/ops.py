"""Jit'd wrapper for the slab decision kernel.

``precision`` casts the query/support data tiles to bf16/f16 before the
kernel (the support set is the serving HBM bill); gamma, the norms, the
accumulator and the slab epilogue ``(s - rho1) * (rho2 - s)`` stay f32
(see ``repro.kernels.precision``). On the packed fast path the support
block is stored in the serving dtype once, at model-pack time.

Tile sizes: the convenience ``decision`` entry point resolves
``tm``/``tn`` from the autotune table when they are left ``None`` (the
committed ``kernels/tuned_configs.json``, keyed on (family="decision",
support rows, D, precision, backend), nearest-shape fallback to the
fixed constants (256, 512); ``REPRO_NO_AUTOTUNE=1`` or explicit kwargs
opt out — docs/kernels.md). ``decision_packed`` does NOT consult the
table: its tile geometry is baked into the packed operands at
model-pack time (``serve.model_cache.pack_model``) and the scorer
passes it explicitly — resolving it per launch could disagree with the
pack and reject the operands.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.kernel_fn import KernelFn
from repro.kernels.tiling import (_auto_interpret, _pad_to, backend_name,
                                  resolve_tiles)
from repro.kernels.decision.kernel import decision_pallas
from repro.kernels.precision import check_launch_precision, tile_dtype


@partial(jax.jit, static_argnames=("kernel", "tm", "tn", "interpret",
                                   "precision"))
def decision(q, t, gamma_vec, rho1, rho2, kernel: KernelFn, *,
             tm: int | None = None, tn: int | None = None,
             interpret: bool | None = None, precision: str = "f32"):
    """Slab decision values for queries q against support set (t, gamma).

    Args:
      q: (NQ, D) query rows; padded internally to tile multiples (extra
        query rows are sliced away).
      t: (M, D) support rows; extra rows get gamma = 0 (no contribution).
        The feature dim is zero-padded to a lane multiple (no effect on
        dot products or norms).
      gamma_vec: (M,) f32 dual coefficients.
      rho1, rho2: slab offsets (scalars, f32).
      kernel: ``repro.core.KernelFn``; name/scalars static.
      tm, tn: query / support block sizes (multiples of 128). ``None``
        (default) resolves from the autotune table; passing either opts
        out of the table (rest fall back to 256/512). The feature dim is
        kept whole (no k-blocking) — OCSSVM feature dims are small.
      interpret: force Pallas interpret mode; ``None`` auto-detects.
      precision: tile-input stream dtype ("f32"/"bf16"/"f16").

    Returns:
      (NQ,) f32 slab decision values ``(s - rho1) * (rho2 - s)``.
    """
    if interpret is None:
        interpret = _auto_interpret()
    check_launch_precision(precision, interpret)
    cfg = resolve_tiles("decision", m=t.shape[0], d=t.shape[1],
                        precision=precision,
                        backend=backend_name(interpret),
                        block_m=tm, block_n=tn)
    tm, tn = cfg.block_m, cfg.block_n
    dt = tile_dtype(precision)
    nq = q.shape[0]
    q = _pad_to(_pad_to(q.astype(jnp.float32), tm, 0), 128, 1).astype(dt)
    t = _pad_to(_pad_to(t.astype(jnp.float32), tn, 0), 128, 1).astype(dt)
    gv = _pad_to(gamma_vec.astype(jnp.float32)[:, None], tn, 0)
    qf = q.astype(jnp.float32)
    tf = t.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=-1, keepdims=True)
    tn_ = jnp.sum(tf * tf, axis=-1, keepdims=True)
    rho = jnp.stack([jnp.asarray(rho1, jnp.float32),
                     jnp.asarray(rho2, jnp.float32)])[None, :]
    out = decision_pallas(q, t, gv, rho, qn, tn_, kind=kernel.name,
                          gamma=kernel.gamma, coef0=kernel.coef0,
                          degree=kernel.degree, tm=tm, tn=tn,
                          interpret=interpret)
    return out[:nq, 0]


@partial(jax.jit, static_argnames=("kernel", "tm", "tn", "interpret",
                                   "precision"))
def decision_packed(q_pad, t_pad, gamma_pad, t_norms, rho1, rho2,
                    kernel: KernelFn, *, tm: int = 256, tn: int = 512,
                    interpret: bool | None = None, precision: str = "f32"):
    """Decision values against a support set already packed to the tile grid.

    The serving fast path: ``t_pad`` (M_pad, d_pad), ``gamma_pad``
    (M_pad, 1) and ``t_norms`` (M_pad, 1) were padded/precomputed once at
    model-compaction time (gamma is zero on padding rows, so they
    contribute nothing), and the query block arrives pre-padded to a
    bucket shape — the per-request work is one cast + ||q||^2 reduction
    plus the kernel launch. ``t_pad`` is expected already in the serving
    tile dtype (``pack_model`` stores it that way; the cast here is a
    no-op then), ``t_norms`` is always f32 and was computed from the
    rounded rows. Returns all ``q_pad.shape[0]`` values; the caller
    slices its live rows.

    ``tm``/``tn`` here are part of the pack geometry (``pack_model``'s
    ``tn``, the scorer's bucket ``tm``) and are always passed
    explicitly by the serving stack — the autotune table is not
    consulted (see the module docstring).
    """
    if interpret is None:
        interpret = _auto_interpret()
    check_launch_precision(precision, interpret)
    dt = tile_dtype(precision)
    if q_pad.shape[0] % tm or t_pad.shape[0] % tn or q_pad.shape[1] % 128:
        raise ValueError(
            f"decision_packed needs pre-padded operands: got q "
            f"{q_pad.shape} (rows % tm={tm}, features % 128) and t "
            f"{t_pad.shape} (rows % tn={tn})")
    if q_pad.shape[1] != t_pad.shape[1]:
        raise ValueError(f"feature-dim mismatch: q {q_pad.shape} vs "
                         f"t {t_pad.shape}")
    q_pad = q_pad.astype(jnp.float32).astype(dt)
    t_pad = t_pad.astype(dt)
    qf = q_pad.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=-1, keepdims=True)
    rho = jnp.stack([jnp.asarray(rho1, jnp.float32),
                     jnp.asarray(rho2, jnp.float32)])[None, :]
    out = decision_pallas(q_pad, t_pad, gamma_pad, rho, qn, t_norms,
                          kind=kernel.name, gamma=kernel.gamma,
                          coef0=kernel.coef0, degree=kernel.degree,
                          tm=tm, tn=tn, interpret=interpret)
    return out[:, 0]

"""Jit'd wrapper for the fused SMO f-cache update.

``precision`` casts the streamed data tiles (x and the selected block) to
bf16/f16; the delta/f operands, norms and the rank-2P matvec epilogue stay
f32 (see ``repro.kernels.precision``).

Tile sizes are owned by the autotune table: with ``tm``/``tk`` left as
``None`` (the default) the launch config comes from
``kernels.tiling.resolve_tiles`` — the committed
``kernels/tuned_configs.json`` keyed on (family="fupdate", M, D,
precision, backend) with nearest-shape fallback to the fixed constants
(512, 512). Passing either explicitly opts the call out of the table;
``REPRO_NO_AUTOTUNE=1`` forces the constants everywhere
(docs/kernels.md).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.kernel_fn import KernelFn
from repro.kernels.tiling import (_auto_interpret, _pad_to, backend_name,
                                  resolve_tiles)
from repro.kernels.fupdate.kernel import fupdate_pallas
from repro.kernels.precision import check_launch_precision, tile_dtype


@partial(jax.jit, static_argnames=("kernel", "tm", "tk", "interpret",
                                   "precision"))
def fupdate(x, xsel, delta, f, kernel: KernelFn, *, tm: int | None = None,
            tk: int | None = None, interpret: bool | None = None,
            precision: str = "f32"):
    """f + k(x, xsel) @ delta — the SMO hot-loop rank-2P update, fused.

    Args:
      x: (m, d) training rows (streamed once per call — the per-iteration
        HBM bill).
      xsel: (s, d) the selected pair block; padded internally to a lane
        multiple (128) with zero rows.
      delta: (s,) dual step; padded deltas are zero, so padding never
        perturbs f (asserted bitwise by tests).
      f: (m,) f32 score cache.
      kernel: ``repro.core.KernelFn``; name/scalars static.
      tm, tk: row / feature block sizes (multiples of 128). ``None``
        (default) resolves from the autotune table; passing either opts
        out of the table (rest fall back to 512/512). The selected block
        has no n-blocking — it is VMEM-resident for the whole grid.
      interpret: force Pallas interpret mode; ``None`` auto-detects.
      precision: tile-input stream dtype ("f32"/"bf16"/"f16").

    Returns:
      (m,) f32 updated score cache.
    """
    if interpret is None:
        interpret = _auto_interpret()
    check_launch_precision(precision, interpret)
    cfg = resolve_tiles("fupdate", m=x.shape[0], d=x.shape[1],
                        precision=precision,
                        backend=backend_name(interpret),
                        block_m=tm, block_k=tk)
    tm, tk = cfg.block_m, cfg.block_k
    dt = tile_dtype(precision)
    m = x.shape[0]
    x = _pad_to(_pad_to(x.astype(jnp.float32), tm, 0), tk, 1).astype(dt)
    xsel = _pad_to(_pad_to(xsel.astype(jnp.float32), 128, 0),
                   tk, 1).astype(dt)
    s = xsel.shape[0]
    delta = _pad_to(delta.astype(jnp.float32)[:, None], 128, 0)
    f2 = _pad_to(f.astype(jnp.float32)[:, None], tm, 0)
    xf = x.astype(jnp.float32)
    xsf = xsel.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=-1, keepdims=True)
    seln = jnp.sum(xsf * xsf, axis=-1, keepdims=True)
    out = fupdate_pallas(x, xsel, delta, f2, xn, seln, kind=kernel.name,
                         gamma=kernel.gamma, coef0=kernel.coef0,
                         degree=kernel.degree, tm=tm, tk=tk,
                         interpret=interpret)
    return out[:m, 0]

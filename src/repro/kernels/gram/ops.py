"""Jit'd public wrapper for the tiled Gram kernel.

Pads inputs to tile multiples, dispatches to the Pallas kernel (interpret
mode on non-TPU backends so the same code path is exercised on CPU), and
slices the result back. Padding rows/features are zeros: they contribute 0
to dot products and norms, and padded outputs are discarded by the slice.

``precision`` ("f32" default, "bf16", "f16") casts the data tiles to the
low-precision dtype before the kernel — halving the streamed bytes — while
norms are computed in f32 from the rounded values and the dot products
accumulate in f32 on the MXU (see ``repro.kernels.precision``).

Tile sizes are owned by the autotune table: with ``tm``/``tn``/``tk``
left as ``None`` (the default) the launch config comes from
``kernels.tiling.resolve_tiles`` — the committed
``kernels/tuned_configs.json`` keyed on (family="gram", max(M, N), D,
precision, backend) with nearest-shape fallback to the fixed constants
(256, 256, 512). Passing any of them explicitly opts the call out of
the table; ``REPRO_NO_AUTOTUNE=1`` forces the constants everywhere
(docs/kernels.md).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.kernel_fn import KernelFn
from repro.kernels.gram.kernel import gram_pallas
from repro.kernels.precision import check_launch_precision, tile_dtype
# Re-exported for backward compatibility: these moved to kernels.tiling so
# sibling kernel families stop importing through this module (import-cycle
# hazard when repro.kernels is the first package imported).
from repro.kernels.tiling import (_auto_interpret, _pad_to,  # noqa: F401
                                  backend_name, resolve_tiles)


@partial(jax.jit, static_argnames=("kernel", "tm", "tn", "tk", "interpret",
                                   "precision"))
def gram(x, y, kernel: KernelFn, *, tm: int | None = None,
         tn: int | None = None, tk: int | None = None,
         interpret: bool | None = None, precision: str = "f32"):
    """K[i, j] = k(x_i, y_j) via the tiled Pallas kernel.

    Args:
      x: (M, D) f32 rows (any float dtype; cast to f32 then to the tile
        dtype). Padded internally to tile multiples.
      y: (N, D) rows, same feature dim as ``x``.
      kernel: ``repro.core.KernelFn`` ("rbf" / "linear" / "poly"); its
        name and scalars are static (one executable per kernel fn).
      tm, tn, tk: row / column / feature block sizes (multiples of 128).
        ``None`` (default) resolves from the autotune table; passing any
        opts out of the table (rest fall back to 256/256/512).
      interpret: force Pallas interpret mode on/off; ``None`` auto
        (on for non-TPU backends, overridable via ``REPRO_INTERPRET``).
      precision: tile-input stream dtype ("f32"/"bf16"/"f16").

    Returns:
      (M, N) f32 kernel matrix.
    """
    if interpret is None:
        interpret = _auto_interpret()
    check_launch_precision(precision, interpret)
    cfg = resolve_tiles("gram", m=max(x.shape[0], y.shape[0]),
                        d=x.shape[1], precision=precision,
                        backend=backend_name(interpret),
                        block_m=tm, block_n=tn, block_k=tk)
    tm, tn, tk = cfg.block_m, cfg.block_n, cfg.block_k
    dt = tile_dtype(precision)
    M, N = x.shape[0], y.shape[0]
    x = _pad_to(_pad_to(x.astype(jnp.float32), tm, 0), tk, 1).astype(dt)
    y = _pad_to(_pad_to(y.astype(jnp.float32), tn, 0), tk, 1).astype(dt)
    # f32 norms of the *rounded* rows: keeps the RBF distance identity
    # exact for the values the MXU actually sees.
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=-1, keepdims=True)
    yn = jnp.sum(yf * yf, axis=-1, keepdims=True)
    out = gram_pallas(x, y, xn, yn, kind=kernel.name, gamma=kernel.gamma,
                      coef0=kernel.coef0, degree=kernel.degree,
                      tm=tm, tn=tn, tk=tk, interpret=interpret)
    return out[:M, :N]

"""Jit'd wrapper for the fused SMO f-cache update, and the prepared X.

X is loop-invariant in a solve, so its stream form is built once
(:func:`prepare_x`): cast to the stream dtype, padded to a lane multiple
of rows and to the k tile's lanes (128 at d=30, not 512), with its f32
row norms stored lane-dense as (1, M). The solver's providers
(``core/engine/gram.py``) build it at construction, outside the
``lax.while_loop``, and hand it to :func:`fupdate` every iteration; a
raw (m, d) X is prepared inside the call.

``precision`` casts the streamed data tiles (x and the selected block) to
bf16/f16; the delta/f operands, norms and the rank-S matvec epilogue stay
f32 (see ``repro.kernels.precision``).

Tile sizes come from ``kernels.tiling.resolve_tiles`` when ``tm``/``tk``
are left as ``None``: the committed ``kernels/tuned_configs.json`` keyed
on (family="fupdate", M, D, precision, backend) with nearest-shape
fallback, else tiles derived from the shapes — ``tk`` from d
(``fupdate_tk``), ``tm`` from the selected block and ``tk`` so that the
(S, TM) accumulator fits VMEM (``fupdate_tm``). Passing either
explicitly opts the call out of the table; ``REPRO_NO_AUTOTUNE=1``
forces the derived defaults everywhere (docs/kernels.md).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.kernel_fn import KernelFn
from repro.kernels.tiling import (LANE, SEL_ROWS, _auto_interpret,
                                  _pad_to, backend_name, resolve_tiles,
                                  round_up)
from repro.kernels.fupdate.kernel import fupdate_pallas
from repro.kernels.precision import (check_launch_precision,
                                     check_precision, tile_dtype)


@partial(jax.tree_util.register_dataclass, data_fields=["x", "xn"],
         meta_fields=["m", "d", "tk", "precision"])
@dataclass(frozen=True)
class PreparedX:
    """X in the form the ``fupdate`` kernel streams.

    x: (M, D) in the stream dtype, M = m rounded up to a lane multiple,
    D = d rounded up to a multiple of ``tk``; padding is zeros.
    xn: (1, M) f32 row norms of the rounded rows, lane-dense.
    m, d: the real rows and features; tk: the k tile D was padded for.
    """

    x: jax.Array
    xn: jax.Array
    m: int
    d: int
    tk: int
    precision: str


def prepare_x(x, *, precision: str = "f32", interpret: bool | None = None,
              tk: int | None = None) -> PreparedX:
    """Build the stream form of X once (see :class:`PreparedX`).

    ``tk`` pins the k tile; ``None`` resolves it like :func:`fupdate`
    does (table, else from d)."""
    check_precision(precision)
    if interpret is None:
        interpret = _auto_interpret()
    m, d = x.shape
    if tk is None:
        tk = resolve_tiles("fupdate", m=m, d=d, precision=precision,
                           backend=backend_name(interpret)).block_k
    xp = _pad_to(_pad_to(x.astype(jnp.float32), LANE, 0), tk, 1)
    xp = xp.astype(tile_dtype(precision))
    xf = xp.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=-1)[None, :]
    return PreparedX(xp, xn, m, d, tk, precision)


@partial(jax.jit, static_argnames=("kernel", "tm", "tk", "interpret",
                                   "precision"))
def fupdate(x, xsel, delta, f, kernel: KernelFn, *, tm: int | None = None,
            tk: int | None = None, interpret: bool | None = None,
            precision: str = "f32"):
    """f + k(x, xsel) @ delta — the SMO hot-loop rank-S update, fused.

    Args:
      x: (m, d) training rows, or their :class:`PreparedX` (the engine's
        providers prepare it once per solve). Streamed once per call —
        the per-iteration HBM bill.
      xsel: (s, d) the selected block; padded internally to a sublane
        multiple with zero rows, which the kernel masks to exactly 0.
      delta: (s,) dual step.
      f: (m,) f32 score cache.
      kernel: ``repro.core.KernelFn``; name/scalars static.
      tm, tk: row / feature block sizes (multiples of 128). ``None``
        (default) resolves from the autotune table, else from the shapes;
        passing either opts out of the table. A prepared X fixes tk. The
        selected block has no n-blocking — it is VMEM-resident for the
        whole grid.
      interpret: force Pallas interpret mode; ``None`` auto-detects.
      precision: tile-input stream dtype ("f32"/"bf16"/"f16").

    Returns:
      (m,) f32 updated score cache.
    """
    if interpret is None:
        interpret = _auto_interpret()
    check_launch_precision(precision, interpret)
    s = xsel.shape[0]
    backend = backend_name(interpret)
    if isinstance(x, PreparedX):
        prep = x
        if prep.precision != precision:
            raise ValueError(f"X was prepared for precision "
                             f"{prep.precision!r}, not {precision!r}")
        if tk is not None and tk != prep.tk:
            raise ValueError(f"X was prepared for tk={prep.tk}, not {tk}")
        tm = resolve_tiles("fupdate", m=prep.m, d=prep.d,
                           precision=precision, backend=backend,
                           block_m=tm, s=s).block_m
    else:
        cfg = resolve_tiles("fupdate", m=x.shape[0], d=x.shape[1],
                            precision=precision, backend=backend,
                            block_m=tm, block_k=tk, s=s)
        tm = cfg.block_m
        prep = prepare_x(x, precision=precision, interpret=interpret,
                         tk=cfg.block_k)
    m, (m_pad, d_pad) = prep.m, prep.x.shape
    dt = tile_dtype(precision)
    s_pad = round_up(s, SEL_ROWS)
    xsel = _pad_to(_pad_to(xsel.astype(jnp.float32), s_pad, 0), d_pad,
                   1).astype(dt)
    xsf = xsel.astype(jnp.float32)
    seln = jnp.sum(xsf * xsf, axis=-1, keepdims=True)
    delta = _pad_to(delta.astype(jnp.float32), s_pad, 0)[:, None]
    # A lane-aligned m (every real deployment size) reaches the kernel by
    # a reshape alone; otherwise f gains the rows X was padded with.
    f2 = _pad_to(f.astype(jnp.float32), LANE, 0).reshape(1, m_pad)
    out = fupdate_pallas(prep.x, xsel, delta, f2, prep.xn, seln, s_live=s,
                         kind=kernel.name, gamma=kernel.gamma,
                         coef0=kernel.coef0, degree=kernel.degree,
                         tm=tm, tk=prep.tk, interpret=interpret)
    out = out.reshape(m_pad)
    return out if m == m_pad else out[:m]

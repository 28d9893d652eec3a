"""Mixed-precision policy for the Pallas kernel families.

The ``gram`` / ``fupdate`` / ``decision`` tiles are bytes-bound: every
operand streamed HBM->VMEM is f32 while the MXU natively consumes
bf16/f16 at twice the rate per byte. The ``precision`` knob halves the
tile *input* bytes without moving the math out of f32 anywhere it
matters:

* tile inputs (the data tiles that dominate HBM traffic) are cast to
  the low-precision dtype **once**, outside the kernel, so the stream
  itself is 16-bit;
* every dot product accumulates via
  ``preferred_element_type=jnp.float32`` (the MXU accumulator is f32);
* norms are computed in f32 **from the rounded values** — so the RBF
  distance ``||x||^2 + ||y||^2 - 2 x.y`` is the true squared distance
  of the rounded points and stays >= 0 up to f32 rounding;
* the epilogue (RBF exp, poly powers, the slab rho comparisons) and the
  f-cache / gamma / decision outputs stay f32.

``precision="f32"`` is the default and is a no-op cast: the compute
graph is bit-identical to the pre-knob kernels (tests assert it).

``"f16"`` runs in interpret mode only: the TPU kernel compiler (Mosaic)
refuses f16 vector loads on v5e ("Invalid vector type for load"), so a
compiled launch asking for it is refused up front by
:func:`check_launch_precision` instead of failing inside the compiler.

The product of two bf16 (8 mantissa bits) or f16 (11 bits) values is
exactly representable in f32 (<= 22 bits), so the only error sources
are the input rounding and the f32 accumulation order — which is why
the pure-jnp refs, parameterized on the same dtype round-trip, track
the Pallas kernels to tight per-dtype tolerances (``TOLERANCES``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Public knob values, in "fastest-safe first" documentation order.
PRECISIONS = ("f32", "bf16", "f16")

_TILE_DTYPES = {
    "f32": jnp.float32,
    "bf16": jnp.bfloat16,
    "f16": jnp.float16,
}

# Documented low-precision-vs-f32-truth tolerances (the bound
# docs/serving.md advertises and the parity matrix asserts): ``rtol``
# element-wise, plus ``atol`` scaled by the OUTPUT magnitude
# (max |truth|, floored at 1) — dot products cancel, so the absolute
# error floor is set by the operand scale, not the result scale. bf16
# keeps ~2 significant digits (2^-8 ulp), f16 ~3 (2^-11); f32
# differences are accumulation-order only.
TOLERANCES = {
    "f32": dict(rtol=2e-4, atol=2e-4),
    "bf16": dict(rtol=4e-2, atol=2e-2),
    "f16": dict(rtol=6e-3, atol=3e-3),
}


def truth_tolerance(precision: str, truth) -> dict:
    """assert_allclose kwargs for comparing a ``precision`` output against
    f32 truth, with atol scaled to the output magnitude (see TOLERANCES)."""
    import numpy as np
    t = TOLERANCES[check_precision(precision)]
    scale = max(1.0, float(np.max(np.abs(np.asarray(truth, np.float32)))))
    return dict(rtol=t["rtol"], atol=t["atol"] * scale)


def check_precision(precision: str) -> str:
    if precision not in _TILE_DTYPES:
        raise ValueError(f"unknown precision {precision!r}; "
                         f"expected one of {PRECISIONS}")
    return precision


def check_launch_precision(precision: str, interpret: bool) -> str:
    """``check_precision`` plus the compiled-kernel rule: f16 tiles are
    interpret-only (Mosaic refuses f16 vector loads on v5e)."""
    check_precision(precision)
    if precision == "f16" and not interpret:
        device = jax.devices()[0]
        raise ValueError(
            f"precision='f16' cannot launch a compiled Pallas kernel on "
            f"{device.platform}:{device.device_kind}: the TPU kernel "
            f"compiler refuses f16 vector loads on v5e ('Invalid vector "
            f"type for load'). Use 'bf16' (same 16-bit stream) or "
            f"interpret=True.")
    return precision


def mxu_precision(dtype):
    """Contract precision for an in-kernel dot on ``dtype`` operands.

    Mosaic's default contracts f32 operands in one bf16 pass on v5e: on
    the chip, f32 ``fupdate`` kernel values were off by up to 3.8e-3 and
    the fit at 284,807 rows never converged. So f32 operands ask for full
    f32. 16-bit operands keep the default: their products are exact in
    f32 already."""
    return jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 \
        else None


def parse_precisions(spec: str) -> tuple:
    """Parse a CLI comma list ("f32,bf16") into validated precisions.

    Empty/whitespace entries are dropped; an empty spec yields ("f32",)
    so benchmark flags always have a well-defined default.
    """
    out = tuple(check_precision(p.strip()) for p in spec.split(",")
                if p.strip())
    return out or ("f32",)


def tile_dtype(precision: str):
    """The dtype tile inputs are streamed in."""
    return _TILE_DTYPES[check_precision(precision)]


def round_to_tile(a, precision: str):
    """f32 -> tile dtype round-trip, back in f32.

    Used where a pure-jnp path (refs, non-Pallas providers) must see the
    same input rounding the Pallas tiles see. No-op for "f32".
    """
    if precision == "f32":
        return a.astype(jnp.float32)
    return a.astype(jnp.float32).astype(tile_dtype(precision)) \
            .astype(jnp.float32)

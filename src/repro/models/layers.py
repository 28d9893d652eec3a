"""Shared neural building blocks (pure JAX, pjit/GSPMD-friendly)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


Array = jax.Array


def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def rope_freqs(head_dim: int, theta: float) -> Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, Dh); positions: (B, S) absolute token positions."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                       # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, Dh/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mlp_forward(params: dict, x: Array, mlp_type: str) -> Array:
    """Gated / plain MLP. params: w1 (d, ff)[, w3 (d, ff)], w2 (ff, d)."""
    if mlp_type == "swiglu":
        h = jax.nn.silu(x @ params["w1"]) * (x @ params["w3"])
    elif mlp_type == "geglu":
        h = jax.nn.gelu(x @ params["w1"]) * (x @ params["w3"])
    elif mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(x @ params["w1"]))
    elif mlp_type == "gelu":
        h = jax.nn.gelu(x @ params["w1"])
    else:
        raise ValueError(mlp_type)
    return h @ params["w2"]


def mlp_forward_tp(params: dict, x: Array, mlp_type: str, ctx) -> Array:
    """Explicit megatron-TP MLP under shard_map.

    Why not let GSPMD do it (hillclimb iter 2, EXPERIMENTS.md §Perf):
    GSPMD all-reduces the f32 dot *accumulator* of the row-parallel matmul
    — 2x the bytes of the bf16 activation. Under shard_map the psum
    operand is explicitly cast to the activation dtype first. Backward
    inherits the same property (dx psum in bf16 at the col-parallel side).
    """
    mesh = ctx["mesh"]
    baxes = ctx["data_axes"]
    fsdp = ctx["fsdp"]
    dp = 1
    for ax in baxes:
        dp *= mesh.shape[ax]
    b = x.shape[0]
    bspec = baxes if b % dp == 0 else None
    xspec = P(bspec, None, None)
    gated = mlp_type in ("swiglu", "geglu")
    w1spec = P("data" if fsdp else None, "model")
    w2spec = P("model", "data" if fsdp else None)

    def local_fn(xl, w1, w2, *rest):
        w3 = rest[0] if gated else None
        if fsdp:
            w1 = jax.lax.all_gather(w1, "data", axis=0, tiled=True)
            w2 = jax.lax.all_gather(w2, "data", axis=1, tiled=True)
            if w3 is not None:
                w3 = jax.lax.all_gather(w3, "data", axis=0, tiled=True)
        h1 = xl @ w1
        if mlp_type == "swiglu":
            h = jax.nn.silu(h1) * (xl @ w3)
        elif mlp_type == "geglu":
            h = jax.nn.gelu(h1) * (xl @ w3)
        elif mlp_type == "relu2":
            h = jnp.square(jax.nn.relu(h1))
        else:
            h = jax.nn.gelu(h1)
        part = (h @ w2).astype(xl.dtype)      # bf16 BEFORE the all-reduce
        return jax.lax.psum(part, "model")

    args = [x, params["w1"], params["w2"]]
    specs = [xspec, w1spec, w2spec]
    if gated:
        args.append(params["w3"])
        specs.append(w1spec)
    return shard_map(local_fn, mesh=mesh, in_specs=tuple(specs),
                     out_specs=xspec, check_vma=False)(*args)


def mlp_init(key: Array, d_model: int, d_ff: int, mlp_type: str,
             dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_ff = d_ff ** -0.5
    p = {
        "w1": (jax.random.normal(k1, (d_model, d_ff), jnp.float32)
               * s_in).astype(dtype),
        "w2": (jax.random.normal(k2, (d_ff, d_model), jnp.float32)
               * s_ff).astype(dtype),
    }
    if mlp_type in ("swiglu", "geglu"):
        p["w3"] = (jax.random.normal(k3, (d_model, d_ff), jnp.float32)
                   * s_in).astype(dtype)
    return p


def embed_init(key: Array, vocab: int, d_model: int, dtype) -> Array:
    return (jax.random.normal(key, (vocab, d_model), jnp.float32)
            * (d_model ** -0.5)).astype(dtype)


def dense_init(key: Array, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0]
    s = (fan_in ** -0.5) if scale is None else scale
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)

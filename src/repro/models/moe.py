"""Top-k MoE with sort-based capacity dispatch (GShard semantics,
shape-static, expert-parallel-shardable).

Dispatch pipeline (all static shapes, no ragged ops):

1. router top-k -> (T*k,) flat expert ids + gates,
2. stable argsort by expert id; position-within-expert via running counts,
3. tokens beyond the per-expert capacity C = ceil(T*k*cf / E) are dropped
   (GShard capacity rule),
4. scatter tokens into the (E, C, d) dispatch buffer, run the batched
   expert FFN einsum (experts sharded over the "model" mesh axis => EP;
   GSPMD inserts the all-to-alls at the (T,d)->(E,C,d) boundary),
5. gather + gate-weighted scatter-add back to (T, d).

FLOPs scale with T*k*cf — the *active* parameter count — so roofline terms
stay honest for 128-expert models (a dense all-experts evaluation would
inflate compute 64x on arctic-480b).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.models.layers import dense_init

Array = jax.Array


def moe_init(key: Array, d_model: int, n_experts: int, ff: int,
             mlp_type: str, dtype) -> dict:
    kr, k1, k2, k3 = jax.random.split(key, 4)
    s_in = d_model ** -0.5
    s_ff = ff ** -0.5
    p = {
        "router": dense_init(kr, (d_model, n_experts), jnp.float32),
        "w1": (jax.random.normal(k1, (n_experts, d_model, ff), jnp.float32)
               * s_in).astype(dtype),
        "w2": (jax.random.normal(k2, (n_experts, ff, d_model), jnp.float32)
               * s_ff).astype(dtype),
    }
    if mlp_type in ("swiglu", "geglu"):
        p["w3"] = (jax.random.normal(k3, (n_experts, d_model, ff),
                                     jnp.float32) * s_in).astype(dtype)
    return p


def _expert_ffn(params: dict, x: Array, mlp_type: str) -> Array:
    """x: (E, C, d) -> (E, C, d), batched over experts."""
    h1 = jnp.einsum("ecd,edf->ecf", x, params["w1"])
    if mlp_type == "swiglu":
        h = jax.nn.silu(h1) * jnp.einsum("ecd,edf->ecf", x, params["w3"])
    elif mlp_type == "geglu":
        h = jax.nn.gelu(h1) * jnp.einsum("ecd,edf->ecf", x, params["w3"])
    elif mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(h1))
    else:
        raise ValueError(mlp_type)
    return jnp.einsum("ecf,efd->ecd", h, params["w2"])


def moe_forward(params: dict, x: Array, *, n_experts: int, top_k: int,
                capacity_factor: float, mlp_type: str,
                router_jitter: bool = False, impl: str = "psum",
                constrain=lambda x, kind: x) -> Tuple[Array, Array]:
    """x: (B, S, d). Returns (output, aux_loss).

    When the constrain hook carries a mesh (production path), dispatch runs
    under an explicit shard_map: impl="a2a" moves tokens to data-sharded
    experts (weights never move); impl="psum" keeps experts model-sharded
    with ZeRO'd weights and an EP-combine psum. Without a mesh (unit
    tests, single device) the global dense path below runs instead.
    """
    ctx = getattr(constrain, "shard_ctx", None)
    if ctx is not None:
        if impl == "a2a":
            mesh = ctx["mesh"]
            dp = 1
            for ax in ctx["data_axes"]:
                dp *= mesh.shape[ax]
            ff = params["w1"].shape[-1]
            if n_experts % dp == 0 and ff % mesh.shape["model"] == 0:
                return _moe_forward_a2a(
                    params, x, ctx, n_experts=n_experts, top_k=top_k,
                    capacity_factor=capacity_factor, mlp_type=mlp_type)
        return _moe_forward_sharded(params, x, ctx, n_experts=n_experts,
                                    top_k=top_k,
                                    capacity_factor=capacity_factor,
                                    mlp_type=mlp_type)
    b, s, d = x.shape
    T = b * s
    xf = constrain(x.reshape(T, d), "moe_tokens")
    E, K = n_experts, top_k
    C = max(1, int((T * K * capacity_factor) / E + 0.999))

    logits = (xf.astype(jnp.float32) @ params["router"])        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)             # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9)

    # Load-balancing aux loss (Switch-style).
    me = probs.mean(axis=0)                                     # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (T * K))
    aux = E * jnp.sum(me * ce)

    flat_expert = expert_ids.reshape(-1)                        # (T*K,)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T), K)

    order = jnp.argsort(flat_expert, stable=True)
    se = flat_expert[order]
    st = flat_token[order]
    sg = flat_gate[order]

    counts = jnp.bincount(flat_expert, length=E)                # (E,)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K) - starts[se]                        # rank in expert
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, 0)

    # Dispatch: (E*C, d) buffer; each kept slot receives exactly one token.
    # The (E, C, d) buffers are constrained to the EP layout (experts over
    # "model", capacity over the data axes) — without this GSPMD leaves
    # them replicated and a 128-expert layer eats tens of GB per device.
    xb = jnp.where(keep[:, None], xf[st], 0.0)
    xdisp = jnp.zeros((E * C, d), x.dtype).at[slot].add(
        xb.astype(x.dtype), mode="drop")
    xdisp = constrain(xdisp.reshape(E, C, d), "moe_dispatch")

    yexp = _expert_ffn(params, xdisp, mlp_type)
    yexp = constrain(yexp, "moe_dispatch").reshape(E * C, d)

    # Combine: gather each kept token's expert output, gate, scatter-add.
    contrib = yexp[slot] * (sg[:, None] * keep[:, None]).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[st].add(contrib, mode="drop")
    y = constrain(y, "moe_tokens")
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# production path: explicit shard_map dispatch
# ---------------------------------------------------------------------------

def _local_dispatch_compute(xf, router, w1, w2, w3, *, E: int, top_k: int,
                            C_loc: int, mlp_type: str, e0, e_loc: int):
    """Device-local token-choice dispatch + expert FFN for experts
    [e0, e0+e_loc). xf: (T_loc, d); weights already gathered/local.
    Returns (partial y (T_loc, d), aux-loss numerator pieces)."""
    T_loc, d = xf.shape
    K = top_k
    logits = xf.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9)

    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (T_loc * K))
    aux = E * jnp.sum(me * ce)

    flat_expert = expert_ids.reshape(-1)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T_loc), K)
    order = jnp.argsort(flat_expert, stable=True)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    counts = jnp.bincount(flat_expert, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T_loc * K) - starts[se]
    keep = pos < C_loc
    slot = jnp.where(keep, se * C_loc + pos, 0)

    xb = jnp.where(keep[:, None], xf[st], 0.0)
    xdisp = jnp.zeros((E * C_loc, d), xf.dtype).at[slot].add(
        xb.astype(xf.dtype), mode="drop").reshape(E, C_loc, d)
    # Each model shard computes only its expert slice (EP) — or all
    # experts on a TP-on-ff slice (expert-TP when E < model axis).
    xslice = jax.lax.dynamic_slice_in_dim(xdisp, e0, e_loc, axis=0) \
        if e_loc != E else xdisp

    p = {"w1": w1, "w2": w2}
    if w3 is not None:
        p["w3"] = w3
    yexp = _expert_ffn(p, xslice, mlp_type)               # (e_loc, C_loc, d)
    if e_loc != E:
        pad = ((0, 0),) * 0
        yfull = jnp.zeros((E, C_loc, d), yexp.dtype)
        yfull = jax.lax.dynamic_update_slice_in_dim(yfull, yexp, e0, axis=0)
    else:
        yfull = yexp
    yflat = yfull.reshape(E * C_loc, d)
    contrib = yflat[slot] * (sg[:, None] * keep[:, None]).astype(xf.dtype)
    y = jnp.zeros((T_loc, d), xf.dtype).at[st].add(contrib, mode="drop")
    return y, aux


def _moe_forward_a2a(params: dict, x: Array, ctx, *, n_experts: int,
                     top_k: int, capacity_factor: float,
                     mlp_type: str) -> Tuple[Array, Array]:
    """Canonical expert parallelism: experts sharded over the DATA axis,
    tokens moved to experts with all-to-all, expert-ff TP over "model".

    Weight layout (w1: P("data", None, "model")) is fully 256-way sharded
    and never gathered — per layer the only comms are two (E, C_loc, d)
    all-to-alls (~T_loc*k*cf tokens) plus one TP psum of the same size.
    Replaces the psum-mode's per-microbatch ZeRO-3 expert-weight
    all-gathers, which dominated arctic-480b training at 58 GB/device
    per pass (hillclimb 2, EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    mesh = ctx["mesh"]
    baxes = ctx["data_axes"]
    model_size = mesh.shape["model"]
    dp = 1
    for ax in baxes:
        dp *= mesh.shape[ax]

    b, s, d = x.shape
    E, K = n_experts, top_k
    e_loc = E // dp
    batch_shardable = b % dp == 0
    T_loc = (b // dp if batch_shardable else b) * s
    if T_loc * K <= 4096:
        C_loc = T_loc * K
    else:
        C_loc = max(1, int(T_loc * K * capacity_factor / E + 0.999))

    gated = mlp_type in ("swiglu", "geglu")
    xspec = P(baxes if batch_shardable else None, None, None)
    wspec = P(baxes, None, "model")     # (E, d, ff)
    w2spec = P(baxes, "model", None)    # (E, ff, d)
    has_w3 = "w3" in params

    def local_fn(xl, router, *ws):
        w1, w2 = ws[0], ws[1]
        w3 = ws[2] if has_w3 else None
        tb, ts, _ = xl.shape
        xf = xl.reshape(tb * ts, d)

        # Local routing + dispatch into (E, C_loc, d) — all experts.
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(axis=-1, keepdims=True), 1e-9)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(
            1.0 / (T_loc * K))
        aux = E * jnp.sum(me * ce)

        flat_expert = expert_ids.reshape(-1)
        flat_gate = gate_vals.reshape(-1)
        flat_token = jnp.repeat(jnp.arange(T_loc), K)
        order = jnp.argsort(flat_expert, stable=True)
        se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
        counts = jnp.bincount(flat_expert, length=E)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(T_loc * K) - starts[se]
        keep = pos < C_loc
        slot = jnp.where(keep, se * C_loc + pos, 0)
        xb = jnp.where(keep[:, None], xf[st], 0.0)
        xdisp = jnp.zeros((E * C_loc, d), xf.dtype).at[slot].add(
            xb.astype(xf.dtype), mode="drop").reshape(E, C_loc, d)

        # Tokens -> expert owners (dp groups of e_loc experts each).
        xexp = jax.lax.all_to_all(xdisp, baxes, split_axis=0,
                                  concat_axis=1, tiled=True)
        # (e_loc, C_loc * dp, d): this shard's experts, everyone's tokens.
        h1 = jnp.einsum("ecd,edf->ecf", xexp, w1)
        if mlp_type == "swiglu":
            h = jax.nn.silu(h1) * jnp.einsum("ecd,edf->ecf", xexp, w3)
        elif mlp_type == "geglu":
            h = jax.nn.gelu(h1) * jnp.einsum("ecd,edf->ecf", xexp, w3)
        elif mlp_type == "relu2":
            h = jnp.square(jax.nn.relu(h1))
        else:
            h = jax.nn.gelu(h1)
        ypart = jnp.einsum("ecf,efd->ecd", h, w2).astype(xf.dtype)
        yexp = jax.lax.psum(ypart, "model")          # ff-TP combine (bf16)

        # Results -> token owners (reverse all-to-all).
        ylocal = jax.lax.all_to_all(yexp, baxes, split_axis=1,
                                    concat_axis=0, tiled=True)
        yflat = ylocal.reshape(E * C_loc, d)
        contrib = yflat[slot] * (sg[:, None] * keep[:, None]).astype(xf.dtype)
        y = jnp.zeros((T_loc, d), xf.dtype).at[st].add(contrib, mode="drop")
        aux = jax.lax.pmean(aux, baxes + ("model",))
        return y.reshape(tb, ts, d), aux

    w_in = [params["w1"], params["w2"]]
    w_specs = [wspec, w2spec]
    if has_w3:
        w_in.append(params["w3"])
        w_specs.append(wspec)
    out, aux = shard_map(
        local_fn, mesh=mesh,
        in_specs=(xspec, P(None, None), *w_specs),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, params["router"], *w_in)
    return out, aux


def _moe_forward_sharded(params: dict, x: Array, ctx, *, n_experts: int,
                         top_k: int, capacity_factor: float,
                         mlp_type: str) -> Tuple[Array, Array]:
    from jax.sharding import PartitionSpec as P

    mesh = ctx["mesh"]
    baxes = ctx["data_axes"]
    fsdp = ctx["fsdp"]
    model_size = mesh.shape["model"]
    dp = 1
    for ax in baxes:
        dp *= mesh.shape[ax]

    b, s, d = x.shape
    E, K = n_experts, top_k
    ep = E % model_size == 0                 # expert-parallel vs expert-TP
    e_loc = E // model_size if ep else E
    batch_shardable = b % dp == 0
    T_loc = (b // dp if batch_shardable else b) * s
    if T_loc * K <= 4096:
        C_loc = T_loc * K                    # dropless (decode/serving)
    else:
        C_loc = max(1, int(T_loc * K * capacity_factor / E + 0.999))

    gated = mlp_type in ("swiglu", "geglu")
    xspec = P(baxes if batch_shardable else None, None, None)
    # weight specs must mirror sharding/specs.py rules
    if ep:
        wspec = (P("model", "data", None) if fsdp
                 else P("model", None, None))
        w2spec = (P("model", None, "data") if fsdp
                  else P("model", None, None))
    else:
        wspec = P(None, "data" if fsdp else None, "model")
        w2spec = P(None, "model", "data" if fsdp else None)

    has_w3 = "w3" in params

    def local_fn(xl, router, *ws):
        w1, w2 = ws[0], ws[1]
        w3 = ws[2] if has_w3 else None
        tb, ts, _ = xl.shape
        xf = xl.reshape(tb * ts, d)
        if fsdp:
            # ZeRO-3: un-shard the weights' FSDP axis at use.
            w1 = jax.lax.all_gather(w1, "data", axis=1, tiled=True)
            if w3 is not None:
                w3 = jax.lax.all_gather(w3, "data", axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, "data", axis=2, tiled=True)
        e0 = jax.lax.axis_index("model") * e_loc if ep else 0
        y, aux = _local_dispatch_compute(
            xf, router, w1, w2, w3, E=E, top_k=K, C_loc=C_loc,
            mlp_type=mlp_type, e0=e0, e_loc=e_loc)
        # EP combine: each token's expert lives on one model shard (EP) or
        # every shard holds a partial-ff sum (expert-TP) — psum either way.
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, baxes + ("model",))
        return y.reshape(tb, ts, d), aux

    w_in = [params["w1"], params["w2"]]
    w_specs = [wspec, w2spec]
    if has_w3:
        w_in.append(params["w3"])
        w_specs.append(wspec)
    out, aux = shard_map(
        local_fn, mesh=mesh,
        in_specs=(xspec, P(None, None), *w_specs),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, params["router"], *w_in)
    return out, aux

"""FFN blocks: dense SwiGLU and Mixture-of-Experts.

MoE uses sort-based token dispatch (argsort by expert id, capacity-
bounded scatter into per-expert slots) + batched expert matmuls — the
einsum shape (E, C, D) x (E, D, F) keeps FLOPs proportional to ACTIVE
parameters (top-k), and the expert dimension shards over the "model"
mesh axis (expert parallelism; tokens cross via the scatter/gather
collectives).  Shared experts (DeepSeek) are a fused dense SwiGLU of
width n_shared * d_ff_expert.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .common import ParamSpec
from .config import ArchConfig


def dense_specs(cfg: ArchConfig, d_ff: int | None = None
                ) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    return {
        "w1": ParamSpec((d, f), ("embed", "ffn"), "lecun"),
        "w3": ParamSpec((d, f), ("embed", "ffn"), "lecun"),
        "w2": ParamSpec((f, d), ("ffn", "embed"), "lecun"),
    }


def dense_forward(p, x: jnp.ndarray, dtype) -> jnp.ndarray:
    h = jax.nn.silu(x @ p["w1"].astype(dtype)) * (x @ p["w3"].astype(dtype))
    return h @ p["w2"].astype(dtype)


def moe_specs(cfg: ArchConfig) -> Dict[str, ParamSpec]:
    d, e, fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    specs: Dict[str, ParamSpec] = {
        "router": ParamSpec((d, e), ("embed", None), "lecun"),
        "w1": ParamSpec((e, d, fe), ("experts", "embed", "ffn"), "lecun"),
        "w3": ParamSpec((e, d, fe), ("experts", "embed", "ffn"), "lecun"),
        "w2": ParamSpec((e, fe, d), ("experts", "ffn", "embed"), "lecun"),
    }
    if cfg.n_shared_experts:
        shared = dict(dense_specs(cfg, cfg.n_shared_experts
                                  * cfg.d_ff_expert))
        specs["shared"] = shared
    return specs


def moe_forward(p, x: jnp.ndarray, cfg: ArchConfig, dtype) -> jnp.ndarray:
    from .common import constrain

    b, t, d = x.shape
    s = b * t
    e, k = cfg.n_experts, cfg.top_k
    xf = constrain(x.reshape(s, d), ("tokens", None))

    gates = jax.nn.softmax(
        (xf @ p["router"].astype(dtype)).astype(jnp.float32), axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates, k)           # (S, k)
    top_vals = top_vals / jnp.maximum(
        top_vals.sum(-1, keepdims=True), 1e-9)            # renormalize

    # per-expert slots; clamped to S (one expert can never receive more
    # than every token).  capacity_factor >= n_experts/top_k => dropless.
    capacity = min(s, int((s * k / e) * cfg.capacity_factor) + 1)

    flat_e = top_idx.reshape(s * k)
    flat_tok = jnp.repeat(jnp.arange(s), k)
    flat_w = top_vals.reshape(s * k)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    sorted_w = flat_w[order]

    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank = jnp.arange(s * k) - seg_start[sorted_e]
    keep = rank < capacity                                # overflow drops
    slot = jnp.where(keep, sorted_e * capacity + rank, e * capacity)

    # token->slot scatter: tokens stay data-sharded, expert slots are
    # expert-parallel over "model" — the partitioner turns the crossing
    # into the EP all-to-all instead of replicating the buffers
    src = constrain(xf[sorted_tok] * keep[:, None].astype(dtype),
                    ("tokens", None))
    buf = jnp.zeros((e * capacity + 1, d), dtype)
    buf = buf.at[slot].set(src)
    expert_in = constrain(buf[:-1].reshape(e, capacity, d),
                          ("experts", None, None))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in,
                               p["w1"].astype(dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, p["w3"].astype(dtype))
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(dtype))
    out_e = constrain(out_e, ("experts", None, None))

    gathered = out_e.reshape(e * capacity, d)[jnp.minimum(
        slot, e * capacity - 1)]
    gathered = constrain(gathered, ("tokens", None))
    gathered = gathered * (keep & True)[:, None].astype(dtype)
    contrib = gathered * sorted_w[:, None].astype(dtype)
    out = jnp.zeros((s, d), dtype).at[sorted_tok].add(contrib)
    out = constrain(out, ("tokens", None))

    if cfg.n_shared_experts:
        out = out + dense_forward(p["shared"], xf, dtype)
    return out.reshape(b, t, d)


def moe_forward_ep(p, x: jnp.ndarray, cfg: ArchConfig, dtype,
                   mesh, token_axes, model_axis: str) -> jnp.ndarray:
    """Expert-parallel MoE via shard_map (the §Perf iteration-3 path).

    Tokens stay batch-sharded (replicated across the model axis);
    experts are model-sharded.  Routing/top-k run at jit level; the
    dispatch scatter, expert matmuls, and combine gather run INSIDE a
    shard_map body — purely shard-LOCAL, so the partitioner can neither
    replicate the buffers nor lower the scatter to masked-dense ops.
    The only cross-shard collective is one psum of the (S_local, d)
    partial outputs over the model axis.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    b, t, d = x.shape
    s = b * t
    e, k = cfg.n_experts, cfg.top_k
    m = dict(zip(mesh.axis_names, mesh.devices.shape))[model_axis]
    assert e % m == 0, (e, m)
    e_loc = e // m

    xf = x.reshape(s, d)
    gates = jax.nn.softmax(
        (xf @ p["router"].astype(dtype)).astype(jnp.float32), axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates, k)
    top_vals = (top_vals / jnp.maximum(
        top_vals.sum(-1, keepdims=True), 1e-9)).astype(dtype)

    n_data = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in token_axes:
        n_data *= sizes[a]
    s_loc = s // n_data
    cap = min(s_loc, int((s_loc * k / e) * cfg.capacity_factor) + 1)

    tok_spec = P(token_axes if s % n_data == 0 and s > 1 else None)

    def body(xf_l, idx_l, vals_l, w1_l, w3_l, w2_l):
        j = jax.lax.axis_index(model_axis)
        lo = j * e_loc
        s_l = xf_l.shape[0]
        flat_e = idx_l.reshape(s_l * k)
        flat_tok = jnp.repeat(jnp.arange(s_l), k)
        flat_w = vals_l.reshape(s_l * k)
        mine = (flat_e >= lo) & (flat_e < lo + e_loc)
        local_e = jnp.where(mine, flat_e - lo, e_loc)   # foreign -> E_loc
        order = jnp.argsort(local_e, stable=True)
        se_, st_, sw_ = local_e[order], flat_tok[order], flat_w[order]
        seg = jnp.searchsorted(se_, jnp.arange(e_loc + 1), side="left")
        rank = jnp.arange(s_l * k) - seg[jnp.minimum(se_, e_loc)]
        keep = (se_ < e_loc) & (rank < cap)
        slot = jnp.where(keep, se_ * cap + rank, e_loc * cap)
        buf = jnp.zeros((e_loc * cap + 1, xf_l.shape[1]), xf_l.dtype)
        buf = buf.at[slot].set(xf_l[st_] * keep[:, None].astype(xf_l.dtype))
        ein = buf[:-1].reshape(e_loc, cap, xf_l.shape[1])
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein, w1_l))
        h = h * jnp.einsum("ecd,edf->ecf", ein, w3_l)
        oe = jnp.einsum("ecf,efd->ecd", h, w2_l)
        g = oe.reshape(e_loc * cap, -1)[jnp.minimum(slot,
                                                    e_loc * cap - 1)]
        g = g * (keep.astype(g.dtype) * sw_)[:, None]
        out_l = jnp.zeros_like(xf_l).at[st_].add(g)
        return jax.lax.psum(out_l, model_axis)

    out = shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec,
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=tok_spec,
        check_vma=False,
    )(xf, top_idx, top_vals, p["w1"].astype(dtype),
      p["w3"].astype(dtype), p["w2"].astype(dtype))

    if cfg.n_shared_experts:
        out = out + dense_forward(p["shared"], xf, dtype)
    return out.reshape(b, t, d)


def ffn_specs(cfg: ArchConfig, kind: str) -> Dict[str, ParamSpec]:
    return moe_specs(cfg) if kind == "moe" else dense_specs(cfg)


def ffn_forward(p, x: jnp.ndarray, cfg: ArchConfig, kind: str, dtype
                ) -> jnp.ndarray:
    if kind == "moe":
        from .common import _ACT_CTX

        ctx = _ACT_CTX.get()
        if ctx is not None and ctx["axes"].get("moe_ep"):
            token_axes, model_axis = ctx["axes"]["moe_ep"]
            return moe_forward_ep(p, x, cfg, dtype, ctx["mesh"],
                                  token_axes, model_axis)
        return moe_forward(p, x, cfg, dtype)
    return dense_forward(p, x, dtype)

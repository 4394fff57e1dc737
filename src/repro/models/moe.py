"""Mixture-of-Experts FFN (top-k routed + optional shared experts).

TPU-native design (see DESIGN.md §3): tokens are data-parallel, experts are
sharded over the ``model`` mesh axis. Inside ``shard_map`` each model-rank
  1. computes the (identical, replicated) routing for its local token block,
  2. gathers only the tokens routed to ITS experts via an index-based dispatch
     (sort + rank-in-expert; no (T, E, C) one-hot dispatch tensor is ever
     materialized — that is the GShard memory hog we deliberately avoid),
  3. runs the expert SwiGLU as a grouped (E_loc, C, d) einsum on the MXU,
  4. scatter-adds weighted expert outputs and psums over the model axis
     (one all-reduce per MoE layer — the Megatron-TP collective schedule).

Without a mesh the same inner function runs with all experts local (CPU tests).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _init

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden
    n_shared: int = 0              # shared (always-on) experts
    capacity_factor: float = 1.25
    router_dtype: Any = jnp.float32
    # expert-parallel weight layout:
    #   "fsdp": E on model, d_ff ZeRO-sharded on data, all-gathered per layer
    #           (best for training: weight traffic amortized over many tokens)
    #   "2d":   E on model AND d/f dims on data — weights fully resident, the
    #           only collectives are tiny activation psums (best for decode,
    #           where per-step FSDP all-gathers would dominate)
    ep_mode: str = "fsdp"


def init_moe(key, d_model: int, cfg: MoEConfig) -> Params:
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d_model, cfg.n_experts), scale=0.02),
        # fused gate+up per expert: (E, d, 2f); down: (E, f, d)
        "w_in": _init(ks[1], (cfg.n_experts, d_model, 2 * cfg.d_ff)),
        "w_out": _init(ks[2], (cfg.n_experts, cfg.d_ff, d_model),
                       scale=1.0 / np.sqrt(cfg.d_ff)),
    }
    if cfg.n_shared:
        p["shared_w_in"] = _init(ks[3], (d_model, 2 * cfg.n_shared * cfg.d_ff))
        p["shared_w_out"] = _init(
            ks[4], (cfg.n_shared * cfg.d_ff, d_model),
            scale=1.0 / np.sqrt(cfg.n_shared * cfg.d_ff),
        )
    return p


def _capacity(n_tokens: int, cfg: MoEConfig) -> int:
    c = int(np.ceil(cfg.top_k * n_tokens / cfg.n_experts * cfg.capacity_factor))
    return max(8, int(np.ceil(c / 8)) * 8)  # pad to sublane multiple


def _moe_inner(
    x: jax.Array,          # (T_loc, d) local token block (replicated over model)
    router_w: jax.Array,   # (d, E) replicated
    w_in: jax.Array,       # (E_loc, d, 2f) local expert shard
    w_out: jax.Array,      # (E_loc, f, d)
    cfg: MoEConfig,
    model_axis: Optional[str],
) -> jax.Array:
    t_loc, d = x.shape
    e_loc = w_in.shape[0]
    e = cfg.n_experts
    k = cfg.top_k
    dt = x.dtype
    cap = _capacity(t_loc, cfg)

    # 1) routing (identical on every model-rank: x and router_w are replicated)
    logits = (x.astype(cfg.router_dtype) @ router_w.astype(cfg.router_dtype))
    probs = jax.nn.softmax(logits, axis=-1)                   # (T_loc, E)
    gate, idx = jax.lax.top_k(probs, k)                       # (T_loc, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # 2) index-based dispatch: rank of each (token, expert) pair within expert
    flat_e = idx.reshape(-1)                                  # (T_loc*k,)
    flat_t = jnp.repeat(jnp.arange(t_loc), k)
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)                  # group by expert
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    starts = jnp.searchsorted(se, jnp.arange(e))              # (E,)
    pos = jnp.arange(t_loc * k) - starts[se]                  # rank in expert

    offset = 0
    if model_axis is not None:
        offset = jax.lax.axis_index(model_axis) * e_loc
    local_e = se - offset
    keep = (local_e >= 0) & (local_e < e_loc) & (pos < cap)
    # dispatch tables (E_loc, cap): source token id and combine weight
    disp_t = jnp.full((e_loc, cap), t_loc, dtype=jnp.int32)   # t_loc = dummy row
    disp_g = jnp.zeros((e_loc, cap), dtype=cfg.router_dtype)
    le = jnp.where(keep, local_e, 0)
    lp = jnp.where(keep, pos, cap - 1)
    disp_t = disp_t.at[le, lp].set(
        jnp.where(keep, st.astype(jnp.int32), t_loc), mode="drop"
    )
    disp_g = disp_g.at[le, lp].set(jnp.where(keep, sg, 0.0), mode="drop")

    # 3) gather + grouped expert SwiGLU
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), dt)], axis=0)
    xe = x_pad[disp_t]                                        # (E_loc, cap, d)
    h = jnp.einsum("ecd,edf->ecf", xe, w_in.astype(dt))       # (E_loc, cap, 2f)
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    oe = jnp.einsum("ecf,efd->ecd", h, w_out.astype(dt))      # (E_loc, cap, d)

    # 4) weighted scatter-add back to tokens (+psum over experts' axis)
    oe = oe * disp_g[..., None].astype(dt)
    out = jnp.zeros((t_loc + 1, d), dt).at[disp_t.reshape(-1)].add(
        oe.reshape(-1, d), mode="drop"
    )[:t_loc]
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
    return out


def _moe_inner_2d(
    x: jax.Array,          # (T, d) FULL token block (replicated over data)
    router_w: jax.Array,   # (d, E)
    w_in: jax.Array,       # (E_loc, d_loc, 2f): E on model, d on data
    w_out: jax.Array,      # (E_loc, f_loc, d): E on model, f on data
    cfg: MoEConfig,
    model_axis: str,
    data_axis: Tuple[str, ...],
) -> jax.Array:
    """Fully-resident 2D expert sharding (decode path): contraction dims are
    data-sharded, so partial matmul products are psum'd (tiny at decode batch)
    and NO weight all-gather ever happens."""
    t, d = x.shape
    e_loc, d_loc, two_f = w_in.shape
    e, k = cfg.n_experts, cfg.top_k
    dt = x.dtype
    cap = _capacity(t, cfg)

    logits = x.astype(cfg.router_dtype) @ router_w.astype(cfg.router_dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    flat_e = idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    starts = jnp.searchsorted(se, jnp.arange(e))
    pos = jnp.arange(t * k) - starts[se]
    offset = jax.lax.axis_index(model_axis) * e_loc
    local_e = se - offset
    keep = (local_e >= 0) & (local_e < e_loc) & (pos < cap)
    disp_t = jnp.full((e_loc, cap), t, dtype=jnp.int32)
    disp_g = jnp.zeros((e_loc, cap), dtype=cfg.router_dtype)
    le = jnp.where(keep, local_e, 0)
    lp = jnp.where(keep, pos, cap - 1)
    disp_t = disp_t.at[le, lp].set(jnp.where(keep, st.astype(jnp.int32), t),
                                   mode="drop")
    disp_g = disp_g.at[le, lp].set(jnp.where(keep, sg, 0.0), mode="drop")

    x_pad = jnp.concatenate([x, jnp.zeros((1, d), dt)], axis=0)
    xe = x_pad[disp_t]                                   # (E_loc, cap, d)
    # slice the contraction dim to this data-rank's weight block
    d_rank = jax.lax.axis_index(data_axis[-1])
    if len(data_axis) > 1:
        d_rank = d_rank + jax.lax.axis_index(data_axis[0]) * \
            jax.lax.axis_size(data_axis[-1])
    xe_loc = jax.lax.dynamic_slice_in_dim(xe, d_rank * d_loc, d_loc, axis=2)
    h = jnp.einsum("ecd,edf->ecf", xe_loc, w_in.astype(dt))
    h = jax.lax.psum(h, data_axis)                       # (E_loc, cap, 2f)
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    f_loc = w_out.shape[1]
    h_loc = jax.lax.dynamic_slice_in_dim(h, d_rank * f_loc, f_loc, axis=2)
    oe = jnp.einsum("ecf,efd->ecd", h_loc, w_out.astype(dt))
    oe = oe * disp_g[..., None].astype(dt)
    out = jnp.zeros((t + 1, d), dt).at[disp_t.reshape(-1)].add(
        oe.reshape(-1, d), mode="drop")[:t]
    return jax.lax.psum(out, (model_axis,) + tuple(data_axis))


def moe_ffn(
    params: Params,
    x: jax.Array,                  # (B, S, d) or (T, d)
    cfg: MoEConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    data_axes: Tuple[str, ...] = ("data",),
    model_axis: str = "model",
) -> jax.Array:
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    if mesh is None:
        out = _moe_inner(xt, params["router"], params["w_in"], params["w_out"],
                         cfg, None)
    elif cfg.ep_mode == "2d":
        P = jax.sharding.PartitionSpec
        wd = ("data",) if "data" in mesh.axis_names else ()
        inner = partial(_moe_inner_2d, cfg=cfg, model_axis=model_axis,
                        data_axis=wd)
        out = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(None, None), P(None, None),
                      P(model_axis, wd, None), P(model_axis, wd, None)),
            out_specs=P(None, None),
            check_vma=False,
        )(xt, params["router"], params["w_in"], params["w_out"])
    else:
        P = jax.sharding.PartitionSpec
        dp = tuple(data_axes) if data_axes else None  # () -> replicated tokens
        inner = partial(_moe_inner, cfg=cfg, model_axis=model_axis)
        out = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(dp, None), P(None, None),
                      P(model_axis, None, None), P(model_axis, None, None)),
            out_specs=P(dp, None),
            check_vma=False,
        )(xt, params["router"], params["w_in"], params["w_out"])
    if mesh is not None:
        # shard_map hands back its out_specs layout; give the caller its
        # input's layout so a layer scan's carry keeps one type
        out = jax.sharding.reshard(out, jax.typeof(xt).sharding.spec)
    if "shared_w_in" in params:
        dt = x.dtype
        h = xt @ params["shared_w_in"].astype(dt)
        g, u = jnp.split(h, 2, axis=-1)
        out = out + (jax.nn.silu(g) * u) @ params["shared_w_out"].astype(dt)
    return out.reshape(shape)


def moe_ref(params: Params, x: jax.Array, cfg: MoEConfig) -> jax.Array:
    """Dense per-token oracle (no capacity drops) for tests: every token is
    processed by its exact top-k experts via full einsum over E."""
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    dt = x.dtype
    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    h = jnp.einsum("td,edf->tef", xt, params["w_in"].astype(dt))
    g, u = jnp.split(h, 2, axis=-1)
    o = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, params["w_out"].astype(dt))
    mask = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)  # (T,k,E)
    w = jnp.einsum("tk,tke->te", gate, mask).astype(dt)
    out = jnp.einsum("te,ted->td", w, o)
    if "shared_w_in" in params:
        hs = xt @ params["shared_w_in"].astype(dt)
        gs, us = jnp.split(hs, 2, axis=-1)
        out = out + (jax.nn.silu(gs) * us) @ params["shared_w_out"].astype(dt)
    return out.reshape(shape)

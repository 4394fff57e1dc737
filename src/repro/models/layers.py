"""Shared pure-JAX layers: RMSNorm, RoPE, qk-norm, GQA + MLA attention,
SwiGLU MLP, chunked-causal attention (flash-style memory behaviour without a
kernel — scores are never materialized at (S, S))."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
    return jax.random.normal(key, shape, dtype) * scale


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 1e6) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 1e6) -> jax.Array:
    """x: [..., S, H, Dh]; positions: [..., S] (int)."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)                     # (dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, dh/2]
    cos = jnp.cos(ang)[..., None, :]                        # [..., S, 1, dh/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (optionally qk-normed), chunked over queries
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    rope_theta: float = 1e6
    q_chunk: int = 1024   # queries per chunk: scores live at (B,H,q_chunk,S)
    unroll: bool = False  # unroll the chunk scan (calibration lowerings)
    scores_f32: bool = True  # False: keep the score pipeline in compute dtype
                             # (halves attention HBM traffic; recsys encoders)


def init_gqa(key, cfg: AttnConfig) -> Params:
    ks = jax.random.split(key, 6)
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": _init(ks[0], (d, h * dh)),
        "wk": _init(ks[1], (d, hk * dh)),
        "wv": _init(ks[2], (d, hk * dh)),
        "wo": _init(ks[3], (h * dh, d)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), jnp.float32)
        p["k_norm"] = jnp.ones((dh,), jnp.float32)
    return p


def _attend_chunked(
    q: jax.Array,            # (B, Sq, H, Dh)
    k: jax.Array,            # (B, Sk, Hk, Dh)  Hk divides H (GQA: no repeat
    v: jax.Array,            # (B, Sk, Hk, Dv)   materialization — grouped einsum)
    q_positions: jax.Array,  # (B, Sq)
    kv_positions: jax.Array, # (B, Sk)
    kv_mask: Optional[jax.Array],  # (B, Sk) valid mask or None
    causal: bool,
    q_chunk: int,
    unroll: bool = False,
    scores_f32: bool = True,
) -> jax.Array:
    b, sq, h, dh = q.shape
    hk = k.shape[2]
    dv = v.shape[3]
    rep = h // hk
    scale = 1.0 / np.sqrt(dh)
    qc = min(q_chunk, sq)
    n_chunks = (sq + qc - 1) // qc
    pad = n_chunks * qc - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)))
    q = q.reshape(b, n_chunks * qc, hk, rep, dh)
    qs = q.reshape(b, n_chunks, qc, hk, rep, dh).transpose(1, 0, 2, 3, 4, 5)
    qps = q_positions.reshape(b, n_chunks, qc).transpose(1, 0, 2)

    def chunk_fn(carry, inp):
        qi, qpi = inp  # (B, qc, Hk, rep, Dh), (B, qc)
        acc_dt = jnp.float32 if scores_f32 else v.dtype
        s = jnp.einsum("bqhrd,bkhd->bhrqk", qi, k,
                       preferred_element_type=acc_dt)
        s = s * jnp.asarray(scale, acc_dt)
        if causal:
            cm = qpi[:, None, None, :, None] >= kv_positions[:, None, None, None, :]
            s = jnp.where(cm, s, -1e30)
        if kv_mask is not None:
            s = jnp.where(kv_mask[:, None, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhrqk,bkhd->bqhrd", p, v)
        return carry, o

    if n_chunks == 1:
        _, outs = chunk_fn(None, (qs[0], qps[0]))
        outs = outs[None]
    else:
        _, outs = jax.lax.scan(chunk_fn, None, (qs, qps), unroll=unroll)
    # one merge per reshape: under Explicit mesh axes a reshape may merge
    # dims on only one axis group
    out = outs.reshape(n_chunks, b, qc, h, dv).transpose(1, 0, 2, 3, 4)
    out = out.reshape(b, n_chunks * qc, h, dv)
    return out[:, :sq]


def _qkv(params: Params, x: jax.Array, positions: jax.Array, cfg: AttnConfig):
    b, s, _ = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    q = (x @ params["wq"].astype(dt)).reshape(b, s, h, dh)
    k = (x @ params["wk"].astype(dt)).reshape(b, s, hk, dh)
    v = (x @ params["wv"].astype(dt)).reshape(b, s, hk, dh)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attention(
    params: Params,
    x: jax.Array,                       # (B, S, D)
    positions: jax.Array,               # (B, S)
    cfg: AttnConfig,
    causal: bool = True,
    kv_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Self-attention over x (training / prefill)."""
    b, s, _ = x.shape
    q, k, v = _qkv(params, x, positions, cfg)
    out = _attend_chunked(q, k, v, positions, positions, kv_mask, causal,
                          cfg.q_chunk, cfg.unroll, cfg.scores_f32)
    return out.reshape(b, s, cfg.n_heads * cfg.head_dim) @ params["wo"].astype(x.dtype)


def gqa_decode(
    params: Params,
    x: jax.Array,                # (B, 1, D) new token
    position: jax.Array,         # (B, 1) its position
    k_cache: jax.Array,          # (B, Skv, Hk, Dh) rope'd cached keys
    v_cache: jax.Array,          # (B, Skv, Hk, Dh)
    cfg: AttnConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step: insert the new token's KV at ``position`` and attend
    against the full cache. Returns (out, k_cache, v_cache) updated."""
    b, s, _ = x.shape
    assert s == 1
    q, k_new, v_new = _qkv(params, x, position, cfg)
    # write the new entry (batch-wise positions may differ -> vmap the update)
    def upd(cache, entry, pos):
        return jax.lax.dynamic_update_slice_in_dim(cache, entry, pos, axis=0)

    k_cache = jax.vmap(upd)(k_cache, k_new, position[:, 0])
    v_cache = jax.vmap(upd)(v_cache, v_new, position[:, 0])
    skv = k_cache.shape[1]
    kv_mask = jnp.arange(skv)[None, :] <= position  # (B, Skv)
    kvp = jnp.broadcast_to(jnp.arange(skv)[None, :], (b, skv))
    out = _attend_chunked(q, k_cache, v_cache, position, kvp, kv_mask, False,
                          cfg.q_chunk)
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim) @ params["wo"].astype(x.dtype)
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _init(ks[0], (d_model, d_ff)),
        "w_up": _init(ks[1], (d_model, d_ff)),
        "w_down": _init(ks[2], (d_ff, d_model)),
    }


def swiglu(params: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    g = jax.nn.silu(x @ params["w_gate"].astype(dt))
    u = x @ params["w_up"].astype(dt)
    return (g * u) @ params["w_down"].astype(dt)


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention, DeepSeek-V2) — compressed KV cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4
    q_chunk: int = 1024
    unroll: bool = False


def init_mla(key, cfg: MLAConfig) -> Params:
    ks = jax.random.split(key, 6)
    d, h = cfg.d_model, cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": _init(ks[0], (d, h * qd)),
        "w_dkv": _init(ks[1], (d, cfg.kv_lora_rank)),     # compress
        "w_k_rope": _init(ks[2], (d, cfg.qk_rope_dim)),   # shared rope key
        "w_uk": _init(ks[3], (cfg.kv_lora_rank, h * cfg.qk_nope_dim)),
        "w_uv": _init(ks[4], (cfg.kv_lora_rank, h * cfg.v_head_dim)),
        "wo": _init(ks[5], (h * cfg.v_head_dim, d)),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
    }


def mla_attention_train(
    params: Params,
    x: jax.Array,              # (B, S, D)
    positions: jax.Array,      # (B, S)
    cfg: MLAConfig,
) -> jax.Array:
    """Training/prefill path: decompress K/V and run standard causal MHA."""
    b, s, d = x.shape
    h = cfg.n_heads
    dt = x.dtype
    q = (x @ params["wq"].astype(dt)).reshape(b, s, h, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)

    c_kv = rms_norm(x @ params["w_dkv"].astype(dt), params["kv_norm"])  # (B,S,r)
    k_pe = apply_rope(
        (x @ params["w_k_rope"].astype(dt))[:, :, None, :], positions, cfg.rope_theta
    )  # (B,S,1,rope)
    k_nope = (c_kv @ params["w_uk"].astype(dt)).reshape(b, s, h, cfg.qk_nope_dim)
    v = (c_kv @ params["w_uv"].astype(dt)).reshape(b, s, h, cfg.v_head_dim)

    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    k_full = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, h, cfg.qk_rope_dim))], axis=-1)
    out = _attend_chunked(q_full, k_full, v, positions, positions, None, True,
                          cfg.q_chunk, cfg.unroll)
    return out.reshape(b, s, h * cfg.v_head_dim) @ params["wo"].astype(dt)


def mla_attention_decode(
    params: Params,
    x: jax.Array,               # (B, 1, D)
    position: jax.Array,        # (B, 1)
    c_kv_cache: jax.Array,      # (B, Skv, r) compressed latents (normed)
    k_pe_cache: jax.Array,      # (B, Skv, rope)
    kv_mask: jax.Array,         # (B, Skv)
    cfg: MLAConfig,
) -> jax.Array:
    """Decode path with the absorbed-matmul trick: score against the compressed
    latents directly; W_uk/W_uv are absorbed into the query/output sides, so the
    per-token KV-cache read is r + rope floats instead of 2*H*Dh."""
    b, s, d = x.shape
    h = cfg.n_heads
    dt = x.dtype
    q = (x @ params["wq"].astype(dt)).reshape(b, s, h, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_pe = apply_rope(q_pe, position, cfg.rope_theta)

    w_uk = params["w_uk"].astype(dt).reshape(cfg.kv_lora_rank, h, cfg.qk_nope_dim)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)          # absorb W_uk
    s_lat = jnp.einsum("bshr,bkr->bhsk", q_lat, c_kv_cache,
                       preferred_element_type=jnp.float32)
    s_pe = jnp.einsum("bshn,bkn->bhsk", q_pe, k_pe_cache,
                      preferred_element_type=jnp.float32)
    scale = 1.0 / np.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    scores = (s_lat + s_pe) * scale
    scores = jnp.where(kv_mask[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(dt)
    o_lat = jnp.einsum("bhsk,bkr->bshr", p, c_kv_cache)         # (B,1,H,r)
    w_uv = params["w_uv"].astype(dt).reshape(cfg.kv_lora_rank, h, cfg.v_head_dim)
    out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)             # absorb W_uv
    return out.reshape(b, s, h * cfg.v_head_dim) @ params["wo"].astype(dt)


def mla_new_cache_entries(params: Params, x: jax.Array, positions: jax.Array,
                          cfg: MLAConfig) -> Tuple[jax.Array, jax.Array]:
    """Compressed cache entries for new tokens: (c_kv, k_pe)."""
    dt = x.dtype
    c_kv = rms_norm(x @ params["w_dkv"].astype(dt), params["kv_norm"])
    k_pe = apply_rope(
        (x @ params["w_k_rope"].astype(dt))[:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]
    return c_kv, k_pe

"""HSTU: Hierarchical Sequential Transduction Units (Zhai et al., "Actions
Speak Louder than Words", arXiv:2402.17152, §3.1, eqs. 1-3), a generative
recommender trained to predict each next item of the user's history.

Each block, over the right-aligned history X (B, L, D):

    U, V, Q, K = split(SiLU(LayerNorm(X) W_uvqk))
    A = SiLU(Q K^T + rab_p(j - i) + rab_t(bucket(t_{i+1} - t_j))) / L,
        over causal pairs of valid events (``kernels/hstu_attention``)
    X = X + W_o (LayerNorm(A V) * U) + b_o

Items enter as their embedding times sqrt(D) plus a learned position
counted from the row's oldest event; the output and the item embeddings it
is scored against are L2-normalised; the loss is a sampled softmax at a
temperature over the next item and negatives shared by the batch
(``recsys.sampled_softmax_nll``). The LayerNorms have no parameters, as in
the authors' code. The residual stream and the norms are float32; matmul
inputs and the attention run in ``compute_dtype``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.hstu_attention.ops import hstu_attention
from repro.models.embedding import init_table
from repro.models.recsys import (
    _count,
    _lookup,
    _seq_lookup,
    _shard_batch_all,
    sampled_softmax_nll,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class HSTUConfig:
    name: str = "hstu"
    embed_dim: int = 256
    n_blocks: int = 8
    n_heads: int = 2
    dqk: int = 128
    dv: int = 128
    seq_len: int = 4096
    item_vocab: int = 26_745      # id 0 pads
    num_negatives: int = 128
    temperature: float = 0.05
    time_buckets: int = 128       # the time table holds buckets 0..128
    compute_dtype: Any = jnp.bfloat16
    mesh: Any = None              # row-sharded lookups when set
    data_axes: Tuple[str, ...] = ("data",)
    unroll_scans: bool = False

    def param_count(self) -> int:
        return _count(self, init_hstu)


def init_hstu(key, cfg: HSTUConfig) -> Params:
    """Tables N(0, 0.01^2), matrices N(0, 1/fan_in), the output bias 0;
    the blocks stacked on a leading axis."""
    d, h = cfg.embed_dim, cfg.n_heads
    width = 2 * h * cfg.dv + 2 * h * cfg.dqk
    ks = jax.random.split(key, 6)
    nb = cfg.n_blocks
    normal = lambda k, shape, scale: (
        jax.random.normal(k, shape, jnp.float32) * scale)
    return {
        "item_table": init_table(ks[0], cfg.item_vocab, d),
        "pos_table": init_table(ks[1], cfg.seq_len, d),
        "blocks": {
            "uvqk": normal(ks[2], (nb, d, width), 1 / np.sqrt(d)),
            "o_w": normal(ks[3], (nb, h * cfg.dv, d), 1 / np.sqrt(h * cfg.dv)),
            "o_b": jnp.zeros((nb, d), jnp.float32),
            "rab_p": normal(ks[4], (nb, cfg.seq_len), 0.01),
            "rab_t": normal(ks[5], (nb, cfg.time_buckets + 1), 0.01),
        },
    }


def _layer_norm(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def l2_normalize(x, eps: float = 1e-6):
    """x / max(|x|, eps), with a zero gradient at x = 0 (padding rows)."""
    x = x.astype(jnp.float32)
    sq = jnp.sum(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.maximum(sq, eps * eps))


@jax.custom_vjp
def _silu(x):
    return jax.nn.silu(x)


def _silu_fwd(x):
    return jax.nn.silu(x), x


def _silu_bwd(x, g):
    """Only the input is kept for the backward; at (B, L, 1024) a saved
    sigmoid costs as much HBM as the activation."""
    x32 = x.astype(jnp.float32)
    s = jax.nn.sigmoid(x32)
    return ((g.astype(jnp.float32) * s * (1 + x32 * (1 - s))).astype(x.dtype),)


_silu.defvjp(_silu_fwd, _silu_bwd)


def _attention(q, k, v, ts, start, rab_p, rab_t):
    """The kernel on each device's rows where a mesh is set (the batch's
    own sharding), else on the whole batch."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return hstu_attention(q, k, v, ts, start, rab_p, rab_t)
    from jax.sharding import PartitionSpec as P

    rows = P(jax.typeof(ts).sharding.spec[0])
    return jax.shard_map(hstu_attention, mesh=mesh,
                         in_specs=(rows,) * 5 + (P(), P()), out_specs=rows,
                         check_vma=False)(q, k, v, ts, start, rab_p, rab_t)


def hstu_encode(params, ids, ts, mask, cfg: HSTUConfig) -> jax.Array:
    """(B, L) item ids, int32 timestamps (seconds) and validity of
    right-aligned rows -> (B, L, D) L2-normalised float32 outputs."""
    dt = cfg.compute_dtype
    b, length = ids.shape
    d, h = cfg.embed_dim, cfg.n_heads
    hv, hq = h * cfg.dv, h * cfg.dqk
    start = (length - jnp.sum(mask, axis=1)).astype(jnp.int32)
    with jax.named_scope("embed"):
        pos = jnp.clip(jnp.arange(length)[None, :] - start[:, None], 0,
                       length - 1)
        x = (_seq_lookup(params["item_table"], ids, cfg, jnp.float32)
             * np.sqrt(d)
             + _seq_lookup(params["pos_table"], pos, cfg, jnp.float32))
        x = jnp.where(mask[..., None], x, 0.0)
    x = _shard_batch_all(x, cfg)
    ts, start = _shard_batch_all(ts, cfg), _shard_batch_all(start, cfg)

    def body(x, blk):
        uvqk = _silu(jnp.einsum(
            "bld,de->ble", _layer_norm(x).astype(dt), blk["uvqk"].astype(dt)))
        u, v, q, k = jnp.split(uvqk, [hv, 2 * hv, 2 * hv + hq], axis=-1)
        a = _attention(q.reshape(b, length, h, cfg.dqk),
                       k.reshape(b, length, h, cfg.dqk),
                       v.reshape(b, length, h, cfg.dv), ts, start,
                       blk["rab_p"], blk["rab_t"])
        y = _layer_norm(a.reshape(b, length, hv)) * u
        return x + jnp.einsum("ble,ed->bld", y.astype(dt),
                              blk["o_w"].astype(dt),
                              preferred_element_type=jnp.float32) \
            + blk["o_b"], None

    with jax.named_scope("encoder"):
        x, _ = jax.lax.scan(body, x, params["blocks"],
                            unroll=cfg.unroll_scans)
        return l2_normalize(x)


def hstu_loss(params, batch, cfg: HSTUConfig) -> jax.Array:
    """Next-item prediction at every position whose next one is valid: a
    sampled softmax over that item and the batch's shared negatives
    ``neg_ids``, embeddings L2-normalised, at ``cfg.temperature``."""
    ids, mask = batch["uih_item_id"], batch["uih_mask"].astype(bool)
    h = hstu_encode(params, ids, batch["uih_timestamp"], mask, cfg)
    with jax.named_scope("logits"):
        target = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
        weights = mask & jnp.pad(mask[:, 1:], ((0, 0), (0, 1)))
        gold = l2_normalize(_seq_lookup(params["item_table"], target, cfg,
                                        jnp.float32))
        neg_ids = batch["neg_ids"]
        neg = l2_normalize(params["item_table"][neg_ids])
        return sampled_softmax_nll(h, gold, neg, weights,
                                   temperature=cfg.temperature,
                                   gold_ids=target, neg_ids=neg_ids)


def _user(params, batch, cfg):
    h = hstu_encode(params, batch["uih_item_id"], batch["uih_timestamp"],
                    batch["uih_mask"].astype(bool), cfg)
    return h[:, -1]                                           # (B, D)


def hstu_forward(params, batch, cfg: HSTUConfig) -> jax.Array:
    """Serving: the candidate item's logit as the next item."""
    cand = l2_normalize(_lookup(params["item_table"], batch["cand_item_id"],
                                cfg, jnp.float32))
    return jnp.sum(_user(params, batch, cfg) * cand, -1) / cfg.temperature


def hstu_score_candidates(params, batch, cand_ids, cfg) -> jax.Array:
    """One history scored against N candidate items: (1, N) logits."""
    cand = l2_normalize(params["item_table"][cand_ids])        # (N, D)
    return _user(params, batch, cfg) @ cand.T / cfg.temperature

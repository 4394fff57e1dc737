"""RecSys model zoo — the tenants of the VLM data plane.

  * TwoTowerRetrieval  — sampled-softmax retrieval (YouTube RecSys'19)
  * DCNv2              — cross-network CTR (arXiv:2008.13535)
  * DIEN               — GRU + AUGRU interest evolution (arXiv:1809.03672)
  * BERT4Rec           — bidirectional masked item prediction (arXiv:1904.06690)
  * DLRMUIH            — the paper's own flagship: DLRM + target-aware
                         transformer encoder over ultra-long UIH sequences

All consume padded UIH arrays exactly as emitted by the DPP featurizer
(``uih_item_id``, ``uih_mask`` ...), so the data plane and the models share one
contract. Embedding tables are huge (1e6–1e8 rows) and row-sharded at dry-run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.embedding import (
    bag_rowsharded,
    embedding_bag,
    init_table,
    lookup_rowsharded,
    mlp_apply,
    mlp_init,
    seq_rowsharded,
)

Params = Dict[str, Any]


def _count(cfg, init_fn) -> int:
    leaves = jax.eval_shape(lambda: init_fn(jax.random.PRNGKey(0), cfg))
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(leaves))


def _lookup(table, ids, cfg, dt):
    """Candidate/field lookup; row-sharded shard_map path on a mesh."""
    if cfg.mesh is not None:
        return lookup_rowsharded(table, ids, cfg.mesh, cfg.data_axes, dtype=dt)
    return table.astype(dt)[ids]


def _seq_lookup(table, ids, cfg, dt):
    """Per-position sequence lookup (B, S) -> (B, S, D)."""
    if cfg.mesh is not None:
        return seq_rowsharded(table, ids, cfg.mesh, cfg.data_axes, dtype=dt)
    return table.astype(dt)[ids]


def _replicated_lookup(table, ids, cfg, dt):
    """Gather from a small replicated table; on a mesh the rows take the
    ids' batch sharding (Explicit axes cannot infer it for a gather)."""
    if cfg.mesh is None:
        return table.astype(dt)[ids]
    from jax.sharding import PartitionSpec as P

    spec = jax.typeof(ids).sharding.spec
    return table.astype(dt).at[ids].get(out_sharding=P(*spec, None))


def _bag(table, ids, mask, combiner, cfg, dt):
    if cfg.mesh is not None:
        return bag_rowsharded(table, ids, mask, combiner, cfg.mesh,
                              cfg.data_axes, dtype=dt)
    return embedding_bag(table, ids, mask, combiner, dt)


def _reshard_batch(x, cfg, axes):
    if cfg.mesh is None:
        return x
    from jax.sharding import PartitionSpec as P

    return jax.sharding.reshard(x, P(axes, *([None] * (x.ndim - 1))))


def _shard_batch_all(x, cfg):
    """Recsys encoders have no model-parallel dims, so the ``model`` axis
    would otherwise idle while per-chip attention/GRU activations blow up
    16x: re-shard the batch over (data x model) for the encoder section
    (one cheap all-to-all in; ``_shard_batch_data`` is the way out)."""
    return _reshard_batch(x, cfg, tuple(cfg.data_axes) + ("model",))


def _shard_batch_data(x, cfg):
    """Back from the encoder section to the data-only batch layout of the
    embedding lookups."""
    return _reshard_batch(x, cfg, tuple(cfg.data_axes))


def bce_with_logits(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    labels = labels.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def normalized_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """NE (paper §5.2, He et al. 2014): CE normalized by the entropy of the
    base rate — the paper's model-quality metric."""
    ce = bce_with_logits(logits, labels)
    p = jnp.clip(jnp.mean(labels.astype(jnp.float32)), 1e-6, 1 - 1e-6)
    h = -(p * jnp.log(p) + (1 - p) * jnp.log(1 - p))
    return ce / h


# ===========================================================================
# Two-tower retrieval
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class TwoTowerConfig:
    name: str = "two-tower-retrieval"
    embed_dim: int = 256
    tower_mlp: Tuple[int, ...] = (1024, 512, 256)
    item_vocab: int = 10_000_000
    user_vocab: int = 20_000_000
    uih_len: int = 100
    temperature: float = 0.05
    compute_dtype: Any = jnp.bfloat16
    mesh: Any = None              # row-sharded lookups when set
    data_axes: Tuple[str, ...] = ("data",)

    def param_count(self) -> int:
        return _count(self, init_two_tower)


def init_two_tower(key, cfg: TwoTowerConfig) -> Params:
    ks = jax.random.split(key, 4)
    d = cfg.embed_dim
    return {
        "item_table": init_table(ks[0], cfg.item_vocab, d),
        "user_table": init_table(ks[1], cfg.user_vocab, d),
        # user tower input: user id emb + history bag emb
        "user_mlp": mlp_init(ks[2], [2 * d, *cfg.tower_mlp]),
        # item tower input: item emb
        "item_mlp": mlp_init(ks[3], [d, *cfg.tower_mlp]),
    }


def two_tower_user(params, user_id, uih_ids, uih_mask, cfg) -> jax.Array:
    dt = cfg.compute_dtype
    with jax.named_scope("embed"):
        u = _lookup(params["user_table"], user_id, cfg, dt)
        hist = _bag(params["item_table"], uih_ids, uih_mask, "mean", cfg, dt)
    z = _shard_batch_all(jnp.concatenate([u, hist], axis=-1), cfg)
    z = mlp_apply(params["user_mlp"], z, len(cfg.tower_mlp))
    return z / (jnp.linalg.norm(z.astype(jnp.float32), axis=-1, keepdims=True)
                + 1e-6).astype(dt)


def two_tower_item(params, item_id, cfg) -> jax.Array:
    dt = cfg.compute_dtype
    with jax.named_scope("embed"):
        z = _lookup(params["item_table"], item_id, cfg, dt)
    z = _shard_batch_all(z, cfg)
    z = mlp_apply(params["item_mlp"], z, len(cfg.tower_mlp))
    return z / (jnp.linalg.norm(z.astype(jnp.float32), axis=-1, keepdims=True)
                + 1e-6).astype(dt)


def two_tower_loss(params, batch, cfg: TwoTowerConfig,
                   log_q: Optional[jax.Array] = None) -> jax.Array:
    """In-batch sampled softmax with logQ correction."""
    u = two_tower_user(params, batch["user_id"], batch["uih_item_id"],
                       batch["uih_mask"], cfg)
    v = two_tower_item(params, batch["cand_item_id"], cfg)
    with jax.named_scope("logits"):
        logits = (u @ v.T).astype(jnp.float32) / cfg.temperature   # (B, B)
        if log_q is not None:  # correct for in-batch sampling bias
            logits = logits - log_q[None, :]
        labels = jnp.arange(logits.shape[0])
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)


def two_tower_score_candidates(params, batch, cand_ids, cfg) -> jax.Array:
    """retrieval_cand: one query vs N candidates as a single batched dot."""
    u = two_tower_user(params, batch["user_id"], batch["uih_item_id"],
                       batch["uih_mask"], cfg)                 # (1, d)
    v = two_tower_item(params, cand_ids, cfg)                  # (N, d)
    return (u @ v.T) / cfg.temperature                         # (1, N)


# ===========================================================================
# DCN-v2
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class DCNv2Config:
    name: str = "dcn-v2"
    n_dense: int = 13
    n_sparse: int = 26
    embed_dim: int = 16
    n_cross_layers: int = 3
    mlp: Tuple[int, ...] = (1024, 1024, 512)
    field_vocab: int = 1_000_000
    compute_dtype: Any = jnp.bfloat16
    mesh: Any = None              # row-sharded lookups when set
    data_axes: Tuple[str, ...] = ("data",)

    @property
    def d_interact(self) -> int:
        return self.n_sparse * self.embed_dim + self.n_dense

    def param_count(self) -> int:
        return _count(self, init_dcn_v2)


def init_dcn_v2(key, cfg: DCNv2Config) -> Params:
    ks = jax.random.split(key, 3 + cfg.n_cross_layers)
    d = cfg.d_interact
    p: Params = {
        # one big table: field f uses rows [f*vocab, (f+1)*vocab)
        "embed": init_table(ks[0], cfg.n_sparse * cfg.field_vocab, cfg.embed_dim),
        "mlp": mlp_init(ks[1], [d, *cfg.mlp]),
        "head": mlp_init(ks[2], [cfg.mlp[-1] + d, 1]),
    }
    for i in range(cfg.n_cross_layers):
        p[f"cross_w{i}"] = jax.random.normal(ks[3 + i], (d, d), jnp.float32) / np.sqrt(d)
        p[f"cross_b{i}"] = jnp.zeros((d,), jnp.float32)
    return p


def dcn_v2_forward(params, batch, cfg: DCNv2Config) -> jax.Array:
    dt = cfg.compute_dtype
    ids = batch["sparse_ids"]                                  # (B, F)
    offsets = jnp.arange(cfg.n_sparse) * cfg.field_vocab
    with jax.named_scope("embed"):
        emb = _seq_lookup(params["embed"], ids + offsets[None, :], cfg,
                          dt)                                  # (B,F,D)
    x0 = _shard_batch_all(jnp.concatenate(
        [emb.reshape(ids.shape[0], -1), batch["dense"].astype(dt)], axis=-1
    ), cfg)
    x = x0
    for i in range(cfg.n_cross_layers):                        # x_{l+1} = x0*(W x_l + b) + x_l
        xw = x @ params[f"cross_w{i}"].astype(dt) + params[f"cross_b{i}"].astype(dt)
        x = x0 * xw + x
    deep = mlp_apply(params["mlp"], x0, len(cfg.mlp), final_act=True)
    z = jnp.concatenate([x, deep], axis=-1)
    return mlp_apply(params["head"], z, 1)[:, 0]


def dcn_v2_loss(params, batch, cfg) -> jax.Array:
    return bce_with_logits(dcn_v2_forward(params, batch, cfg), batch["label"])


# ===========================================================================
# DIEN (GRU interest extractor + AUGRU interest evolution)
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class DIENConfig:
    name: str = "dien"
    embed_dim: int = 18
    seq_len: int = 100
    gru_dim: int = 108
    mlp: Tuple[int, ...] = (200, 80)
    item_vocab: int = 1_000_000
    cat_vocab: int = 10_000
    compute_dtype: Any = jnp.bfloat16
    mesh: Any = None              # row-sharded lookups when set
    data_axes: Tuple[str, ...] = ("data",)
    unroll_scans: bool = False

    @property
    def d_in(self) -> int:
        return 2 * self.embed_dim  # item emb ++ category emb

    def param_count(self) -> int:
        return _count(self, init_dien)


def _gru_init(key, d_in, d_h):
    ks = jax.random.split(key, 3)
    s_in, s_h = 1.0 / np.sqrt(d_in), 1.0 / np.sqrt(d_h)
    return {
        "wx": jax.random.normal(ks[0], (d_in, 3 * d_h), jnp.float32) * s_in,
        "wh": jax.random.normal(ks[1], (d_h, 3 * d_h), jnp.float32) * s_h,
        "b": jnp.zeros((3 * d_h,), jnp.float32),
    }


def _gru_cell(p, h, x, att: Optional[jax.Array] = None):
    """GRU step; ``att`` (B, 1) turns it into AUGRU (attention-gated update)."""
    dt = x.dtype
    gx = x @ p["wx"].astype(dt) + p["b"].astype(dt)
    gh = h @ p["wh"].astype(dt)
    rx, zx, nx = jnp.split(gx, 3, axis=-1)
    rh, zh, nh = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(rx + rh)
    z = jax.nn.sigmoid(zx + zh)
    n = jnp.tanh(nx + r * nh)
    if att is not None:
        z = z * att  # AUGRU: scale update gate by attention weight
    return (1 - z) * h + z * n


def init_dien(key, cfg: DIENConfig) -> Params:
    ks = jax.random.split(key, 6)
    return {
        "item_table": init_table(ks[0], cfg.item_vocab, cfg.embed_dim),
        "cat_table": init_table(ks[1], cfg.cat_vocab, cfg.embed_dim),
        "gru1": _gru_init(ks[2], cfg.d_in, cfg.gru_dim),
        "augru": _gru_init(ks[3], cfg.gru_dim, cfg.gru_dim),
        "att_w": jax.random.normal(ks[4], (cfg.gru_dim, cfg.d_in), jnp.float32)
        * (1.0 / np.sqrt(cfg.gru_dim)),
        "mlp": mlp_init(ks[5], [cfg.gru_dim + 2 * cfg.d_in, *cfg.mlp, 1]),
    }


def dien_forward(params, batch, cfg: DIENConfig) -> jax.Array:
    dt = cfg.compute_dtype
    ids, cats = batch["uih_item_id"], batch["uih_category"]
    mask = batch["uih_mask"].astype(dt)                        # (B, S)
    with jax.named_scope("embed"):
        e = jnp.concatenate(
            [_seq_lookup(params["item_table"], ids, cfg, dt),
             _seq_lookup(params["cat_table"], cats, cfg, dt)],
            axis=-1,
        )                                                      # (B, S, 2D)
        tgt = jnp.concatenate(
            [_lookup(params["item_table"], batch["cand_item_id"], cfg, dt),
             _lookup(params["cat_table"], batch["cand_category"], cfg, dt)],
            axis=-1,
        )                                                      # (B, 2D)
    e = _shard_batch_all(e, cfg)
    mask = _shard_batch_all(mask, cfg)
    tgt = _shard_batch_all(tgt, cfg)
    b, s, _ = e.shape
    h0 = jnp.zeros((b, cfg.gru_dim), dt)

    def step1(h, inp):
        x, mk = inp
        h_new = _gru_cell(params["gru1"], h, x)
        h = jnp.where(mk[:, None] > 0, h_new, h)
        return h, h

    with jax.named_scope("encoder"):
        _, interests = jax.lax.scan(step1, h0,
                                    (e.transpose(1, 0, 2), mask.T),
                                    unroll=cfg.unroll_scans)
    interests = interests.transpose(1, 0, 2)                   # (B, S, H)

    # attention of target vs interest states
    att_logits = jnp.einsum(
        "bsh,hd,bd->bs", interests, params["att_w"].astype(dt), tgt,
        preferred_element_type=jnp.float32,
    )
    att = jax.nn.softmax(
        jnp.where(mask > 0, att_logits, -1e30), axis=-1
    ).astype(dt)                                               # (B, S)

    def step2(h, inp):
        x, a, mk = inp
        h_new = _gru_cell(params["augru"], h, x, a[:, None])
        h = jnp.where(mk[:, None] > 0, h_new, h)
        return h, None

    final, _ = jax.lax.scan(
        step2, h0, (interests.transpose(1, 0, 2), att.T, mask.T),
        unroll=cfg.unroll_scans,
    )                                                          # (B, H)
    hist_sum = jnp.sum(e * mask[..., None], axis=1)
    z = jnp.concatenate([final, tgt, hist_sum], axis=-1)
    return mlp_apply(params["mlp"], z, len(cfg.mlp) + 1)[:, 0]


def dien_loss(params, batch, cfg) -> jax.Array:
    return bce_with_logits(dien_forward(params, batch, cfg), batch["label"])


# ===========================================================================
# BERT4Rec
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class BERT4RecConfig:
    name: str = "bert4rec"
    embed_dim: int = 64
    n_blocks: int = 2
    n_heads: int = 2
    seq_len: int = 200
    item_vocab: int = 1_000_000
    mask_token: int = 0
    compute_dtype: Any = jnp.bfloat16
    mesh: Any = None              # row-sharded lookups when set
    data_axes: Tuple[str, ...] = ("data",)
    loss_chunk: int = 0   # 0 = no chunking
    unroll_scans: bool = False

    def param_count(self) -> int:
        return _count(self, init_bert4rec)


def init_bert4rec(key, cfg: BERT4RecConfig) -> Params:
    ks = jax.random.split(key, 2 + cfg.n_blocks)
    d = cfg.embed_dim
    attn_cfg = L.AttnConfig(d_model=d, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_heads, head_dim=d // cfg.n_heads,
                            rope_theta=1e4, q_chunk=1 << 30)

    def block_init(k):
        k1, k2 = jax.random.split(k)
        return {
            "attn": L.init_gqa(k1, attn_cfg),
            "ffn": L.init_swiglu(k2, d, 4 * d),
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
        }

    return {
        "item_table": init_table(ks[0], cfg.item_vocab, d),
        "pos_table": init_table(ks[1], cfg.seq_len, d),
        "blocks": jax.vmap(block_init)(jax.random.split(ks[-1], cfg.n_blocks)),
        "final_ln": jnp.ones((d,), jnp.float32),
    }


def bert4rec_encode(params, ids, mask, cfg: BERT4RecConfig) -> jax.Array:
    dt = cfg.compute_dtype
    b, s = ids.shape
    attn_cfg = L.AttnConfig(d_model=cfg.embed_dim, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_heads,
                            head_dim=cfg.embed_dim // cfg.n_heads,
                            rope_theta=1e4, q_chunk=1 << 30,
                            unroll=cfg.unroll_scans,
                            scores_f32=(cfg.mesh is None))
    with jax.named_scope("embed"):
        h = _seq_lookup(params["item_table"], ids, cfg, dt) \
            + params["pos_table"].astype(dt)[None]
    h = _shard_batch_all(h, cfg)
    mask = _shard_batch_all(mask, cfg)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def body(h, block):
        hn = L.rms_norm(h, block["ln1"])
        h = h + L.gqa_attention(block["attn"], hn, positions, attn_cfg,
                                causal=False, kv_mask=mask)   # bidirectional
        hn = L.rms_norm(h, block["ln2"])
        return h + L.swiglu(block["ffn"], hn), None

    with jax.named_scope("encoder"):
        h, _ = jax.lax.scan(body, h, params["blocks"],
                            unroll=cfg.unroll_scans)
        return L.rms_norm(h, params["final_ln"])


def bert4rec_loss(params, batch, cfg: BERT4RecConfig) -> jax.Array:
    """Cloze objective: predict items at masked positions.

    At production vocab (1e6 items) a full softmax over (B, S, V) is
    infeasible; when the batch carries shared sampled negatives (``neg_ids``)
    we use a sampled softmax, chunked over the sequence axis."""
    ids = batch["uih_item_id"]
    mask = batch["uih_mask"]
    mask_pos = batch["mask_pos"].astype(bool)                 # (B, S) to predict
    inputs = jnp.where(mask_pos, cfg.mask_token, ids)
    h = bert4rec_encode(params, inputs, mask, cfg)            # (B, S, D)
    with jax.named_scope("logits"):
        return _bert4rec_nll(params, h, ids, mask_pos, batch.get("neg_ids"),
                             cfg)


def _bert4rec_nll(params, h, ids, mask_pos, neg_ids, cfg: BERT4RecConfig):
    """Mean NLL of the items at masked positions: a full softmax over the
    table, or a sampled one over shared negatives ``neg_ids``."""
    table = params["item_table"].astype(h.dtype)
    if neg_ids is None:                                       # smoke path: full softmax
        logits = jnp.einsum("bsd,vd->bsv", h, table).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ids[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mask_pos
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask_pos), 1)

    neg_emb = table[neg_ids]                                  # (N, D) small
    gold_emb = _seq_lookup(params["item_table"], ids, cfg, h.dtype)  # (B,S,D)
    gold_logit = jnp.sum(h * gold_emb, axis=-1).astype(jnp.float32)  # (B, S)
    b, s, d = h.shape
    lc = cfg.loss_chunk if cfg.loss_chunk and s % cfg.loss_chunk == 0 else s
    n_chunks = s // lc
    hs = h.reshape(b, n_chunks, lc, d).transpose(1, 0, 2, 3)
    gl = gold_logit.reshape(b, n_chunks, lc).transpose(1, 0, 2)
    mp = mask_pos.reshape(b, n_chunks, lc).transpose(1, 0, 2)

    def chunk(carry, inp):
        hi, gi, mi = inp
        neg_logits = jnp.einsum("bsd,nd->bsn", hi, neg_emb).astype(jnp.float32)
        # sampled softmax over [gold | negatives]; max per (b, s) position
        m = jnp.maximum(jnp.max(neg_logits, -1), gi)
        z = jnp.exp(gi - m) + jnp.sum(jnp.exp(neg_logits - m[..., None]), -1)
        nll = (m + jnp.log(z) - gi) * mi
        return carry + jnp.sum(nll), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (hs, gl, mp),
                            unroll=cfg.unroll_scans)
    return total / jnp.maximum(jnp.sum(mask_pos), 1)


def bert4rec_forward(params, batch, cfg: BERT4RecConfig) -> jax.Array:
    """Serving: score the candidate item for the next position."""
    h = bert4rec_encode(params, batch["uih_item_id"], batch["uih_mask"], cfg)
    user_repr = h[:, -1]                                      # (B, D)
    cand = _lookup(params["item_table"], batch["cand_item_id"], cfg, h.dtype)
    return jnp.sum(user_repr * cand, axis=-1)


# ===========================================================================
# DLRM-UIH — the paper's flagship long-sequence ranking model
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class DLRMUIHConfig:
    name: str = "dlrm-uih"
    seq_len: int = 2048
    d_seq: int = 128              # sequence-encoder width
    n_seq_layers: int = 2
    n_heads: int = 4
    n_dense: int = 13
    n_sparse: int = 4
    embed_dim: int = 64           # sparse field embedding dim
    item_vocab: int = 10_000_000
    field_vocab: int = 1_000_000
    top_mlp: Tuple[int, ...] = (512, 256)
    compute_dtype: Any = jnp.bfloat16
    mesh: Any = None
    data_axes: Tuple[str, ...] = ("data",)
    remat: bool = True
    unroll_scans: bool = False
    q_chunk: int = 512

    def param_count(self) -> int:
        return _count(self, init_dlrm_uih)


def init_dlrm_uih(key, cfg: DLRMUIHConfig) -> Params:
    ks = jax.random.split(key, 8)
    d = cfg.d_seq
    attn_cfg = L.AttnConfig(d_model=d, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_heads, head_dim=d // cfg.n_heads,
                            rope_theta=1e4, q_chunk=512)

    def block_init(k):
        k1, k2 = jax.random.split(k)
        return {
            "attn": L.init_gqa(k1, attn_cfg),
            "ffn": L.init_swiglu(k2, d, 4 * d),
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
        }

    n_inter = 3 + cfg.n_sparse   # user_seq, target, dense_proj + sparse fields
    d_pairs = n_inter * (n_inter - 1) // 2
    return {
        "item_table": init_table(ks[0], cfg.item_vocab, d),
        "action_table": init_table(ks[1], 16, d),
        "sparse_tables": init_table(ks[2], cfg.n_sparse * cfg.field_vocab,
                                    cfg.embed_dim),
        "dense_proj": mlp_init(ks[3], [cfg.n_dense, cfg.embed_dim]),
        "seq_blocks": jax.vmap(block_init)(
            jax.random.split(ks[4], cfg.n_seq_layers)
        ),
        "seq_ln": jnp.ones((d,), jnp.float32),
        "seq_proj": mlp_init(ks[5], [d, cfg.embed_dim]),
        "target_proj": mlp_init(ks[6], [d, cfg.embed_dim]),
        "top_mlp": mlp_init(ks[7], [d_pairs + cfg.embed_dim, *cfg.top_mlp, 1]),
    }


def dlrm_uih_forward(params, batch, cfg: DLRMUIHConfig) -> jax.Array:
    dt = cfg.compute_dtype
    b, s = batch["uih_item_id"].shape
    attn_cfg = L.AttnConfig(d_model=cfg.d_seq, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_heads,
                            head_dim=cfg.d_seq // cfg.n_heads,
                            rope_theta=1e4, q_chunk=cfg.q_chunk,
                            unroll=cfg.unroll_scans,
                            scores_f32=(cfg.mesh is None))
    # --- UIH sequence encoder (causal, target-aware last token) ---
    with jax.named_scope("embed"):
        e = (_seq_lookup(params["item_table"], batch["uih_item_id"], cfg, dt)
             + _replicated_lookup(params["action_table"],
                                  batch["uih_action_type"], cfg, dt))
    e = _shard_batch_all(e, cfg)
    mask = _shard_batch_all(batch["uih_mask"], cfg)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def body(h, block):
        hn = L.rms_norm(h, block["ln1"])
        h = h + L.gqa_attention(block["attn"], hn, positions, attn_cfg,
                                causal=True, kv_mask=mask)
        hn = L.rms_norm(h, block["ln2"])
        return h + L.swiglu(block["ffn"], hn), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    with jax.named_scope("encoder"):
        h, _ = jax.lax.scan(body_fn, e, params["seq_blocks"],
                            unroll=cfg.unroll_scans)
        h = L.rms_norm(h, params["seq_ln"])

    # target-aware pooling: attention of the candidate over history (DIN-style)
    tgt = _lookup(params["item_table"], batch["cand_item_id"], cfg, dt)  # (B, D)
    att = jnp.einsum("bsd,bd->bs", h, _shard_batch_all(tgt, cfg),
                     preferred_element_type=jnp.float32)
    att = jax.nn.softmax(
        jnp.where(mask, att / np.sqrt(cfg.d_seq), -1e30), axis=-1
    ).astype(dt)
    user_seq = _shard_batch_data(jnp.einsum("bs,bsd->bd", att, h),
                                 cfg)                                  # (B, D)

    # --- DLRM-style feature interaction ---
    offsets = jnp.arange(cfg.n_sparse) * cfg.field_vocab
    sparse = _seq_lookup(params["sparse_tables"],
                         batch["sparse_ids"] + offsets, cfg, dt)
    dense = _shard_batch_data(
        mlp_apply(params["dense_proj"], batch["dense"].astype(dt), 1), cfg)
    feats = jnp.stack(
        [
            mlp_apply(params["seq_proj"], user_seq, 1),
            mlp_apply(params["target_proj"], tgt, 1),
            dense,
        ]
        + [sparse[:, i] for i in range(cfg.n_sparse)],
        axis=1,
    )                                                                  # (B, F, D)
    inter = jnp.einsum("bfd,bgd->bfg", feats, feats)
    iu, ju = jnp.triu_indices(feats.shape[1], k=1)
    pairs = inter[:, iu, ju]                                           # (B, F*(F-1)/2)
    z = jnp.concatenate([pairs, dense], axis=-1)
    return mlp_apply(params["top_mlp"], z, len(cfg.top_mlp) + 1)[:, 0]


def dlrm_uih_loss(params, batch, cfg) -> jax.Array:
    return bce_with_logits(dlrm_uih_forward(params, batch, cfg), batch["label"])


# ===========================================================================
# retrieval_cand paths: 1 query scored against N candidates (no python loops)
# ===========================================================================

def bert4rec_score_candidates(params, batch, cand_ids, cfg) -> jax.Array:
    h = bert4rec_encode(params, batch["uih_item_id"], batch["uih_mask"], cfg)
    user_repr = h[:, -1]                                       # (1, D)
    cand = params["item_table"].astype(h.dtype)[cand_ids]      # (N, D)
    return user_repr @ cand.T                                  # (1, N)


def dcn_v2_score_candidates(params, batch, cand_ids, cfg: DCNv2Config) -> jax.Array:
    """Offline bulk scoring: broadcast the user context across N candidates;
    sparse field 0 is the candidate item."""
    n = cand_ids.shape[0]
    sparse = jnp.broadcast_to(batch["sparse_ids"], (n, cfg.n_sparse))
    sparse = sparse.at[:, 0].set(cand_ids)
    dense = jnp.broadcast_to(batch["dense"], (n, cfg.n_dense))
    return dcn_v2_forward(params, {"sparse_ids": sparse, "dense": dense}, cfg)


def dien_score_candidates(params, batch, cand_ids, cand_cats,
                          cfg: DIENConfig) -> jax.Array:
    """GRU-1 interest extraction runs ONCE; target-aware attention + AUGRU run
    batched over the N candidates."""
    dt = cfg.compute_dtype
    ids, cats = batch["uih_item_id"], batch["uih_category"]    # (1, S)
    mask = batch["uih_mask"].astype(dt)
    e = jnp.concatenate(
        [_seq_lookup(params["item_table"], ids, cfg, dt),
         _seq_lookup(params["cat_table"], cats, cfg, dt)],
        axis=-1,
    )                                                          # (1, S, 2D)
    h0 = jnp.zeros((1, cfg.gru_dim), dt)

    def step1(h, inp):
        x, mk = inp
        h_new = _gru_cell(params["gru1"], h, x)
        h = jnp.where(mk[:, None] > 0, h_new, h)
        return h, h

    with jax.named_scope("encoder"):
        _, interests = jax.lax.scan(step1, h0,
                                    (e.transpose(1, 0, 2), mask.T),
                                    unroll=cfg.unroll_scans)
    interests = interests[:, 0]                                # (S, H)

    n = cand_ids.shape[0]
    tgt = jnp.concatenate(
        [params["item_table"].astype(dt)[cand_ids],
         params["cat_table"].astype(dt)[cand_cats]], axis=-1,
    )                                                          # (N, 2D)
    att_logits = jnp.einsum("sh,hd,nd->ns", interests,
                            params["att_w"].astype(dt), tgt,
                            preferred_element_type=jnp.float32)
    att = jax.nn.softmax(
        jnp.where(mask[0][None, :] > 0, att_logits, -1e30), axis=-1
    ).astype(dt)                                               # (N, S)

    hn0 = jnp.zeros((n, cfg.gru_dim), dt)

    def step2(h, inp):
        x, a, mk = inp                                         # (H,), (N,), ()
        xb = jnp.broadcast_to(x[None, :], (n, cfg.gru_dim))
        h_new = _gru_cell(params["augru"], h, xb, a[:, None])
        return jnp.where(mk > 0, h_new, h), None

    final, _ = jax.lax.scan(step2, hn0, (interests, att.T, mask[0]),
                            unroll=cfg.unroll_scans)
    hist_sum = jnp.sum(e[0] * mask[0][:, None], axis=0)        # (2D,)
    z = jnp.concatenate(
        [final, tgt, jnp.broadcast_to(hist_sum[None, :], (n, tgt.shape[1]))],
        axis=-1,
    )
    return mlp_apply(params["mlp"], z, len(cfg.mlp) + 1)[:, 0]


def dlrm_uih_score_candidates(params, batch, cand_ids,
                              cfg: DLRMUIHConfig) -> jax.Array:
    """Sequence encoder runs ONCE; target-aware pooling + interaction + top
    MLP run batched over N candidates."""
    dt = cfg.compute_dtype
    b, s = batch["uih_item_id"].shape
    assert b == 1
    attn_cfg = L.AttnConfig(d_model=cfg.d_seq, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_heads,
                            head_dim=cfg.d_seq // cfg.n_heads,
                            rope_theta=1e4, q_chunk=cfg.q_chunk,
                            unroll=cfg.unroll_scans)
    e = (_seq_lookup(params["item_table"], batch["uih_item_id"], cfg, dt)
         + params["action_table"].astype(dt)[batch["uih_action_type"]])
    mask = batch["uih_mask"]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (1, s))

    def body(h, block):
        hn = L.rms_norm(h, block["ln1"])
        h = h + L.gqa_attention(block["attn"], hn, positions, attn_cfg,
                                causal=True, kv_mask=mask)
        hn = L.rms_norm(h, block["ln2"])
        return h + L.swiglu(block["ffn"], hn), None

    h, _ = jax.lax.scan(body, e, params["seq_blocks"], unroll=cfg.unroll_scans)
    h = L.rms_norm(h, params["seq_ln"])[0]                     # (S, D)

    n = cand_ids.shape[0]
    tgt = _lookup(params["item_table"], cand_ids, cfg, dt)     # (N, D)
    att = jnp.einsum("sd,nd->ns", h, tgt, preferred_element_type=jnp.float32)
    att = jax.nn.softmax(
        jnp.where(mask[0][None, :], att / np.sqrt(cfg.d_seq), -1e30), axis=-1
    ).astype(dt)
    user_seq = att @ h                                         # (N, D)

    offsets = jnp.arange(cfg.n_sparse) * cfg.field_vocab
    sparse = _seq_lookup(params["sparse_tables"],
                         batch["sparse_ids"] + offsets, cfg, dt)
    sparse = jnp.broadcast_to(sparse, (n, cfg.n_sparse, cfg.embed_dim))
    dense = mlp_apply(params["dense_proj"], batch["dense"].astype(dt), 1)
    dense = jnp.broadcast_to(dense, (n, cfg.embed_dim))
    feats = jnp.stack(
        [
            mlp_apply(params["seq_proj"], user_seq, 1),
            mlp_apply(params["target_proj"], tgt, 1),
            dense,
        ]
        + [sparse[:, i] for i in range(cfg.n_sparse)],
        axis=1,
    )
    inter = jnp.einsum("nfd,ngd->nfg", feats, feats)
    iu, ju = jnp.triu_indices(feats.shape[1], k=1)
    pairs = inter[:, iu, ju]
    z = jnp.concatenate([pairs, dense], axis=-1)
    return mlp_apply(params["top_mlp"], z, len(cfg.top_mlp) + 1)[:, 0]

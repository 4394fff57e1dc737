"""Cell builders: (arch × shape × mesh) -> step function + input specs +
shardings. ``input_specs()`` returns ShapeDtypeStructs only — the dry-run
never allocates full-size arrays.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchSpec
from repro.launch import shardings as SH
from repro.launch.mesh import all_axes_of, data_axes_of
from repro.models import gnn as G
from repro.models import recsys as R
from repro.models import transformer as T
from repro.train.optimizer import AdamWConfig, AdamWState, adamw_init, make_train_step

S = jax.ShapeDtypeStruct


@dataclasses.dataclass
class Cell:
    arch_id: str
    shape_name: str
    kind: str
    step_fn: Callable          # positional args
    args_spec: Tuple[Any, ...] # ShapeDtypeStruct pytrees (positional)
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    model_flops: float         # 6ND-style useful flops for this step
    meta: Dict[str, Any]


def _div(b: int, axes_size: int) -> bool:
    return b % axes_size == 0 and b >= axes_size


def _batch_axes(mesh, b: int):
    da = data_axes_of(mesh)
    size = int(np.prod([mesh.shape[a] for a in da]))
    return (da if _div(b, size) else None), da


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def _lm_params_spec(cfg, mesh, serving: bool = False, moe_2d: bool = False):
    pshape = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))
    if serving:  # inference holds bf16 weights (no fp32 master needed)
        pshape = jax.tree.map(
            lambda l: S(l.shape, jnp.bfloat16)
            if jnp.issubdtype(l.dtype, jnp.floating) else l, pshape)
    return pshape, SH.lm_param_specs(pshape, mesh, moe_2d=moe_2d)


def build_lm_cell(spec: ArchSpec, shape_name: str, mesh,
                  use_full: bool = True, cfg_override=None) -> Cell:
    cfg = cfg_override or (spec.full if use_full else spec.smoke)
    shp = spec.shapes[shape_name]
    b, sl = shp["batch"], shp["seq_len"]
    if not use_full:  # smoke: shrink shapes
        b, sl = max(2, b // 128), min(sl, 64)
    da = data_axes_of(mesh)
    b_axes, _ = _batch_axes(mesh, b)
    moe_data_axes = b_axes if (cfg.moe is not None and shp["kind"] == "decode") \
        else (da if cfg.moe is not None else da)
    if cfg.moe is not None and shp["kind"] == "decode" and b_axes is None:
        moe_data_axes = ()
    kind = shp["kind"]
    # decode: fully-resident 2D expert sharding (no per-step FSDP all-gather)
    if cfg.moe is not None and kind == "decode":
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, ep_mode="2d"))
    pshape, pspec = _lm_params_spec(
        cfg, mesh, serving=(kind != "train"),
        moe_2d=(cfg.moe is not None and cfg.moe.ep_mode == "2d"))
    n_params = cfg.active_param_count()

    if kind == "train":
        opt_cfg = AdamWConfig()
        oshape = jax.eval_shape(lambda: adamw_init(
            jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), pshape)))
        ospec = SH.opt_specs(pspec, pshape, mesh)
        loss = lambda p, batch: T.loss_fn(p, batch["tokens"], batch["targets"],
                                          cfg, mesh=mesh, data_axes=da)
        step = make_train_step(loss, opt_cfg)
        batch_spec = {
            "tokens": S((b, sl), jnp.int32),
            "targets": S((b, sl), jnp.int32),
        }
        batch_sh = {
            "tokens": P(b_axes, None),
            "targets": P(b_axes, None),
        }
        return Cell(
            spec.arch_id, shape_name, kind, step,
            (pshape, oshape, batch_spec),
            (pspec, ospec, batch_sh),
            (pspec, ospec, P()),
            model_flops=6.0 * n_params * b * sl,
            meta={"tokens": b * sl, "cfg": cfg},
        )

    if kind == "prefill":
        fn = lambda p, batch: T.prefill(p, batch["tokens"], cfg, mesh=mesh,
                                        data_axes=da)
        batch_spec = {"tokens": S((b, sl), jnp.int32)}
        batch_sh = {"tokens": P(b_axes, None)}
        cache_sh = _kv_cache_spec(cfg, mesh, b, sl, stacked=True)[1]
        return Cell(
            spec.arch_id, shape_name, kind, fn,
            (pshape, batch_spec), (pspec, batch_sh),
            (P(b_axes, "model"), cache_sh),
            model_flops=2.0 * n_params * b * sl,
            meta={"tokens": b * sl, "cfg": cfg},
        )

    # decode
    cache_shape, cache_sh = _kv_cache_spec(cfg, mesh, b, sl, stacked=True)
    fn = lambda p, cache, batch: T.decode_step(
        p, cache, batch["token"], batch["position"], cfg, mesh=mesh,
        data_axes=moe_data_axes)
    batch_spec = {
        "token": S((b,), jnp.int32),
        "position": S((b,), jnp.int32),
    }
    batch_sh = {"token": P(b_axes), "position": P(b_axes)}
    return Cell(
        spec.arch_id, shape_name, kind, fn,
        (pshape, cache_shape, batch_spec),
        (pspec, cache_sh, batch_sh),
        (P(b_axes, "model"), cache_sh),
        model_flops=2.0 * n_params * b,   # + attention KV term reported in meta
        meta={"tokens": b, "kv_len": sl, "cfg": cfg},
    )


def _kv_cache_spec(cfg, mesh, b: int, sl: int, stacked: bool):
    da = data_axes_of(mesh)
    size_da = int(np.prod([mesh.shape[a] for a in da]))
    if _div(b, size_da):
        b_ax, s_ax = da, "model"
    else:
        # batch too small: flash-decoding style sequence sharding over all axes
        b_ax, s_ax = None, tuple(all_axes_of(mesh))
    dt = cfg.compute_dtype
    if cfg.attention == "mla":
        shape = {
            "c_kv": S((cfg.n_layers, b, sl, cfg.kv_lora_rank), dt),
            "k_pe": S((cfg.n_layers, b, sl, cfg.qk_rope_dim), dt),
        }
        sh = {
            "c_kv": P(None, b_ax, s_ax, None),
            "k_pe": P(None, b_ax, s_ax, None),
        }
    else:
        shape = {
            "k": S((cfg.n_layers, b, sl, cfg.n_kv_heads, cfg.head_dim), dt),
            "v": S((cfg.n_layers, b, sl, cfg.n_kv_heads, cfg.head_dim), dt),
        }
        sh = {
            "k": P(None, b_ax, s_ax, None, None),
            "v": P(None, b_ax, s_ax, None, None),
        }
    return shape, sh


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------

def build_gnn_cell(spec: ArchSpec, shape_name: str, mesh,
                   use_full: bool = True, cfg_override=None) -> Cell:
    base_cfg = cfg_override or (spec.full if use_full else spec.smoke)
    shp = spec.shapes[shape_name]
    n, e, d_feat = shp["n_nodes"], shp["n_edges"], shp["d_feat"]
    if not use_full:
        n, e, d_feat = min(n, 64), min(e, 256), min(d_feat, 8)
    cfg = dataclasses.replace(base_cfg, d_node_in=d_feat)
    # pad edges to a multiple of the full device count for clean sharding
    ndev = int(np.prod(list(mesh.shape.values())))
    e_pad = int(np.ceil(e / ndev) * ndev)
    axes = tuple(all_axes_of(mesh))

    pshape = jax.eval_shape(lambda: G.init(jax.random.PRNGKey(0), cfg))
    pspec = SH.gnn_param_specs(pshape, mesh)
    opt_cfg = AdamWConfig()
    oshape = jax.eval_shape(lambda: adamw_init(
        jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), pshape)))
    ospec = SH.opt_specs(pspec, pshape, mesh)

    def loss(p, batch):
        return G.loss_fn(p, batch["node_feats"], batch["edge_feats"],
                         batch["senders"], batch["receivers"],
                         batch["targets"], cfg, edge_mask=batch["edge_mask"])

    step = make_train_step(loss, opt_cfg)
    batch_spec = {
        "node_feats": S((n, d_feat), jnp.float32),
        "edge_feats": S((e_pad, cfg.d_edge_in), jnp.float32),
        "senders": S((e_pad,), jnp.int32),
        "receivers": S((e_pad,), jnp.int32),
        "edge_mask": S((e_pad,), jnp.bool_),
        "targets": S((n, cfg.d_out), jnp.float32),
    }
    batch_sh = {
        "node_feats": P(None, None),          # replicated (vertex-cut)
        "edge_feats": P(axes, None),
        "senders": P(axes),
        "receivers": P(axes),
        "edge_mask": P(axes),
        "targets": P(None, None),
    }
    # flops: per MP layer ~ edges * (3h->h MLP) + nodes * (2h->h MLP)
    h = cfg.d_hidden
    mp = cfg.n_layers * (e * (3 * h * h + h * h) + n * (2 * h * h + h * h)) * 2
    enc = (n * d_feat * h + e * cfg.d_edge_in * h + n * h * cfg.d_out) * 2
    return Cell(
        spec.arch_id, shape_name, "train", step,
        (pshape, oshape, batch_spec),
        (pspec, ospec, batch_sh),
        (pspec, ospec, P()),
        model_flops=3.0 * (mp + enc),        # fwd + bwd ~ 3x fwd
        meta={"n_nodes": n, "n_edges": e, "cfg": cfg},
    )


# ---------------------------------------------------------------------------
# RecSys cells
# ---------------------------------------------------------------------------

def _recsys_batch(arch_id: str, cfg, b: int, mesh, with_label: bool):
    """(spec, shardings) for one batch of each recsys tenant's features."""
    b_axes, _ = _batch_axes(mesh, b)
    bp = lambda *rest: P(b_axes, *rest)
    if arch_id == "two-tower-retrieval":
        spec = {
            "user_id": S((b,), jnp.int32),
            "uih_item_id": S((b, cfg.uih_len), jnp.int32),
            "uih_mask": S((b, cfg.uih_len), jnp.bool_),
            "cand_item_id": S((b,), jnp.int32),
        }
        sh = {
            "user_id": bp(), "uih_item_id": bp(None), "uih_mask": bp(None),
            "cand_item_id": bp(),
        }
        if with_label:
            spec["log_q"] = S((b,), jnp.float32)
            sh["log_q"] = bp()
    elif arch_id == "dcn-v2":
        spec = {
            "dense": S((b, cfg.n_dense), jnp.float32),
            "sparse_ids": S((b, cfg.n_sparse), jnp.int32),
        }
        sh = {"dense": bp(None), "sparse_ids": bp(None)}
    elif arch_id == "dien":
        spec = {
            "uih_item_id": S((b, cfg.seq_len), jnp.int32),
            "uih_category": S((b, cfg.seq_len), jnp.int32),
            "uih_mask": S((b, cfg.seq_len), jnp.bool_),
            "cand_item_id": S((b,), jnp.int32),
            "cand_category": S((b,), jnp.int32),
        }
        sh = {
            "uih_item_id": bp(None), "uih_category": bp(None),
            "uih_mask": bp(None), "cand_item_id": bp(), "cand_category": bp(),
        }
    elif arch_id == "bert4rec":
        spec = {
            "uih_item_id": S((b, cfg.seq_len), jnp.int32),
            "uih_mask": S((b, cfg.seq_len), jnp.bool_),
        }
        sh = {"uih_item_id": bp(None), "uih_mask": bp(None)}
        if with_label:
            spec["mask_pos"] = S((b, cfg.seq_len), jnp.bool_)
            sh["mask_pos"] = bp(None)
            spec["neg_ids"] = S((1024,), jnp.int32)
            sh["neg_ids"] = P(None)
        else:
            spec["cand_item_id"] = S((b,), jnp.int32)
            sh["cand_item_id"] = bp()
    elif arch_id == "dlrm-uih":
        spec = {
            "uih_item_id": S((b, cfg.seq_len), jnp.int32),
            "uih_action_type": S((b, cfg.seq_len), jnp.int32),
            "uih_mask": S((b, cfg.seq_len), jnp.bool_),
            "cand_item_id": S((b,), jnp.int32),
            "sparse_ids": S((b, cfg.n_sparse), jnp.int32),
            "dense": S((b, cfg.n_dense), jnp.float32),
        }
        sh = {
            "uih_item_id": bp(None), "uih_action_type": bp(None),
            "uih_mask": bp(None), "cand_item_id": bp(),
            "sparse_ids": bp(None), "dense": bp(None),
        }
    else:
        raise KeyError(arch_id)
    if with_label and arch_id not in ("two-tower-retrieval", "bert4rec"):
        spec["label"] = S((b,), jnp.float32)
        sh["label"] = bp()
    return spec, sh


_RECSYS_FNS = {
    "two-tower-retrieval": (R.init_two_tower, R.two_tower_loss, None,
                            R.two_tower_score_candidates),
    "dcn-v2": (R.init_dcn_v2, R.dcn_v2_loss, R.dcn_v2_forward,
               R.dcn_v2_score_candidates),
    "dien": (R.init_dien, R.dien_loss, R.dien_forward, None),
    "bert4rec": (R.init_bert4rec, R.bert4rec_loss, R.bert4rec_forward,
                 R.bert4rec_score_candidates),
    "dlrm-uih": (R.init_dlrm_uih, R.dlrm_uih_loss, R.dlrm_uih_forward,
                 R.dlrm_uih_score_candidates),
}


def _two_tower_towers(cfg):
    d = cfg.embed_dim
    user = 2 * d * cfg.tower_mlp[0] + sum(
        cfg.tower_mlp[i] * cfg.tower_mlp[i + 1]
        for i in range(len(cfg.tower_mlp) - 1))
    item = d * cfg.tower_mlp[0] + sum(
        cfg.tower_mlp[i] * cfg.tower_mlp[i + 1]
        for i in range(len(cfg.tower_mlp) - 1))
    return user, item


def _recsys_flops(arch_id: str, cfg, b: int) -> float:
    """Per-step useful forward flops (dense-equivalent), x3 for training."""
    if arch_id == "two-tower-retrieval":
        d = cfg.embed_dim
        user, item = _two_tower_towers(cfg)
        return 2.0 * b * (user + item + cfg.uih_len * d) + 2.0 * b * b * d
    if arch_id == "dcn-v2":
        d = cfg.d_interact
        mlp = d * cfg.mlp[0] + sum(cfg.mlp[i] * cfg.mlp[i + 1]
                                   for i in range(len(cfg.mlp) - 1))
        return 2.0 * b * (cfg.n_cross_layers * d * d + mlp)
    if arch_id == "dien":
        per_step = 2 * (cfg.d_in * 3 * cfg.gru_dim + cfg.gru_dim * 3 * cfg.gru_dim)
        return 2.0 * b * cfg.seq_len * per_step
    if arch_id == "bert4rec":
        d = cfg.embed_dim
        per_tok = 12 * d * d + 2 * cfg.seq_len * d  # attn+ffn+scores
        return 2.0 * b * cfg.seq_len * cfg.n_blocks * per_tok
    if arch_id == "dlrm-uih":
        d = cfg.d_seq
        per_tok = 12 * d * d + 2 * cfg.seq_len * d
        return 2.0 * b * cfg.seq_len * cfg.n_seq_layers * per_tok
    raise KeyError(arch_id)


def build_recsys_cell(spec: ArchSpec, shape_name: str, mesh,
                      use_full: bool = True, cfg_override=None) -> Cell:
    cfg = cfg_override or (spec.full if use_full else spec.smoke)
    shp = spec.shapes[shape_name]
    b = shp["batch"]
    n_cand = shp.get("n_candidates", 0)
    if not use_full:
        b = max(2, min(b, 8))
        n_cand = min(n_cand, 64)
    init_fn, loss_fn, fwd_fn, score_fn = _RECSYS_FNS[spec.arch_id]
    kind = shp["kind"]
    # train/serve cells use the shard_map row-sharded embedding path;
    # retrieval cells keep the GSPMD path (candidate ids shard over all axes)
    if kind in ("train", "serve") and use_full:
        cfg = dataclasses.replace(cfg, mesh=mesh,
                                  data_axes=data_axes_of(mesh))
    pshape = jax.eval_shape(lambda: init_fn(jax.random.PRNGKey(0), cfg))
    if kind != "train":  # serving holds bf16 weights
        pshape = jax.tree.map(
            lambda l: S(l.shape, jnp.bfloat16)
            if jnp.issubdtype(l.dtype, jnp.floating) else l, pshape)
    pspec = SH.recsys_param_specs(pshape, mesh)
    axes = tuple(all_axes_of(mesh))
    fwd_flops = _recsys_flops(spec.arch_id, cfg, b)

    if kind == "train":
        opt_cfg = AdamWConfig()
        oshape = jax.eval_shape(lambda: adamw_init(
            jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), pshape)))
        ospec = SH.opt_specs(pspec, pshape, mesh)
        batch_spec, batch_sh = _recsys_batch(spec.arch_id, cfg, b, mesh, True)
        step = make_train_step(lambda p, batch: loss_fn(p, batch, cfg),
                               AdamWConfig())
        return Cell(
            spec.arch_id, shape_name, kind, step,
            (pshape, oshape, batch_spec),
            (pspec, ospec, batch_sh),
            (pspec, ospec, P()),
            model_flops=3.0 * fwd_flops,
            meta={"batch": b, "cfg": cfg},
        )

    if kind == "serve":
        batch_spec, batch_sh = _recsys_batch(spec.arch_id, cfg, b, mesh, False)
        if spec.arch_id == "two-tower-retrieval":
            fn = lambda p, batch: R.two_tower_user(
                p, batch["user_id"], batch["uih_item_id"], batch["uih_mask"], cfg)
            b_axes, _ = _batch_axes(mesh, b)
            out_sh = P(b_axes, None)
            user, _ = _two_tower_towers(cfg)
            fwd_flops = 2.0 * b * (user + cfg.uih_len * cfg.embed_dim)
        else:
            fn = lambda p, batch: fwd_fn(p, batch, cfg)
            b_axes, _ = _batch_axes(mesh, b)
            out_sh = P(b_axes)
        return Cell(
            spec.arch_id, shape_name, kind, fn,
            (pshape, batch_spec), (pspec, batch_sh), out_sh,
            model_flops=fwd_flops,
            meta={"batch": b, "cfg": cfg},
        )

    # retrieval_cand
    batch_spec, batch_sh = _recsys_batch(spec.arch_id, cfg, 1, mesh, False)
    ndev = int(np.prod(list(mesh.shape.values())))
    n_cand = int(np.ceil(n_cand / ndev) * ndev)   # pad to shard boundary
    cand_spec = S((n_cand,), jnp.int32)
    cand_sh = P(axes)
    if spec.arch_id == "dien":
        fn = lambda p, batch, cand, cand_cat: R.dien_score_candidates(
            p, batch, cand, cand_cat, cfg)
        args = (pshape, batch_spec, cand_spec, S((n_cand,), jnp.int32))
        in_sh = (pspec, batch_sh, cand_sh, cand_sh)
    else:
        fn = lambda p, batch, cand: score_fn(p, batch, cand, cfg)
        args = (pshape, batch_spec, cand_spec)
        in_sh = (pspec, batch_sh, cand_sh)
    return Cell(
        spec.arch_id, shape_name, kind, fn,
        args, in_sh, P(axes) if spec.arch_id in ("dcn-v2", "dien", "dlrm-uih")
        else P(None, axes),
        model_flops=_retrieval_flops(spec.arch_id, cfg, n_cand),
        meta={"n_candidates": n_cand, "cfg": cfg},
    )


def sharded_init(cell: Cell, mesh) -> Callable:
    """Jitted ``seed -> params`` for a recsys ``train`` cell, each param
    laid out by the cell's sharding on an Auto-axes copy of ``mesh``.

    Under Auto axes the partitioner has each device draw only its rows of a
    row-sharded table; traced under Explicit axes (the training mesh set
    with ``jax.set_mesh``, or a key committed to it) every device draws the
    whole table and then slices it: for dlrm-uih's 10,000,384-row item
    table compiled for a v5e:2x2, 16.6 GB of temporaries per chip, more
    than its HBM. So the init takes a plain int seed and traces under the
    Auto mesh whatever mesh is current."""
    init_fn = _RECSYS_FNS[cell.arch_id][0]
    cfg = cell.meta["cfg"]
    auto = jax.sharding.Mesh(
        mesh.devices, mesh.axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))

    def init(seed):
        with jax.sharding.use_abstract_mesh(auto.abstract_mesh):
            return init_fn(jax.random.PRNGKey(seed), cfg)

    return jax.jit(init, in_shardings=jax.sharding.NamedSharding(auto, P()),
                   out_shardings=SH.named(auto, cell.in_shardings[0]))


def init_train_state(cell: Cell, mesh, seed: int) -> Tuple[Any, AdamWState]:
    """Random params and fresh AdamW state for a recsys ``train`` cell,
    placed by the cell's shardings on ``mesh``: the arguments
    ``jax.jit(cell.step_fn, in_shardings=cell.in_shardings)`` takes."""
    pspec, ospec, _ = cell.in_shardings
    params = jax.device_put(sharded_init(cell, mesh)(seed),
                            SH.named(mesh, pspec))
    opt = jax.jit(adamw_init, out_shardings=SH.named(mesh, ospec))(params)
    return params, opt


def _retrieval_flops(arch_id: str, cfg, n: int) -> float:
    """Shared encoders run ONCE; only the per-candidate tail scales with N."""
    if arch_id == "two-tower-retrieval":
        user, item = _two_tower_towers(cfg)
        return 2.0 * (user + cfg.uih_len * cfg.embed_dim) \
            + 2.0 * n * (item + cfg.embed_dim)
    if arch_id == "dcn-v2":
        return _recsys_flops(arch_id, cfg, n)    # full forward per candidate
    if arch_id == "dien":
        h, s = cfg.gru_dim, cfg.seq_len
        gru1_once = 2.0 * s * (cfg.d_in * 3 * h + h * 3 * h)
        per_cand = 2.0 * s * (h * 3 * h + h * 3 * h) \
            + 2.0 * s * h + 2.0 * (h + 2 * cfg.d_in) * cfg.mlp[0]
        return gru1_once + n * per_cand
    if arch_id == "bert4rec":
        d = cfg.embed_dim
        enc_once = 2.0 * cfg.seq_len * cfg.n_blocks * (12 * d * d
                                                       + 4 * cfg.seq_len * d)
        return enc_once + 2.0 * n * d
    if arch_id == "dlrm-uih":
        d = cfg.d_seq
        enc_once = 2.0 * cfg.seq_len * cfg.n_seq_layers * (12 * d * d
                                                           + 4 * cfg.seq_len * d)
        f = 3 + cfg.n_sparse
        pairs = f * (f - 1) // 2
        per_cand = (2.0 * cfg.seq_len * d                 # target-aware pooling
                    + 2.0 * 3 * d * cfg.embed_dim         # projections
                    + 2.0 * f * f * cfg.embed_dim         # interactions
                    + 2.0 * ((pairs + cfg.embed_dim) * cfg.top_mlp[0]
                             + cfg.top_mlp[0] * cfg.top_mlp[1]))
        return enc_once + n * per_cand
    raise KeyError(arch_id)


# ---------------------------------------------------------------------------
# Device feed: host data plane -> sharded device batches
#
# DEPRECATED SHIMS. The declarative read path (repro.data) replaced both of
# these: describe the feed as a DatasetSpec and call
# ``repro.data.open_feed(spec, sim, cell=cell, mesh=mesh, prep_fn=...)``.
# The shims keep old call sites working — same arguments, same behavior —
# but now return the uniform ``repro.data.Feed`` protocol (which iterates,
# ``get``s, and records train steps exactly like the DevicePrefetcher they
# used to return) and emit a DeprecationWarning.
# ---------------------------------------------------------------------------

def make_device_feed(cell: Cell, source, mesh=None, depth: int = 2,
                     prep_fn=None, stats=None, recycle_host: bool = False):
    """DEPRECATED: use ``repro.data.open_feed`` (this is a thin shim).

    Double-buffered device feed for a cell's input batches: wraps a
    host-batch source (a ``RebatchingClient``, or any iterable of host batch
    dicts) in a ``DevicePrefetcher`` whose ``device_put`` honors the cell's
    batch shardings, returned behind the uniform ``Feed`` protocol.
    """
    import warnings

    warnings.warn(
        "launch.steps.make_device_feed is deprecated; build a "
        "repro.data.DatasetSpec and call repro.data.open_feed(...) instead",
        DeprecationWarning, stacklevel=2)
    return _shim_feed(cell, source, mesh, depth, prep_fn, stats, recycle_host)


def make_streaming_feed(cell: Cell, session, mesh=None, depth: int = 2,
                        prep_fn=None, recycle_host: bool = False):
    """DEPRECATED: use ``repro.data.open_feed`` with a ``StreamSource`` spec
    (this is a thin shim).

    Wraps a ``repro.streaming.StreamingSession`` in the cell-sharded device
    prefetcher behind the uniform ``Feed`` protocol: H2D overlaps the step
    exactly as in batch mode while the session settles event→gradient
    freshness and releases generation leases. ``session.start()`` is implicit
    on first pull."""
    import warnings

    warnings.warn(
        "launch.steps.make_streaming_feed is deprecated; build a "
        "repro.data.DatasetSpec(source=StreamSource(...)) and call "
        "repro.data.open_feed(...) instead",
        DeprecationWarning, stacklevel=2)
    return _shim_feed(cell, session, mesh, depth, prep_fn, None, recycle_host)


def _shim_feed(cell, source, mesh, depth, prep_fn, stats, recycle_host):
    from repro.data.compile import cell_input_sharding
    from repro.data.feed import Feed
    from repro.dpp.prefetch import DevicePrefetcher
    from repro.streaming.session import StreamingSession

    sharding = cell_input_sharding(cell, mesh)
    pf = DevicePrefetcher(source, depth=depth, sharding=sharding,
                          prep_fn=prep_fn, stats=stats,
                          recycle_host=recycle_host)
    session = source if isinstance(source, StreamingSession) else None
    client = source if (session is None and hasattr(source, "recycle")
                       and hasattr(source, "get_full_batch")) else None
    return Feed(pf, client=client, session=session, prefetcher=pf,
                prep_fn=prep_fn)


def build_cell(spec: ArchSpec, shape_name: str, mesh, use_full=True,
               cfg_override=None) -> Cell:
    if spec.family == "lm":
        return build_lm_cell(spec, shape_name, mesh, use_full, cfg_override)
    if spec.family == "gnn":
        return build_gnn_cell(spec, shape_name, mesh, use_full, cfg_override)
    if spec.family == "recsys":
        return build_recsys_cell(spec, shape_name, mesh, use_full, cfg_override)
    raise KeyError(spec.family)

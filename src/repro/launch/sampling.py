"""Materialize runnable inputs for a Cell (smoke tests / e2e examples).

The dry-run itself never calls this — it lowers from ShapeDtypeStructs. Smoke
tests execute reduced cells on CPU with inputs sampled here (ids bounded by the
config's vocabularies, masks non-degenerate, floats standard-normal)."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.steps import Cell
from repro.models import gnn as G
from repro.models import hstu as H
from repro.models import recsys as R
from repro.models import transformer as T
from repro.train.optimizer import adamw_init

_INIT_FNS = {
    "lm": T.init,
    "gnn": G.init,
}


def _vocab_for(name: str, cfg, meta) -> int:
    c = cfg
    table = {
        "tokens": getattr(c, "vocab", 0),
        "targets": getattr(c, "vocab", 0),
        "token": getattr(c, "vocab", 0),
        "uih_item_id": getattr(c, "item_vocab", 0),
        "cand_item_id": getattr(c, "item_vocab", 0),
        "neg_ids": getattr(c, "item_vocab", 0),
        "user_id": getattr(c, "user_vocab", 0),
        "uih_category": getattr(c, "cat_vocab", 0),
        "cand_category": getattr(c, "cat_vocab", 0),
        "sparse_ids": getattr(c, "field_vocab", 0),
        "uih_action_type": 16,
        "senders": meta.get("n_nodes", 0),
        "receivers": meta.get("n_nodes", 0),
        "position": meta.get("kv_len", 1),
    }
    return table.get(name, 0)


def _sample_leaf(name: str, leaf, cfg, meta, rng: np.random.Generator):
    shape, dtype = leaf.shape, leaf.dtype
    if name == "position":
        return jnp.zeros(shape, dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        hi = max(_vocab_for(name, cfg, meta), 2)
        return jnp.asarray(rng.integers(0, hi, size=shape), dtype)
    if dtype == jnp.bool_:
        if "mask_pos" in name:
            return jnp.asarray(rng.random(shape) < 0.2)
        return jnp.asarray(rng.random(shape) < 0.9)
    if name == "label":
        return jnp.asarray(rng.random(shape) < 0.3, dtype)
    if name == "log_q":
        return jnp.zeros(shape, dtype)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def sample_args(cell: Cell, family: str, seed: int = 0):
    """Build positional args for cell.step_fn with real (small) arrays."""
    cfg = cell.meta["cfg"]
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    out = []

    def _cast_like(params, spec_tree):
        return jax.tree.map(
            lambda p, sp: p.astype(sp.dtype) if hasattr(sp, "dtype") else p,
            params, spec_tree)

    for i, arg in enumerate(cell.args_spec):
        if i == 0:  # params
            if family == "lm":
                out.append(_cast_like(T.init(key, cfg), arg))
            elif family == "gnn":
                out.append(_cast_like(G.init(key, cfg), arg))
            else:
                init_fn = {
                    "two-tower-retrieval": R.init_two_tower,
                    "dcn-v2": R.init_dcn_v2,
                    "dien": R.init_dien,
                    "bert4rec": R.init_bert4rec,
                    "dlrm-uih": R.init_dlrm_uih,
                    "hstu": H.init_hstu,
                }[cell.arch_id]
                out.append(_cast_like(init_fn(key, cfg), arg))
            continue
        if _is_opt_state(arg):
            out.append(adamw_init(out[0]))
            continue
        if _is_kv_cache(arg):
            out.append(jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), arg))
            continue
        out.append(
            jax.tree_util.tree_map_with_path(
                lambda path, l: _sample_leaf(_leaf_name(path), l, cfg,
                                             cell.meta, rng),
                arg,
            )
        )
    return tuple(out)


def _leaf_name(path) -> str:
    for p in reversed(path):
        if hasattr(p, "key"):
            return str(p.key)
    return ""


def _is_opt_state(arg) -> bool:
    return hasattr(arg, "_fields") and "m" in getattr(arg, "_fields", ())


def _is_kv_cache(arg) -> bool:
    return isinstance(arg, dict) and (set(arg) == {"k", "v"}
                                      or set(arg) == {"c_kv", "k_pe"})

"""Sharded embedding tables + EmbeddingBag (JAX has no native EmbeddingBag —
implemented as gather + masked segment reduction, as the assignment requires).

Tables are row(vocab)-sharded across the whole mesh for the dry-run; lookups
lower to masked local gathers + an all-reduce under GSPMD (the TPU analogue of
DLRM's model-parallel embedding all-to-all)."""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


def init_table(key, vocab: int, dim: int, scale: float = 0.01) -> jax.Array:
    return jax.random.normal(key, (vocab, dim), jnp.float32) * scale


def bag_rowsharded(
    table: jax.Array,          # (V, D) — sharded P(model_axis, None)
    ids: jax.Array,            # (B, L) — sharded P(data_axes, None)
    mask: Optional[jax.Array],
    combiner: str,
    mesh: jax.sharding.Mesh,
    data_axes=("data",),
    model_axis: str = "model",
    dtype=None,
) -> jax.Array:
    """Row(vocab)-sharded EmbeddingBag with the reduction BEFORE the collective.

    GSPMD's default lowering of a gather from a sharded table all-reduces the
    full (B, L, D) pre-reduction gather output; here each model-rank gathers
    hits among its local rows, reduces the bag locally, and psums only the
    (B_local, D) bag result — O(L) less collective traffic. The table is
    replicated over ``data`` (optimizer states stay ZeRO-sharded)."""
    from jax.sharding import PartitionSpec as P

    v, d = table.shape
    dt = dtype or table.dtype
    table = table.astype(dt)   # cast BEFORE shard_map: collectives move bf16
    b, l = ids.shape
    mask_arr = (jnp.ones_like(ids, jnp.bool_) if mask is None else mask)

    def inner(tab, idx, mk):
        rank = jax.lax.axis_index(model_axis)
        v_loc = tab.shape[0]
        lo = rank * v_loc
        local = idx - lo
        hit = (local >= 0) & (local < v_loc) & mk
        emb = tab.astype(dt)[jnp.clip(local, 0, v_loc - 1)]   # (B_loc, L, D)
        emb = emb * hit[..., None].astype(dt)
        return jax.lax.psum(jnp.sum(emb, axis=-2), model_axis)

    dp = tuple(data_axes) if data_axes else None
    out = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(model_axis, None), P(dp, None), P(dp, None)),
        out_specs=P(dp, None),
        check_vma=False,
    )(table, ids, mask_arr)
    if combiner == "mean":
        denom = jnp.maximum(jnp.sum(mask_arr, -1, keepdims=True), 1).astype(dt)
        out = out / denom
    return out


def lookup_rowsharded(table, ids, mesh, data_axes=("data",),
                      model_axis="model", dtype=None) -> jax.Array:
    """Single-id row-sharded lookup: (B,) ids -> (B, D)."""
    out = bag_rowsharded(table, ids[:, None], None, "sum", mesh, data_axes,
                         model_axis, dtype)
    return out


def seq_rowsharded(table, ids, mesh, data_axes=("data",),
                   model_axis="model", dtype=None) -> jax.Array:
    """Per-position sequence lookup from a row-sharded table: (B, S) ids ->
    (B, S, D). Each model-rank gathers hits among its local rows (compute
    dtype, typically bf16) and the partials are psum'd — half the traffic of
    GSPMD's default f32 partial all-reduce and no stray resharding copies."""
    from jax.sharding import PartitionSpec as P

    dt = dtype or table.dtype
    table = table.astype(dt)   # cast BEFORE shard_map: collectives move bf16

    def inner(tab, idx):
        rank = jax.lax.axis_index(model_axis)
        v_loc = tab.shape[0]
        local = idx - rank * v_loc
        hit = (local >= 0) & (local < v_loc)
        emb = tab.astype(dt)[jnp.clip(local, 0, v_loc - 1)]
        emb = emb * hit[..., None].astype(dt)
        return jax.lax.psum(emb, model_axis)

    dp = tuple(data_axes) if data_axes else None
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(model_axis, None), P(dp, None)),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(table, ids)


def embedding_bag(
    table: jax.Array,          # (V, D)
    ids: jax.Array,            # (B, L) padded multi-hot ids
    mask: Optional[jax.Array] = None,   # (B, L) validity
    combiner: str = "sum",     # sum | mean | none
    dtype=None,
) -> jax.Array:
    """EmbeddingBag: ragged gather + segment reduction over the bag axis."""
    dt = dtype or table.dtype
    emb = table.astype(dt)[ids]                    # (B, L, D)
    if mask is not None:
        emb = emb * mask[..., None].astype(dt)
    if combiner == "none":
        return emb
    s = jnp.sum(emb, axis=-2)
    if combiner == "sum":
        return s
    if combiner == "mean":
        denom = (
            jnp.maximum(jnp.sum(mask, axis=-1, keepdims=True), 1).astype(dt)
            if mask is not None
            else jnp.asarray(ids.shape[-1], dt)
        )
        return s / denom
    raise ValueError(combiner)


def field_embeddings(
    tables: Dict[str, jax.Array],
    ids: jax.Array,            # (B, F) one id per sparse field
    field_names,
    dtype=None,
) -> jax.Array:
    """Per-field single-hot lookup -> (B, F, D)."""
    cols = [tables[f].astype(dtype or tables[f].dtype)[ids[:, i]]
            for i, f in enumerate(field_names)]
    return jnp.stack(cols, axis=1)


def mlp_init(key, dims, scale=None) -> Params:
    ks = jax.random.split(key, len(dims) - 1)
    return {
        f"w{i}": jax.random.normal(ks[i], (dims[i], dims[i + 1]), jnp.float32)
        * (scale or 1.0 / np.sqrt(dims[i]))
        for i in range(len(dims) - 1)
    } | {
        f"b{i}": jnp.zeros((dims[i + 1],), jnp.float32)
        for i in range(len(dims) - 1)
    }


def mlp_apply(params: Params, x: jax.Array, n_layers: int,
              final_act: bool = False) -> jax.Array:
    dt = x.dtype
    for i in range(n_layers):
        x = x @ params[f"w{i}"].astype(dt) + params[f"b{i}"].astype(dt)
        if i < n_layers - 1 or final_act:
            x = jax.nn.relu(x)
    return x

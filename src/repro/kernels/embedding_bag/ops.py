"""Public jit'd wrapper for the fused EmbeddingBag kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import runtime
from repro.kernels.embedding_bag.embedding_bag import embedding_bag_kernel


def embedding_bag(table: jax.Array, ids: jax.Array, mask: jax.Array,
                  combiner: str = "sum") -> jax.Array:
    """(V, D) table, (B, L) ids/mask -> (B, D). The kernel reads the table
    as 128-lane column tiles: a reshape when D is 128, a padded copy or a
    relayout per call otherwise.

    ids are clamped into [0, V) inside the kernel before the row DMA — the
    featurizer's zero-padded (and any sentinel-poisoned) lanes ride through
    under mask==0 without ever addressing HBM out of bounds."""
    v, d = table.shape
    b, l = ids.shape
    if b == 0 or l == 0:
        # degenerate bags: a zero-step grid (or zero-trip DMA loop) is not a
        # valid pallas_call — the masked reduction is identically zero
        out = jnp.zeros((b, d), table.dtype)
    else:
        out = embedding_bag_kernel(
            runtime.to_lane_tiles(table), ids.astype(jnp.int32),
            mask.astype(table.dtype), bag_len=l,
            interpret=runtime.interpret_default(),
        )[:, :d]
    if combiner == "mean":
        denom = jnp.maximum(mask.sum(axis=1, keepdims=True), 1).astype(out.dtype)
        out = out / denom
    return out

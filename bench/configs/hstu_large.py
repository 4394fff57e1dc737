"""HSTU-large (``hstu_large.json`` beside this file): a causal generative
recommender over the user's history with pointwise (SiLU) attention,
relative position and time biases, trained to predict each next item by a
sampled softmax over it and negatives shared by the batch.

The harness takes from here the program's model config and loss, the
parameters (made on the device from the seed, in the program's tree
layout), the host prep (ids, seconds, the batch's negatives), the useful
FLOPs of a row, the attention kernel's work and pair counts for the traced
run, and the float32 reference, which imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

# the program's HSTU, imported here so that a tree without it fails at once
from repro.kernels.hstu_attention.ops import block_for
from repro.models.hstu import HSTUConfig, hstu_loss

ENTRY = "trainer"
SHARED_KEYS = ("neg_ids",)          # one draw of negatives for the batch
SCOPES = ("hstu_attention",)


def model_config(c: dict):
    import jax.numpy as jnp

    return HSTUConfig(
        name=c["name"], embed_dim=c["embed_dim"], n_blocks=c["n_blocks"],
        n_heads=c["n_heads"], dqk=c["dqk"], dv=c["dv"],
        seq_len=c["seq_len"], item_vocab=c["item_vocab"],
        num_negatives=c["num_negatives"], temperature=c["temperature"],
        time_buckets=c["time_buckets"],
        compute_dtype=getattr(jnp, c["compute_dtype"]))


def loss_fn(mcfg):
    return lambda params, batch: hstu_loss(params, batch, mcfg)


def param_shapes(c: dict) -> dict:
    """The program's parameter tree: leaf -> (shape, kind of init), see
    ``bench.gen.init_params``."""
    d, nb, h = c["embed_dim"], c["n_blocks"], c["n_heads"]
    hq, hv = h * c["dqk"], h * c["dv"]
    return {
        "item_table": ((c["item_vocab"], d), "table"),
        "pos_table": ((c["seq_len"], d), "table"),
        "blocks": {
            "uvqk": ((nb, d, 2 * hv + 2 * hq), "w"),
            "o_w": ((nb, hv, d), "w"),
            "o_b": ((nb, d), "zero"),
            "rab_p": ((nb, c["seq_len"]), "table"),
            "rab_t": ((nb, c["time_buckets"] + 1), "table"),
        },
    }


def prep(raw: dict, c: dict, batch_index: int, seed: int) -> dict:
    """Host batch -> model inputs. Item ids move to [1, vocab) so that 0
    pads; timestamps become int32 seconds (milliseconds overflow int32
    after 24 days, and the device copy is int32); the batch's negatives are
    drawn uniformly from [1, vocab), from the seed and the batch's index
    only."""
    rng = np.random.default_rng((abs(int(seed)), int(batch_index)))
    v = c["item_vocab"]
    mask = raw["uih_mask"]
    return {
        "uih_item_id": np.where(mask, raw["uih_item_id"] % (v - 1) + 1,
                                0).astype(np.int32),
        "uih_timestamp": np.where(mask, raw["uih_timestamp"] // 1000,
                                  0).astype(np.int32),
        "uih_mask": mask,
        "neg_ids": rng.integers(1, v, size=c["num_negatives"],
                                dtype=np.int32),
    }


def _lengths(batch: dict) -> np.ndarray:
    return np.asarray(batch["uih_mask"]).sum(1).astype(np.float64)


def flops_per_row(batch: dict, c: dict) -> np.ndarray:
    """Matmul FLOPs of forward and backward (3x forward) that a row needs:
    the projections at valid positions only, the attention over causal
    pairs of valid events only, the logits over the next item and the
    negatives at positions that have a next item."""
    d = c["embed_dim"]
    hq, hv = c["n_heads"] * c["dqk"], c["n_heads"] * c["dv"]
    n = _lengths(batch)
    pairs = n * (n + 1) / 2
    per_layer = n * 2 * (d * (2 * hq + 2 * hv) + hv * d) \
        + pairs * 2 * (hq + hv)
    logits = np.maximum(n - 1, 0) * 2 * d * (1 + c["num_negatives"])
    return 3 * (c["n_blocks"] * per_layer + logits)


# ---------------------------------------------------------------------------
# the attention kernel's work (``KERNELS``) and its pair counts
# ---------------------------------------------------------------------------

def _pairs(batch: dict, c: dict):
    """Causal pairs among valid events, and the pairs in the blocks that
    the kernel visits: key block >= first valid position // T, query block
    >= key block, over rows with any event."""
    n = _lengths(batch)
    t, length = block_for(c["seq_len"]), c["seq_len"]
    m = np.where(n > 0, length // t - (length - n) // t, 0)
    return (n * (n + 1) / 2).sum(), (t * t * m * (m + 1) / 2).sum()


def cost(batch: dict, c: dict):
    """(FLOPs, bytes) of the attention kernels of every block for one
    batch, forward and backward. FLOPs: the useful causal pairs' QK and
    AV products, x3 for the backward. Bytes: every operand and result of
    the forward and the backward kernel, the bias tiles and the time table
    included, at the compute dtype (float32 gradients)."""
    b, length = np.asarray(batch["uih_mask"]).shape
    hq, hv = c["n_heads"] * c["dqk"], c["n_heads"] * c["dv"]
    useful, _ = _pairs(batch, c)
    flops = 3 * c["n_blocks"] * useful * 2 * (hq + hv)
    item = {"bfloat16": 2, "float32": 4}[c["compute_dtype"]]
    rows = b * length
    tables = length * block_for(length) * 4 + 256 * 4     # bias tiles, time
    scalars = rows * 4 * 2 + b * 4                        # timestamps, starts
    fwd = rows * (2 * hq + 2 * hv) * item + tables + scalars
    bwd = (rows * (2 * hq + 2 * hv) * item + tables + scalars
           + rows * (2 * hq + hv) * 4 + tables)
    return flops, c["n_blocks"] * (fwd + bwd)


def counters(batch: dict, c: dict) -> dict:
    useful, computed = _pairs(batch, c)
    return {"pairs_useful": useful, "pairs_computed": computed}


KERNELS = {"hstu_attention": cost}


# ---------------------------------------------------------------------------
# float32 reference
# ---------------------------------------------------------------------------

def _layer_norm(x):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6)


def _l2(x):
    """x / max(|x|, 1e-6), written so that a zero row has a zero
    gradient."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True),
                                    1e-12))


def _by_offset(r):
    """(L,) bias by offset -> (L, L) holding ``r[i - j]`` at ``[i, j]``
    where ``j <= i``, 0 above: a tiled row read at a shift of one per row,
    pads, reshapes and slices only (a gather's gradient on the chip would
    be a scatter-add of every pair)."""
    import jax.numpy as jnp

    n = r.shape[0]
    u = jnp.concatenate([jnp.zeros(n, r.dtype), r])[::-1]
    flat = jnp.tile(u, n)[n - 1:n - 1 + n * (2 * n - 1)]
    return flat.reshape(n, 2 * n - 1)[:, :n]


def _by_bucket(table, bucket):
    """``table[bucket]`` as a masked sum over the table's entries: no
    gather, as above."""
    import jax.numpy as jnp

    hit = bucket[..., None] == jnp.arange(table.shape[0])
    return jnp.sum(jnp.where(hit, table, 0.0), axis=-1)


def reference_terms(p, b, c: dict):
    """Sum over the block's positions that have a next item of the
    sampled-softmax loss, with dense (L, L) attention."""
    import jax
    import jax.numpy as jnp

    ids, mask = b["uih_item_id"], b["uih_mask"].astype(bool)
    ts, neg = b["uih_timestamp"], b["neg_ids"]
    rows, length = ids.shape
    d, h = c["embed_dim"], c["n_heads"]
    hq, hv = h * c["dqk"], h * c["dv"]
    start = length - jnp.sum(mask, 1)
    i = jnp.arange(length)[:, None]
    j = jnp.arange(length)[None, :]
    pos = jnp.clip(j - start[:, None], 0, length - 1)
    x = p["item_table"][ids] * np.sqrt(d) + p["pos_table"][pos]
    x = jnp.where(mask[..., None], x, 0.0)
    allowed = (j <= i)[None] & (j[None] >= start[:, None, None])
    tn = jnp.concatenate([ts[:, 1:], ts[:, -1:]], 1)
    gap = jnp.abs(tn[:, :, None] - ts[:, None, :]).astype(jnp.float32)
    bucket = jnp.clip(jnp.floor(jnp.log(jnp.maximum(gap, 1.0)) / 0.301)
                      .astype(jnp.int32), 0, c["time_buckets"])
    for k in range(c["n_blocks"]):
        w = jax.tree.map(lambda a: a[k], p["blocks"])
        uvqk = jax.nn.silu(_layer_norm(x) @ w["uvqk"])
        u, v, q, kk = jnp.split(uvqk, [hv, 2 * hv, 2 * hv + hq], axis=-1)
        s = jnp.einsum("bqhd,bkhd->bhqk",
                       q.reshape(rows, length, h, -1),
                       kk.reshape(rows, length, h, -1))
        s = s + (_by_offset(w["rab_p"])[None]
                 + _by_bucket(w["rab_t"], bucket))[:, None]
        a = jnp.where(allowed[:, None], jax.nn.silu(s) / length, 0.0)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v.reshape(rows, length, h, -1))
        x = x + (_layer_norm(o.reshape(rows, length, hv)) * u) @ w["o_w"] \
            + w["o_b"]
    out = _l2(x)
    target = jnp.pad(ids[:, 1:], ((0, 0), (0, 1)))
    weights = mask & jnp.pad(mask[:, 1:], ((0, 0), (0, 1)))
    gold = jnp.sum(out * _l2(p["item_table"][target]), -1) / c["temperature"]
    negs = out @ _l2(p["item_table"][neg]).T / c["temperature"]
    negs = jnp.where(target[..., None] == neg, -1e30, negs)
    lse = jax.nn.logsumexp(jnp.concatenate([gold[..., None], negs], -1), -1)
    return jnp.sum((lse - gold) * weights)


def reference_denominator(batch: dict, rows: int) -> float:
    m = np.asarray(batch["uih_mask"])[:rows].astype(bool)
    return float(max((m[:, :-1] & m[:, 1:]).sum(), 1))

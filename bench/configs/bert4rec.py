"""BERT4Rec (``bert4rec.json`` beside this file): a bidirectional
transformer over the user's item sequence trained by the cloze objective. A
share of the valid positions (at least one per row) is replaced by the
[mask] id 0; each is predicted by a full softmax over the item table.

The harness takes from here the program's model config and loss, the
parameters (made on the device from the seed, in the program's tree
layout), the host cloze prep, the useful FLOPs of a row, and the float32
reference, which imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from bench.reference import rms_norm, transformer_block

ENTRY = "trainer"
SHARED_KEYS = ()            # every batch array has one row per example


def model_config(c: dict):
    import jax.numpy as jnp

    from repro.models.recsys import BERT4RecConfig

    return BERT4RecConfig(
        name=c["name"], embed_dim=c["embed_dim"], n_blocks=c["n_blocks"],
        n_heads=c["n_heads"], seq_len=c["seq_len"],
        item_vocab=c["item_vocab"], mask_token=0,
        compute_dtype=getattr(jnp, c["compute_dtype"]))


def loss_fn(mcfg):
    from repro.models.recsys import bert4rec_loss

    return lambda params, batch: bert4rec_loss(params, batch, mcfg)


def param_shapes(c: dict) -> dict:
    """The program's parameter tree: leaf -> (shape, kind of init), see
    ``bench.gen.init_params``."""
    d, nb = c["embed_dim"], c["n_blocks"]
    return {
        "item_table": ((c["item_vocab"], d), "table"),
        "pos_table": ((c["seq_len"], d), "table"),
        "blocks": {
            "attn": {k: ((nb, d, d), "w") for k in ("wq", "wk", "wv", "wo")},
            "ffn": {"w_gate": ((nb, d, 4 * d), "w"),
                    "w_up": ((nb, d, 4 * d), "w"),
                    "w_down": ((nb, 4 * d, d), "w")},
            "ln1": ((nb, d), "one"), "ln2": ((nb, d), "one")},
        "final_ln": ((d,), "one"),
    }


def prep(raw: dict, c: dict, batch_index: int, seed: int) -> dict:
    """Host batch -> cloze inputs. Item ids move to [1, vocab) so that 0 is
    the [mask] id; each valid position is masked with ``mask_prob`` (the
    newest one where a row drew none). Draws depend on the seed and the
    batch's index only."""
    rng = np.random.default_rng((abs(int(seed)), int(batch_index)))
    v = c["item_vocab"]
    mask = raw["uih_mask"]
    ids = np.where(mask, raw["uih_item_id"] % (v - 1) + 1, 0)
    pick = (rng.random(mask.shape) < c["mask_prob"]) & mask
    none = ~pick.any(1) & mask.any(1)
    pick[none, -1] = True                       # rows are right-aligned
    return {
        "uih_item_id": ids.astype(np.int32),
        "uih_mask": mask,
        "mask_pos": pick,
    }


def flops_per_row(batch: dict, c: dict) -> np.ndarray:
    """Matmul FLOPs of forward and backward (3x forward) that a row needs:
    valid positions only, all pairs among them (bidirectional), and the
    logits over the whole table at masked positions only."""
    d = c["embed_dim"]
    n = np.asarray(batch["uih_mask"]).sum(1).astype(np.float64)
    m = np.asarray(batch["mask_pos"]).sum(1).astype(np.float64)
    per_layer = n * 2 * (4 * d * d + 12 * d * d) + n * n * 4 * d
    logits = m * c["item_vocab"] * 2 * d
    return 3 * (c["n_blocks"] * per_layer + logits)


# ---------------------------------------------------------------------------
# float32 reference
# ---------------------------------------------------------------------------

def reference_terms(p, b, c: dict):
    """Sum over the block's masked positions of the full-softmax loss."""
    import jax
    import jax.numpy as jnp

    ids, mask, mpos = b["uih_item_id"], b["uih_mask"], b["mask_pos"]
    x = p["item_table"][jnp.where(mpos, 0, ids)] + p["pos_table"][None]
    for i in range(c["n_blocks"]):
        x = transformer_block(x, jax.tree.map(lambda a: a[i], p["blocks"]),
                              c["n_heads"], mask[:, None, None, :])
    x = rms_norm(x, p["final_ln"])
    logits = jnp.einsum("bld,vd->blv", x, p["item_table"])      # (B, L, V)
    gold = jnp.take_along_axis(logits, ids[..., None], -1)[..., 0]
    return jnp.sum((jax.nn.logsumexp(logits, -1) - gold) * mpos)


def reference_denominator(batch: dict, rows: int) -> float:
    return float(max(np.asarray(batch["mask_pos"])[:rows].sum(), 1))

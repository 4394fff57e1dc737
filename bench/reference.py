"""The plain reference that decides ``correct``. It imports nothing of the
program: the rows come from the sim's event stream (the source of truth the
store was compacted from), the training steps from each configuration's
float32 ``jax.numpy`` model and the optimizer written out below.

Numbers compared (``bench/limits/<workload>.json`` holds each limit):

* ``wrong_rows``: trained rows (the first three steps' and a seeded sample
  of the window's) whose arrays differ from the row rebuilt here;
* ``epoch_errors``: examples dropped or trained twice within an epoch;
* ``loss_gap``: worst relative gap of the first three steps' losses;
* ``grad_gap``: worst leaf of the first gradient as the optimizer gets it
  (read from its first moment after one step), as a gap of norms over the
  reference leaf's norm or the median leaf's, whichever is larger;
* ``update_gap``: the same for each leaf's change over the three steps,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (round-off alone moves those under Adam);
* ``grad_diff``: worst leaf of the first gradient as a norm of the
  difference of the two vectors, over the same denominator. A gap of norms
  misses a gradient that points elsewhere at the same length, as that of
  half a batch does.
"""
from __future__ import annotations

import collections
import warnings
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

MS_PER_DAY = 86_400_000
TRAITS_DTYPE = {"item_id": np.int64, "action_type": np.int32,
                "category": np.int32}


# ---------------------------------------------------------------------------
# rows: the UIH of each example rebuilt from the event stream
# ---------------------------------------------------------------------------

def row_key(user_id, request_ts, cand_item_id) -> tuple:
    return (int(user_id), int(request_ts), int(cand_item_id))


class SourceOfTruth:
    """Each example's UIH as the events of its user with
    ``request_ts - lookback <= timestamp <= request_ts``, oldest first, the
    newest ``seq_len`` kept and right-aligned."""

    def __init__(self, sim, lookback_ms: int, seq_len: int,
                 traits: Sequence[str]):
        self.events = sim.events
        self.lookback_ms = lookback_ms
        self.seq_len = seq_len
        self.traits = tuple(traits)
        self.examples: Dict[tuple, list] = collections.defaultdict(list)
        for e in sim.examples:
            self.examples[row_key(e.user_id, e.request_ts,
                                  e.candidate["item_id"])].append(e)
        self._hist: Dict[int, dict] = {}

    def _history(self, user: int, last_day: int) -> dict:
        h = self._hist.get(user)
        if h is None or h["last_day"] < last_day:
            days = [self.events.day_events(user, d)
                    for d in range(last_day + 1)]
            cols = {k: np.concatenate([d[k] for d in days])
                    for k in ("timestamp",) + self.traits}
            order = np.argsort(cols["timestamp"], kind="stable")
            h = {k: v[order] for k, v in cols.items()}
            h["last_day"] = last_day
            self._hist[user] = h
        return h

    def uih(self, user: int, t: int) -> dict:
        h = self._history(user, t // MS_PER_DAY)
        ts = h["timestamp"]
        keep = np.nonzero((ts >= t - self.lookback_ms) & (ts <= t))[0]
        keep = keep[-self.seq_len:]
        return {k: h[k][keep] for k in ("timestamp",) + self.traits}

    def raw_batch(self, keys: Sequence[tuple]) -> dict:
        """The featurized batch these rows make: ``uih_<trait>`` [B, L]
        right-aligned, ``uih_mask``, ``uih_len`` and the example's scalars;
        ``_ts`` holds each position's timestamp (ties are compared as a
        set)."""
        b, L = len(keys), self.seq_len
        out = {f"uih_{t}": np.zeros((b, L), TRAITS_DTYPE.get(t, np.int64))
               for t in self.traits}
        out["uih_mask"] = np.zeros((b, L), bool)
        out["uih_len"] = np.zeros(b, np.int32)
        out["_ts"] = np.full((b, L), -1, np.int64)
        out["user_id"] = np.array([k[0] for k in keys], np.int64)
        out["request_ts"] = np.array([k[1] for k in keys], np.int64)
        out["cand_item_id"] = np.array([k[2] for k in keys], np.int64)
        out["label_click"] = np.zeros(b, np.float32)
        for i, k in enumerate(keys):
            ex = self.examples.get(k)
            if not ex:
                out["label_click"][i] = np.nan     # no such example
                continue
            out["label_click"][i] = ex[0].labels.get("click", 0.0)
            u = self.uih(k[0], k[1])
            n = len(u["timestamp"])
            out["uih_len"][i] = n
            if n:
                out["uih_mask"][i, L - n:] = True
                out["_ts"][i, L - n:] = u["timestamp"]
                for t in self.traits:
                    out[f"uih_{t}"][i, L - n:] = u[t]
        return out


def _canonical_ties(cols: List[np.ndarray], ts: np.ndarray) -> None:
    """Sort, in place, the positions of equal timestamps by value: events
    logged in the same millisecond have no order of their own."""
    i, n = 0, len(ts)
    while i < n:
        j = i
        while j + 1 < n and ts[j + 1] == ts[i] and ts[i] >= 0:
            j += 1
        if j > i:
            block = np.stack([c[i:j + 1] for c in cols], 1)
            order = np.lexsort(block.T[::-1])
            for c in cols:
                c[i:j + 1] = c[i:j + 1][order]
        i = j + 1


def wrong_rows(got: dict, want: dict, ts: np.ndarray) -> int:
    """Rows of ``got`` (a trained batch read back) that differ from ``want``
    (the batch rebuilt here and prepared alike). An array of another shape,
    dtype or key set counts every row."""
    b = len(ts)
    if set(got) != set(want):
        return b
    bad = np.zeros(b, bool)
    event_keys = [k for k in want if k.startswith("uih_") and k != "uih_mask"
                  and np.ndim(want[k]) == 2 and want[k].shape == ts.shape]
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        if g.shape != w.shape or g.dtype != w.dtype:
            return b
        if g.ndim and g.shape[0] == b:
            diff = ~np.all((g == w).reshape(b, -1), axis=1)
            if k in event_keys:
                diff &= ~_same_up_to_ties(got, want, event_keys, ts, diff)
            bad |= diff
        elif not np.array_equal(g, w):
            bad[:] = True            # a batch-level array (shared negatives)
    return int(bad.sum())


def _same_up_to_ties(got, want, keys, ts, rows) -> np.ndarray:
    """For the ``rows`` that differ: do they agree once events of equal
    timestamp are put in one order?"""
    same = np.zeros(len(ts), bool)
    for r in np.nonzero(rows)[0]:
        gc = [np.array(np.asarray(got[k])[r]) for k in keys]
        wc = [np.array(np.asarray(want[k])[r]) for k in keys]
        _canonical_ties(gc, ts[r])
        _canonical_ties(wc, ts[r])
        same[r] = all(np.array_equal(g, w) for g, w in zip(gc, wc))
    return same


def epoch_errors(trained: Sequence[Sequence[tuple]],
                 examples: Sequence[tuple]) -> int:
    """Trained rows, batch after batch, cut into epochs of
    ``len(examples)`` rows: every whole epoch holds each example once, the
    last one at most once. Returns the examples missing plus those extra."""
    want = collections.Counter(examples)
    n = len(examples)
    rows = [k for batch in trained for k in batch]
    errors = 0
    for lo in range(0, len(rows), n):
        got = collections.Counter(rows[lo:lo + n])
        extra = got - want
        errors += sum(extra.values())
        if lo + n <= len(rows):
            errors += sum((want - got).values())
    return errors


# ---------------------------------------------------------------------------
# training: three steps of the float32 model with AdamW as written here
# ---------------------------------------------------------------------------

def rms_norm(x, w):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w


def rope(x, pos, theta=1e4):
    """Rotate the two halves of each head by position-dependent angles."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos[:, None] * freq[None, :]                   # (L, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def transformer_block(x, blk: dict, heads: int, allowed):
    """Pre-norm block: RMSNorm, multi-head attention with RoPE at absolute
    positions over the keys ``allowed`` ([B, 1, Lq, Lk] or broadcastable),
    then RMSNorm and a SwiGLU FFN, each added to the residual."""
    import jax
    import jax.numpy as jnp

    nb, L, d = x.shape
    pos = jnp.arange(L, dtype=jnp.float32)
    h = rms_norm(x, blk["ln1"])
    q, k, v = (jnp.einsum("bld,de->ble", h, blk["attn"][w]).reshape(
        nb, L, heads, d // heads) for w in ("wq", "wk", "wv"))
    s = jnp.einsum("bqhd,bkhd->bhqk", rope(q, pos), rope(k, pos))
    w = jax.nn.softmax(jnp.where(allowed, s / np.sqrt(d // heads), -1e30), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(nb, L, d)
    x = x + jnp.einsum("bld,de->ble", o, blk["attn"]["wo"])
    h = rms_norm(x, blk["ln2"])
    g = jax.nn.silu(jnp.einsum("bld,df->blf", h, blk["ffn"]["w_gate"]))
    u = jnp.einsum("bld,df->blf", h, blk["ffn"]["w_up"])
    return x + jnp.einsum("blf,fd->bld", g * u, blk["ffn"]["w_down"])


def adamw_reference(opt: dict):
    """One AdamW step with global-norm clipping, bias correction and decay
    on matrices only, jitted and in place: ``(params, grads, m, v, lr, c1,
    c2, scale) -> (params, m, v, clipped gradient, clipped leaf norms, raw
    leaf norms)``, the gradient being ``grads * scale``."""
    import jax
    import jax.numpy as jnp

    b1, b2 = opt["beta1"], opt["beta2"]

    def step(params, grads, m, v, lr, c1, c2, scale):
        grads = jax.tree.map(lambda g: g * scale, grads)
        raw = _norms(grads)
        if opt["grad_clip"] > 0:
            gn = jnp.sqrt(jnp.sum(raw * raw))
            scale = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

        def upd(p, a, s):
            u = (a / c1) / (jnp.sqrt(s / c2) + opt["eps"])
            if p.ndim >= 2:
                u = u + opt["weight_decay"] * p
            return p - lr * u

        return (jax.tree.map(upd, params, m, v), m, v, grads, _norms(grads),
                raw)

    return jax.jit(step, donate_argnums=(0, 1, 2, 3))


def learning_rate(step: int, opt: dict) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio``."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    prog = min(max((step - opt["warmup_steps"]) / span, 0.0), 1.0)
    decay = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (
        1 + np.cos(np.pi * prog))
    return opt["lr"] * warm * decay


def _norms(tree):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(_norms(tree))


def reference_steps(model, mcfg, init: Callable, batches: Sequence[dict],
                    opt: dict, block_rows: int,
                    rows: Optional[int] = None) -> dict:
    """Three AdamW steps of ``model.reference_terms`` in float32 at the
    highest matmul precision over ``batches``, ``block_rows`` rows at a
    time; ``rows`` keeps only a batch's first rows (a planted fault).
    Returns the losses, the clipped first gradient (its leaves on the host)
    and its and the raw first gradient's leaf norms, and each leaf's change
    after the steps."""
    import jax
    import jax.numpy as jnp

    def accumulate(p, blk, acc):
        val, g = jax.value_and_grad(model.reference_terms)(p, blk, mcfg)
        return val, jax.tree.map(jnp.add, acc, g)

    shared = getattr(model, "SHARED_KEYS", ())
    # the gradient buffers are donated to the update only to free them
    warnings.filterwarnings("ignore", "Some donated buffers were not usable")
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(accumulate, donate_argnums=(2,))
        update = adamw_reference(opt)
        params = init()
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, g1, g1_raw, g1_leaves = [], None, None, None
        for step, batch in enumerate(batches, 1):
            n = rows or len(batch["uih_mask"])
            den = float(model.reference_denominator(batch, n))
            total, grads = 0.0, jax.tree.map(jnp.zeros_like, params)
            for lo in range(0, n, block_rows):
                hi = min(lo + block_rows, n)
                blk = {k: (a if k in shared else a[lo:hi])
                       for k, a in batch.items() if not k.startswith("_")}
                val, grads = vg(params, blk, grads)
                total += float(val)
            losses.append(total / den)
            params, m, v, grads, clipped, raw = update(
                params, grads, m, v, learning_rate(step, opt),
                1 - opt["beta1"] ** step, 1 - opt["beta2"] ** step, 1 / den)
            if step == 1:
                g1, g1_raw = np.asarray(clipped), np.asarray(raw)
                g1_leaves = [np.asarray(x) for x in jax.tree.leaves(grads)]
            del grads
        del m, v
        change = leaf_norms(jax.tree.map(jnp.subtract, params, init()))
    return {"losses": losses, "grad": g1, "grad_raw": g1_raw,
            "grad_leaves": g1_leaves, "change": change}


def gap_by_leaf(got: np.ndarray, want: np.ndarray,
                keep: Optional[np.ndarray] = None) -> tuple:
    """Worst leaf's gap of norms over max(its reference norm, the median
    reference leaf norm); returns (gap, leaf index)."""
    keep = np.ones(len(want), bool) if keep is None else keep
    floor = np.median(want[keep])
    gaps = np.abs(got - want) / np.maximum(want, floor)
    gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def diff_by_leaf(got: Sequence[np.ndarray], want: Sequence[np.ndarray],
                 want_norms: np.ndarray) -> tuple:
    """Worst leaf's norm of the difference of the two vectors over
    max(its reference norm, the median reference leaf norm); returns (gap,
    leaf index)."""
    diff = np.array([np.linalg.norm(np.asarray(g, np.float32) - w)
                     for g, w in zip(got, want)], np.float64)
    gaps = diff / np.maximum(want_norms, np.median(want_norms))
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def compare_training(prog: dict, ref: dict) -> dict:
    """The training numbers from the program's readings (``losses``,
    ``grad``, ``grad_leaves``, ``change``) against the reference's."""
    lp, lr_ = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr_) / np.abs(lr_)))
    grad_gap, gi = gap_by_leaf(np.asarray(prog["grad"]), ref["grad"])
    moving = ref["grad_raw"] >= 1e-3 * np.median(ref["grad_raw"])
    upd_gap, ui = gap_by_leaf(np.asarray(prog["change"]), ref["change"],
                              moving)
    diff, di = diff_by_leaf(prog["grad_leaves"], ref["grad_leaves"],
                            ref["grad"])
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": upd_gap, "grad_diff": diff, "_grad_leaf": gi,
            "_update_leaf": ui, "_diff_leaf": di,
            "_excluded_leaves": int((~moving).sum())}

"""Public wrappers + host helpers for the fused late-materialization path.

Layering (DESIGN §3): the host ships the **compact** jagged layout — one
stacked int32 arena per shared ScatterPlan, offsets, and (for timestamp
traits) window-relative int32 deltas + per-row bases. On device, ONE
``fused_densify`` call (one jit: the ``jagged_to_padded`` Pallas kernel,
then an XLA cumsum over the timestamp lane — see fused.py) rebuilds every
trait's right-aligned [B, L] lanes with timestamps decoded; the dense id
lanes then feed ``embedding_bag`` straight from HBM (no host round trip).

dtype contract under jax's default x64-disabled config: the device batch is
*canonical* — int64 host traits arrive as wrapped int32 (exactly what
``jax.device_put`` of the host-dense batch produces), float32 rides the
arena bit-cast and is reconstructed bit-exact, float64 canonicalizes to
float32. Timestamps stay exact as int64 only on the host paths (see
delta_decode/ops.py); on device they are canonically wrapped like every
other int64 lane.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import runtime
from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.fused.fused import densify_decode

_I32_MAX = np.int64(2**31 - 1)


# ---------------------------------------------------------------------------
# Host-side packing helpers (numpy; run in the prefetch thread)
# ---------------------------------------------------------------------------

def ts_delta_encode(arena: np.ndarray, offsets: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Window-relative delta encoding of an absolute int64 timestamp arena.

    Returns ``(deltas int32 [N], bases int64 [B])``: each row's first kept
    element becomes delta 0 and its absolute value the row base, so the
    device cumsum only ever carries within-window offsets. Raises if a
    within-window span exceeds int32 — the codec contract (stripes are
    bounded time windows) is broken and wrapping it would corrupt data."""
    arena = np.asarray(arena, dtype=np.int64)
    offsets = np.asarray(offsets, dtype=np.int64)
    lens = np.diff(offsets)
    b = len(lens)
    bases = np.zeros(b, np.int64)
    nz = lens > 0
    starts = offsets[:-1][nz]
    bases[nz] = arena[starts]
    if not len(arena):
        return np.zeros(0, np.int32), bases
    d = np.empty(len(arena), np.int64)
    d[0] = 0
    d[1:] = arena[1:] - arena[:-1]
    d[starts] = 0                      # row starts: relative to own base
    rel = arena - np.repeat(bases, lens)
    if (np.abs(d).max(initial=0) > _I32_MAX
            or np.abs(rel).max(initial=0) > _I32_MAX):
        raise ValueError(
            "timestamp window span exceeds int32: the stripe codec's "
            "bounded-window contract is broken (see delta_decode/ops.py)")
    return d.astype(np.int32), bases


def _to_i32_col(col: np.ndarray) -> np.ndarray:
    """One trait column -> its int32 arena representation (see module doc)."""
    if col.dtype == np.float64:
        col = col.astype(np.float32)
    if col.dtype == np.float32:
        return col.view(np.int32)
    return col.astype(np.int32)        # ints/bool: wrap == canonicalization


def pack_arena(values: Dict[str, np.ndarray]
               ) -> Tuple[np.ndarray, List[Tuple[str, np.dtype]]]:
    """Stack same-plan trait arenas into one (N, T) int32 arena + metas
    (trait name, original host dtype) in column order."""
    metas = [(trait, np.asarray(col).dtype) for trait, col in values.items()]
    cols = [_to_i32_col(np.asarray(col)) for col in values.values()]
    n = len(cols[0]) if cols else 0
    arena = np.empty((n, len(cols)), np.int32)
    for i, c in enumerate(cols):
        arena[:, i] = c
    return arena, metas


# ---------------------------------------------------------------------------
# Device-side ops
# ---------------------------------------------------------------------------

def fused_densify(arena: jax.Array, offsets: jax.Array, seq_len: int,
                  ts_bases: Optional[jax.Array] = None, ts_col: int = -1
                  ) -> jax.Array:
    """(N, T) int32 arena + (B+1,) offsets -> (B, L, T) int32, right-aligned,
    timestamp column (if any) delta-decoded in-window.

    Runs the kernel on a window of L rounded up to whole 8-row tiles over
    128-lane column tiles of the arena, front-padded by that many zero rows
    so the kernel's fixed-size DMA window is always in-bounds. Rows are
    pre-clipped to L (the featurizer contract), so the right-aligned last L
    rows of the window are the answer.
    ``ts_bases`` must already be int32 (host callers wrap int64 bases with
    ``.astype(np.int32)`` — canonicalization parity, see module doc)."""
    b = offsets.shape[0] - 1
    n, t = arena.shape
    if b == 0 or seq_len == 0 or t == 0:
        return jnp.zeros((b, seq_len, t), jnp.int32)
    lp = runtime.tile_rows(seq_len)
    v = runtime.to_lane_tiles(jnp.asarray(arena), lp)
    bases = (jnp.zeros(b, jnp.int32) if ts_bases is None
             else jnp.asarray(ts_bases).astype(jnp.int32))
    out = densify_decode(
        v, jnp.asarray(offsets).astype(jnp.int32), bases,
        max_len=lp, ts_col=ts_col,
        interpret=runtime.interpret_default())
    return out[:, lp - seq_len:, :t]


def unpack_dense(dense: jax.Array, metas: List[Tuple[str, np.dtype]]
                 ) -> Dict[str, jax.Array]:
    """Split a (B, L, T) int32 dense block back into per-trait [B, L] lanes
    with their canonical device dtypes restored (bit-exact for float32)."""
    out: Dict[str, jax.Array] = {}
    for i, (trait, dt) in enumerate(metas):
        col = dense[:, :, i]
        if dt in (np.float32, np.float64):
            out[trait] = jax.lax.bitcast_convert_type(col, jnp.float32)
        else:
            out[trait] = col.astype(jax.dtypes.canonicalize_dtype(dt))
    return out


def late_materialize(values: Dict[str, np.ndarray], offsets: np.ndarray,
                     seq_len: int, *, ts_trait: Optional[str] = None,
                     table: Optional[jax.Array] = None,
                     ids_trait: Optional[str] = None,
                     combiner: str = "sum") -> Dict[str, object]:
    """One-call device pipeline: densify + timestamp decode in one jit
    (``fused_densify``), then ``embedding_bag`` over the dense id lanes
    on-device.

    ``values`` are flat per-trait arenas (clipped tails) sharing ``offsets``;
    a ``ts_trait`` arena is given in ABSOLUTE int64 and is delta-encoded
    here (rows must be pre-clipped to ``seq_len`` — the featurizer contract —
    so the window base is the first KEPT element). Returns
    ``{"lens", "mask", "traits": {trait: [B, L]}, "pooled"?}``.

    The training feed uses ``fused_densify``/``unpack_dense`` directly and
    leaves the embedding lookup inside the jit'd step — the table is a
    trained parameter (fusion boundary, DESIGN §3); this composition is the
    bench/serving-style surface that exercises all three stages together."""
    offs = np.asarray(offsets, dtype=np.int64)
    vals = dict(values)
    ts_bases = None
    ts_col = -1
    if ts_trait is not None and ts_trait in vals:
        deltas, bases64 = ts_delta_encode(vals[ts_trait], offs)
        vals[ts_trait] = deltas
        ts_bases = bases64.astype(np.int32)
        ts_col = list(vals).index(ts_trait)
    arena, metas = pack_arena(vals)
    offs32 = jnp.asarray(offs.astype(np.int32))
    dense = fused_densify(jnp.asarray(arena), offs32, seq_len,
                          ts_bases=ts_bases, ts_col=ts_col)
    traits = unpack_dense(dense, metas)
    lens = jnp.minimum(jnp.diff(offs32), seq_len).astype(jnp.int32)
    j = jnp.arange(seq_len, dtype=jnp.int32)[None, :]
    mask = j >= (seq_len - lens[:, None])
    out: Dict[str, object] = {"lens": lens, "mask": mask, "traits": traits}
    if table is not None and ids_trait is not None:
        out["pooled"] = embedding_bag(jnp.asarray(table), traits[ids_trait],
                                      mask, combiner=combiner)
    return out

"""Device-side late materialization: the host<->device handover adapter.

``RebatchingClient(emit_jagged=True)`` emits compact payloads (flat arena +
offsets per trait — DESIGN §3 layout contract) instead of dense [B, L]
batches. ``DeviceMaterializer`` sits inside the DevicePrefetcher's transfer
thread: it uploads ONLY the compact arrays (the zero padding never crosses
the PCIe/ICI link), then runs the ``kernels/fused`` densify (Pallas) and
timestamp decode (XLA), one jit, on device and rebuilds exactly the batch dict the host-dense path would have
produced after ``jax.device_put`` — same keys, same order, same canonical
dtypes, same bytes (tests/test_feed.py asserts identity in interpret mode).

The embedding lookup deliberately stays OUT of this adapter for training:
the table is a trained parameter living inside the jit'd step, so the
fusion boundary is decode+densify (see ``kernels/fused/ops.late_materialize``
for the fully fused decode->densify->embed composition used by serving-style
consumers, and ``roofline.analysis.materialization_roofline`` for why the
boundary costs nothing — the dense id lanes must transit HBM for the model
either way).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from repro.kernels.fused.ops import (
    fused_densify,
    pack_arena,
    ts_delta_encode,
    unpack_dense,
)

HostBatch = Dict[str, np.ndarray]


def is_jagged_batch(batch: Any) -> bool:
    """True for compact payloads from a jagged-emission client."""
    return isinstance(batch, dict) and "_seq_len" in batch


def jagged_batch_nbytes(batch: HostBatch) -> int:
    """Bytes this payload ships over H2D (arena/offsets/scalars; the metadata
    scalar ``_seq_len`` stays host-side)."""
    total = 0
    for k, v in batch.items():
        if k == "_seq_len":
            continue
        a = np.asarray(v)
        if k.startswith("_arena_") and a.dtype == np.int64:
            # int64 arenas upload as int32 (canonicalization / delta packing)
            total += a.size * 4
        else:
            total += a.nbytes
    return total


def densify_host(batch: HostBatch) -> HostBatch:
    """Host-side fallback densify of a compact payload (numpy scatter) —
    the oracle the device path is tested against, and the escape hatch for
    consumers that receive a payload without a device stage."""
    seq_len = int(batch["_seq_len"])
    lens = np.asarray(batch["uih_len"])
    b = len(lens)
    shared = np.zeros(b + 1, np.int64)
    shared[1:] = np.cumsum(lens, dtype=np.int64)
    j = np.arange(seq_len)
    out: HostBatch = {"uih_len": lens}
    for k, v in batch.items():
        if not k.startswith("_arena_"):
            continue
        trait = k[len("_arena_"):]
        offs = np.asarray(batch.get(f"_offsets_{trait}", shared))
        tl = np.minimum(np.diff(offs), seq_len)
        dense = np.zeros((b, seq_len), v.dtype)
        dense[j >= (seq_len - tl)[:, None]] = v
        out[f"uih_{trait}"] = dense
    out["uih_mask"] = j >= (seq_len - lens)[:, None]
    for k, v in batch.items():
        if k == "_seq_len" or k == "uih_len" or k.startswith(("_arena_",
                                                              "_offsets_")):
            continue
        out[k] = v
    return out


class DeviceMaterializer:
    """Upload a compact jagged payload + densify and decode it on device.

    Stateless per batch except ``last_h2d_bytes`` (read by the prefetcher
    right after each call for the ``ClientStats.h2d_bytes`` counter)."""

    def __init__(self, ts_trait: str = "timestamp", device: Any = None,
                 sharding: Any = None):
        self.ts_trait = ts_trait
        self.device = device
        self.sharding = sharding
        self.last_h2d_bytes = 0

    def _put(self, x: np.ndarray):
        import jax

        self.last_h2d_bytes += x.nbytes
        if self.device is not None:
            return jax.device_put(x, self.device)
        return jax.device_put(x)

    def _group(self, batch: HostBatch, traits: List[str], offs: np.ndarray,
               seq_len: int) -> Dict[str, Any]:
        """Materialize one shared-plan trait group with ONE kernel launch."""
        vals: Dict[str, np.ndarray] = {}
        ts_bases = None
        ts_col = -1
        for t in traits:
            col = np.asarray(batch[f"_arena_{t}"])
            if t == self.ts_trait and col.dtype == np.int64:
                deltas, bases64 = ts_delta_encode(col, offs)
                vals[t] = deltas
                # wrapped int32 base: decoded lanes match what device_put of
                # the host-dense int64 timestamps canonicalizes to
                ts_bases = self._put(bases64.astype(np.int32))
                ts_col = len(vals) - 1
            else:
                vals[t] = col
        arena, metas = pack_arena(vals)
        dense = fused_densify(self._put(arena),
                              self._put(offs.astype(np.int32)),
                              seq_len, ts_bases=ts_bases, ts_col=ts_col)
        return unpack_dense(dense, metas)

    def __call__(self, batch: HostBatch):
        import jax
        import jax.numpy as jnp

        self.last_h2d_bytes = 0
        seq_len = int(batch["_seq_len"])
        lens_h = np.asarray(batch["uih_len"])
        b = len(lens_h)
        shared = np.zeros(b + 1, np.int64)
        shared[1:] = np.cumsum(lens_h, dtype=np.int64)
        traits = [k[len("_arena_"):] for k in batch if k.startswith("_arena_")]
        shared_group = [t for t in traits if f"_offsets_{t}" not in batch]
        dense_traits: Dict[str, Any] = {}
        if shared_group:
            dense_traits.update(
                self._group(batch, shared_group, shared, seq_len))
        for t in traits:
            if f"_offsets_{t}" not in batch:
                continue
            # schema-evolution trait with its own jagged structure: its own
            # (1-column) kernel launch over its own offsets
            dense_traits.update(self._group(
                batch, [t], np.asarray(batch[f"_offsets_{t}"]), seq_len))
        lens = self._put(lens_h)
        j = jnp.arange(seq_len, dtype=jnp.int32)[None, :]
        mask = j >= (seq_len - lens[:, None])
        # key order mirrors JaggedFeatures.to_padded exactly — consumers and
        # parity tests see the SAME dict shape as the host-dense path
        out: Dict[str, Any] = {"uih_len": lens}
        for t in traits:
            out[f"uih_{t}"] = dense_traits[t]
        out["uih_mask"] = mask
        for k, v in batch.items():
            if k in ("_seq_len", "uih_len") or k.startswith(("_arena_",
                                                             "_offsets_")):
                continue
            out[k] = self._put(np.asarray(v))
        if self.sharding is not None:
            out = jax.device_put(out, self.sharding)
        return out

"""Late materialization on device: jagged trait arena -> dense right-aligned
[B, L, T] block with the timestamp delta-decode, in one jit.

This is the device half of the paper's §4.2 training-time reconstruction:
the host ships only the compact values arena + offsets (no [B, L] zero
padding over the wire), and the densify + decode run where the bandwidth
is. All traits of a batch share one ScatterPlan, so their clipped tails
stack as int32 columns of a single (N, T) arena (float traits ride
bit-cast — see ops.pack_arena).

Two stages, not one fused kernel:

1. densify: the ``kernels/jagged`` Pallas kernel (Mosaic custom call
   ``jagged_to_padded``). Grid = (B, 128-lane tiles); each step DMAs the
   L-row window ending at ``offsets[b+1]`` (the wrapper rounds L up to
   whole 8-row tiles and front-pads by that much, so the window is always
   in-bounds) into VMEM, masks the invalid prefix and writes a (1, L, 128)
   block to HBM.
2. decode, only when the batch carries a delta-encoded timestamp column:
   XLA ops after the kernel in the same jit re-read the (B, L) timestamp
   lane from HBM, cumsum it in int32, add the per-row (int32-wrapped) base
   and write the lane back. Mosaic has no cumsum lowering, and the roll-based
   scan that it does lower (as in ``kernels/delta_decode``) keeps log2(L)
   temporaries of the whole (L, 128) window in VMEM: compiled for a v5e at
   B=64, that in-kernel decode fit at L=4096 but not at L=8192, where the
   densify alone fits. The price is one extra HBM round trip of the
   timestamp lane (``roofline.analysis.MaterializationRoofline``).

The carry never leaves one row's window, so the int32-width hazard of the
standalone delta_decode kernel (see delta_decode/ops.py) cannot arise —
window-relative offsets are duration-bounded by codec construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.jagged.jagged import jagged_to_padded_kernel


@functools.partial(jax.jit, static_argnames=("max_len", "ts_col", "interpret"))
def densify_decode(
    values_tiles: jax.Array,    # (C, N + max_len, 128) int32 arena tiles
    offsets: jax.Array,         # (B+1,) int32
    ts_bases: jax.Array,        # (B,) int32 (zeros when ts_col < 0)
    max_len: int,
    ts_col: int = -1,
    interpret: bool = False,
) -> jax.Array:
    dense = jagged_to_padded_kernel(values_tiles, offsets, max_len=max_len,
                                    interpret=interpret)
    if ts_col < 0:
        return dense
    # the first kept element's delta is 0 by encoding and the kernel zeroed
    # the invalid prefix, so the cumsum yields the window-relative offset at
    # every valid lane; adding the wrapped int32 base reproduces exactly what
    # device_put'ing the host-dense int64 timestamps canonicalizes to (x64
    # is disabled)
    lens = jnp.minimum(offsets[1:] - offsets[:-1], max_len)
    valid = jnp.arange(max_len)[None, :] >= (max_len - lens)[:, None]
    ts = (jnp.cumsum(dense[:, :, ts_col], axis=1, dtype=jnp.int32)
          + ts_bases[:, None])
    return dense.at[:, :, ts_col].set(jnp.where(valid, ts, 0))

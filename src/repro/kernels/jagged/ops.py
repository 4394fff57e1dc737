"""Public jit'd wrapper for the jagged->padded materialization kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import runtime
from repro.kernels.jagged.jagged import jagged_to_padded_kernel


def jagged_to_padded(values: jax.Array, offsets: jax.Array, max_len: int
                     ) -> jax.Array:
    """values (N, D) + offsets (B+1,) -> (B, max_len, D), right-aligned.

    Runs the kernel on a window of whole 8-row tiles over 128-lane column
    tiles of values, front-padded by that many zero rows so the kernel's
    fixed-size DMA window is always in-bounds."""
    n, d = values.shape
    b = offsets.shape[0] - 1
    if b == 0 or max_len == 0:
        # zero-step grids / zero-row DMA windows are not valid pallas_calls
        return jnp.zeros((b, max_len, d), values.dtype)
    lp = runtime.tile_rows(max_len)
    out = jagged_to_padded_kernel(runtime.to_lane_tiles(values, lp),
                                  offsets.astype(jnp.int32), lp,
                                  interpret=runtime.interpret_default())
    return out[:, lp - max_len:, :d]

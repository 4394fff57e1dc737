"""Pallas TPU kernel: jagged -> padded-dense (right-aligned) UIH batch
materialization — the device-side hot path of training-time late
materialization (paper §4.2).

TPU mapping: the jagged values stay in HBM (pl.ANY) as 128-lane column tiles
(``runtime.to_lane_tiles``); grid step (b, c) DMAs the L-row window of tile
c ending at ``offsets[b+1]`` (front-padded by the wrapper so the window is
always in-bounds) into a VMEM scratch, masks the invalid prefix, and writes
the (1, L, 128) output block. One sequential DMA per step.

The window is a whole number of 8-row sublane tiles: the wrappers round L
up with ``runtime.tile_rows`` and slice the right-aligned tail back off (on
a v5e, a kernel test at a 5-row window never finished).

VMEM: the (L, 128) scratch plus the double-buffered (1, L, 128) output
block, 3·L·128·itemsize bytes whatever D is, must fit Mosaic's default
scoped VMEM limit. Longer windows are refused before lowering with an
error that names the limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import LANES

# Mosaic's default scoped VMEM limit on a TPU v5e. Compiled for a v5e at
# B=64, int32: L=10752 (15.75 MiB) fits, L=11264 (16.5 MiB) does not
SCOPED_VMEM_BYTES = 16 * 2**20


def vmem_bytes(max_len: int, itemsize: int) -> int:
    """VMEM the kernel needs: scratch + double-buffered output block."""
    return 3 * max_len * LANES * itemsize


def _kernel(offsets_ref, values_ref, out_ref, scratch, sem, *, max_len):
    b = pl.program_id(0)
    c = pl.program_id(1)
    end = offsets_ref[b + 1] + max_len        # +max_len: wrapper front-pad
    start = offsets_ref[b]
    ln = jnp.minimum(end - max_len - start, max_len)
    copy = pltpu.make_async_copy(
        values_ref.at[c, pl.ds(end - max_len, max_len), :], scratch, sem)
    copy.start()
    copy.wait()
    j = jax.lax.broadcasted_iota(jnp.int32, scratch.shape, 0)
    valid = j >= (max_len - ln)
    out_ref[0] = jnp.where(valid, scratch[...], jnp.zeros((), scratch.dtype))


@functools.partial(jax.jit, static_argnames=("max_len", "interpret"))
def jagged_to_padded_kernel(
    values_tiles: jax.Array,    # (C, N + max_len, 128): front-padded tiles
    offsets: jax.Array,         # (B+1,) int32
    max_len: int,
    interpret: bool = False,
) -> jax.Array:
    b = offsets.shape[0] - 1
    n_tiles = values_tiles.shape[0]
    dtype = values_tiles.dtype
    if max_len % 8:
        raise ValueError(f"window of {max_len} rows is not whole 8-row "
                         f"tiles; pad it with tile_rows()")
    need = vmem_bytes(max_len, dtype.itemsize)
    if need > SCOPED_VMEM_BYTES:
        raise ValueError(
            f"jagged densify window of {max_len} rows needs {need} bytes of "
            f"VMEM, above the {SCOPED_VMEM_BYTES}-byte scoped VMEM limit of "
            f"a TPU v5e; use a shorter seq_len")
    kern = functools.partial(_kernel, max_len=max_len)
    return pl.pallas_call(
        kern,
        grid=(b, n_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # offsets (scalar loads)
            pl.BlockSpec(memory_space=pl.ANY),       # jagged values in HBM
        ],
        out_specs=pl.BlockSpec((1, max_len, LANES), lambda i, c: (i, 0, c)),
        out_shape=jax.ShapeDtypeStruct((b, max_len, n_tiles * LANES), dtype),
        scratch_shapes=[
            pltpu.VMEM((max_len, LANES), dtype),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
        name="jagged_to_padded",
    )(offsets, values_tiles)

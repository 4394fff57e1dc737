"""Pallas TPU kernel: EmbeddingBag — fused gather + masked bag reduction.

The recsys hot path (kernel_taxonomy §B.6 / §B.11): the table is far larger
than VMEM, so it stays in HBM (pl.ANY) and rows are fetched by **double-
buffered async DMA** — while row l is being accumulated, the DMA for row l+1
is already in flight, hiding HBM gather latency behind the VPU adds. ids and
mask weights live in SMEM, read as scalars; the (1, 128) accumulator and
the two row slots live in VMEM. The table arrives as 128-lane column tiles
(``runtime.to_lane_tiles``) and grid step (b, c) pools tile c of bag b.

Block shapes: Mosaic wants the last two dims of every block divisible by
(8, 128) or equal to the array's. A (1, L) row of a (B, L) ids array is
neither, so ``embedding_bag_kernel`` views ids and mask as (B, 1, L) and
the output as (B, 1, D): each grid step then takes a (1, 1, L) block whose
last two dims equal the array's.

(On real v5e hardware this op belongs to SparseCore; this is the TensorCore-
resident formulation, which is also what one uses when embedding output feeds
straight into MXU matmuls.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import LANES


def _kernel(ids_ref, mask_ref, table_ref, out_ref, acc, slots, sems, *,
            bag_len, vocab):
    c = pl.program_id(1)

    def dma(l, slot):
        # clamp BEFORE the DMA is issued: padded/sentinel lanes carry
        # arbitrary ids under mask==0, and an async copy from table[id] reads
        # HBM unconditionally — an out-of-range id must never leave [0, V)
        # even though its row is multiplied by zero afterwards
        idx = jnp.clip(ids_ref[0, 0, l], 0, vocab - 1)
        return pltpu.make_async_copy(
            table_ref.at[c, pl.ds(idx, 1), :], slots.at[slot], sems.at[slot]
        )

    dma(0, 0).start()

    def body(l, _):
        slot = jax.lax.rem(l, 2)
        nxt = jax.lax.rem(l + 1, 2)

        @pl.when(l + 1 < bag_len)
        def _prefetch():
            dma(l + 1, nxt).start()

        dma(l, slot).wait()
        w = mask_ref[0, 0, l].astype(acc.dtype)
        acc[...] += slots[slot] * w
        return 0

    acc[...] = jnp.zeros_like(acc)
    jax.lax.fori_loop(0, bag_len, body, 0)
    out_ref[0] = acc[...]


@functools.partial(jax.jit, static_argnames=("bag_len", "interpret"))
def embedding_bag_kernel(
    table_tiles: jax.Array,  # (C, V, 128) — HBM resident column tiles
    ids: jax.Array,          # (B, L) int32
    mask: jax.Array,         # (B, L) float (0/1)
    bag_len: int,
    interpret: bool = False,
) -> jax.Array:
    b, l = ids.shape
    n_tiles, v, _ = table_tiles.shape
    out_dt = table_tiles.dtype
    # a 16-bit table is tiled in HBM with two rows packed per sublane, and
    # Mosaic refuses a one-row DMA out of it (compiled for a v5e): such
    # tables are gathered and pooled in float32 and the result cast back
    dt = jnp.dtype(jnp.float32) if out_dt.itemsize < 4 else out_dt
    assert l == bag_len, (l, bag_len)   # ops.py owns ragged-shape padding
    return pl.pallas_call(
        functools.partial(_kernel, bag_len=bag_len, vocab=v),
        grid=(b, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, l), lambda i, c: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, l), lambda i, c: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda i, c: (i, 0, c)),
        out_shape=jax.ShapeDtypeStruct((b, 1, n_tiles * LANES), dt),
        scratch_shapes=[
            pltpu.VMEM((1, LANES), dt),
            pltpu.VMEM((2, 1, LANES), dt),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(ids[:, None, :], mask[:, None, :].astype(dt),
      table_tiles.astype(dt))[:, 0].astype(out_dt)

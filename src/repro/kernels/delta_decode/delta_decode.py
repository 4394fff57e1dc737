"""Pallas TPU kernel: batched delta-decode (prefix sum) of columnar stripes.

The trait-aware codec (paper §4.1.2) stores timestamps as deltas; training-time
materialization decodes whole batches of stripes at once. TPU mapping: grid =
(B, N/block_n); the N axis is innermost, and the TPU grid executes sequentially,
so a VMEM carry holds the running sum across column blocks of the same row
(classic sequential-grid scan). Block shapes are (block_b, block_n) in VMEM,
lane-aligned to 128.

Carry-width contract: the scan accumulates in int32, so the kernel decodes
**window-relative** offsets only — callers with int64 arenas (epoch-ms
timestamps) must pass window-relative deltas with ``bases=0`` and re-add the
per-row int64 base host-side (``ops.delta_decode`` does exactly this; see the
regression test with timestamps > 2^31 in tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _prefix_sum(x: jax.Array, axis: int) -> jax.Array:
    """Inclusive int32 prefix sum of a kernel value along ``axis``.

    Mosaic has no cumsum lowering; this builds the scan from ``pltpu.roll``
    and masked adds in ceil(log2 n) steps (Hillis-Steele). Wrapping int32
    addition is associative, so the result is bit-identical to
    ``jnp.cumsum(x, axis, dtype=jnp.int32)``."""
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    k = 1
    while k < n:
        # roll moves element i to i + k (jnp.roll semantics); the first k
        # positions receive wrapped-around values and must add nothing
        x = x + jnp.where(idx >= k, pltpu.roll(x, k, axis), 0)
        k *= 2
    return x


def _kernel(deltas_ref, bases_ref, out_ref, carry_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    block = deltas_ref[...]                          # (block_b, block_n)
    csum = _prefix_sum(block, axis=1)
    out_ref[...] = csum + carry_ref[...] + bases_ref[...]
    carry_ref[...] = carry_ref[...] + csum[:, -1:]


@functools.partial(jax.jit, static_argnames=("block_b", "block_n", "interpret"))
def delta_decode_kernel(
    deltas: jax.Array,      # (B, N) int32
    bases: jax.Array,       # (B,) int32
    block_b: int = 8,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, n = deltas.shape
    assert b % block_b == 0 and n % block_n == 0, (b, n, block_b, block_n)
    bases2d = bases[:, None]                         # (B, 1)
    grid = (b // block_b, n // block_n)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_b, 1), jnp.int32)],
        interpret=interpret,
    )(deltas, bases2d)

"""Pallas TPU kernels: HSTU's causal pointwise (SiLU) attention over
right-aligned jagged rows, with a relative position bias and a bucketed
time bias (arXiv:2402.17152, eqs. 1-3).

For row ``b`` with ``n`` events, right-aligned so that they fill positions
``[s, L)`` with ``s = L - n``, and for each head:

    out[i] = sum_{s <= j <= i} silu(q_i . k_j + P(i - j) + W(bucket(tn_i - t_j))) / L * v_j

where ``tn_i`` is the timestamp of event ``i + 1`` (the newest event's own),
``bucket(x) = floor(ln(max(|x|, 1)) / 0.301)`` clamped to the time table,
``P`` the position bias by offset and ``W`` the time table. Positions
before ``s`` (padding) produce zeros and are never read as keys.

TPU mapping. Blocks of ``T`` queries by ``T`` keys; a block is visited only
when it holds at least one causal pair of valid events, i.e. key block
``kj >= s // T`` and query block ``qi >= kj``. Both heads of a block share
one bias tile.

* Forward, grid ``(B, L/T)``: step ``(b, qi)`` holds the query block and
  the whole row of keys and values (fetched once per row) and loops over
  key blocks ``s // T .. qi``.
* Backward, grid ``(B, L/T)``: step ``(b, kj)`` holds the key block and the
  whole row of queries and output gradients and loops over query blocks
  ``kj .. L/T - 1``. dK and dV accumulate in their output block; dQ in an
  output that holds the whole row and stays resident across the row's
  steps; the position-bias and time-table gradients in outputs resident
  across the whole grid, so every axis is sequential.

The bias is built in the kernel: the position term as one ``(T, T)`` tile
per block offset ``qi - kj`` (a Toeplitz block, made by the wrapper from
the table, the same for every row and head), the time term from the
timestamps, bucketed on the VPU and looked up with a 128-lane gather. Its
gradient is a per-bucket sum over the block, looped over the buckets that
the block's valid pairs reach. No ``(L, L)`` array is stored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# ln(2) rounded as HSTU's code has it: log buckets about 1.35x wide
BUCKET_LOG_WIDTH = 0.301
# the time table padded to whole lanes; HSTU's 128 buckets + 1 fit in 256
TABLE_LANES = 2 * LANES
# Mosaic's scoped VMEM limit for these kernels: whole rows of keys and
# values (or queries and gradients) and the Toeplitz tiles, double-buffered
VMEM_LIMIT_BYTES = 100 * 2**20


def bucket(diff: jax.Array, n_buckets: int) -> jax.Array:
    """Time bucket of a difference of timestamps (seconds)."""
    x = jnp.maximum(jnp.abs(diff.astype(jnp.float32)), 1.0)
    b = jnp.floor(jnp.log(x) / BUCKET_LOG_WIDTH).astype(jnp.int32)
    return jnp.clip(b, 0, n_buckets - 1)


def pair_mask(rows: jax.Array, cols: jax.Array, start) -> jax.Array:
    """Causal pairs among valid events: ``start <= j <= i``."""
    return (cols <= rows) & (cols >= start)


def time_bias(idx: jax.Array, table: jax.Array) -> jax.Array:
    """``table[idx]`` for a ``(T, T)`` tile of bucket indices and a
    ``(1, 256)`` table: a lane gather per 128 columns from the table's
    first 128 entries, entry 128 selected apart."""
    t = idx.shape[0]
    lanes = jnp.broadcast_to(table[:, :LANES], (t, LANES))
    parts = [jnp.take_along_axis(
        lanes, jnp.minimum(idx[:, lo:lo + LANES], LANES - 1), axis=1)
        for lo in range(0, idx.shape[1], LANES)]
    g = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    # entry 128 as a scalar (Mosaic broadcasts no lane slice at offset 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    last = jnp.sum(jnp.where(lane == 0, table[:, LANES:], 0.0))
    return jnp.where(idx >= LANES, last, g)


def _bias_tile(p_ref, wt_ref, tn, tk, offset, n_buckets):
    idx = bucket(tn - tk, n_buckets)
    return idx, p_ref[offset] + time_bias(idx, wt_ref[...])


def _dot_nt(a, b):
    """``a @ b.T`` with float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """``a.T @ b`` with float32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def _fwd_kernel(start_ref, q_ref, k_ref, v_ref, tq_ref, tk_ref, p_ref,
                wt_ref, o_ref, acc_ref, *, block, heads, dqk, dv, scale,
                n_buckets):
    b, qi = pl.program_id(0), pl.program_id(1)
    s = start_ref[b]
    q_lo = qi * block
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(q_lo + block > s)
    def _():
        q = q_ref[0]
        tn = tq_ref[0]
        rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        cols0 = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)

        def body(kj, carry):
            k_lo = pl.multiple_of(kj * block, block)
            kb = k_ref[0, pl.ds(k_lo, block), :]
            vb = v_ref[0, pl.ds(k_lo, block), :]
            _, bias = _bias_tile(p_ref, wt_ref, tn,
                                 tk_ref[0, :, pl.ds(k_lo, block)], qi - kj,
                                 n_buckets)
            mask = pair_mask(rows, k_lo + cols0, s)
            for h in range(heads):
                z = _dot_nt(q[:, h * dqk:(h + 1) * dqk],
                            kb[:, h * dqk:(h + 1) * dqk]) + bias
                a = jnp.where(mask, z * _sigmoid(z) * scale, 0.0)
                acc_ref[:, h * dv:(h + 1) * dv] += _dot(
                    a.astype(vb.dtype), vb[:, h * dv:(h + 1) * dv])
            return carry

        jax.lax.fori_loop(s // block, qi + 1, body, 0)

    o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _bwd_kernel(start_ref, q_ref, k_ref, v_ref, do_ref, tq_ref, tk_ref,
                p_ref, wt_ref, dq_ref, dk_ref, dv_ref, dp_ref, dwt_ref, *,
                block, heads, dqk, dv, scale, n_buckets, n_blocks):
    b, kj = pl.program_id(0), pl.program_id(1)
    s = start_ref[b]
    k_lo = kj * block

    @pl.when((b == 0) & (kj == 0))
    def _():
        dp_ref[...] = jnp.zeros_like(dp_ref)
        dwt_ref[...] = jnp.zeros_like(dwt_ref)

    @pl.when(kj == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    dk_ref[...] = jnp.zeros_like(dk_ref)
    dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(k_lo + block > s)
    def _():
        kb, vb, tk = k_ref[0], v_ref[0], tk_ref[0]
        rows0 = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, TABLE_LANES), 1)

        def body(qi, carry):
            q_lo = pl.multiple_of(qi * block, block)
            qb = q_ref[0, pl.ds(q_lo, block), :]
            dob = do_ref[0, pl.ds(q_lo, block), :]
            idx, bias = _bias_tile(p_ref, wt_ref,
                                   tq_ref[0, pl.ds(q_lo, block), :], tk,
                                   qi - kj, n_buckets)
            mask = pair_mask(q_lo + rows0, cols, s)
            dbias = jnp.zeros((block, block), jnp.float32)
            dq = []
            for h in range(heads):
                hq = slice(h * dqk, (h + 1) * dqk)
                hv = slice(h * dv, (h + 1) * dv)
                z = _dot_nt(qb[:, hq], kb[:, hq]) + bias
                sig = _sigmoid(z)
                a = jnp.where(mask, z * sig * scale, 0.0)
                dv_ref[0, :, hv] += _dot_tn(a.astype(dob.dtype), dob[:, hv])
                da = _dot_nt(dob[:, hv], vb[:, hv])
                dz = jnp.where(mask, da * (sig * (1.0 + z * (1.0 - sig))
                                           * scale), 0.0)
                dk_ref[0, :, hq] += _dot_tn(dz.astype(qb.dtype), qb[:, hq])
                dq.append(_dot(dz.astype(kb.dtype), kb[:, hq]))
                dbias = dbias + dz
            dq_ref[0, pl.ds(q_lo, block), :] += (
                dq[0] if heads == 1 else jnp.concatenate(dq, axis=1))
            dp_ref[qi - kj] += dbias
            # the time table's gradient: each bucket's sum, over the buckets
            # that the block's valid pairs reach
            lo = jnp.min(jnp.where(mask, idx, n_buckets))
            hi = jnp.max(jnp.where(mask, idx, -1))

            def per_bucket(c, acc):
                tot = jnp.sum(jnp.sum(jnp.where(idx == c, dbias, 0.0), axis=0,
                                      keepdims=True), axis=1, keepdims=True)
                return acc + jnp.where(lanes == c, tot, 0.0)

            dwt_ref[...] += jax.lax.fori_loop(
                lo, hi + 1, per_bucket,
                jnp.zeros((1, TABLE_LANES), jnp.float32))
            return carry

        jax.lax.fori_loop(kj, n_blocks, body, 0)


def _params(sequential: bool):
    sem = ("arbitrary",) * 2 if sequential else ("parallel", "arbitrary")
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _check(q, block):
    length = q.shape[1]
    if length % block:
        raise ValueError(f"{length} positions are not whole blocks of "
                         f"{block}")


@functools.partial(jax.jit, static_argnames=("block", "heads", "n_buckets",
                                             "interpret"))
def hstu_attention_fwd(start, q, k, v, tq, tk, p_blocks, wt, *, block,
                       heads, n_buckets, interpret=False):
    """Forward. ``q``, ``k``: (B, L, H*dqk); ``v``: (B, L, H*dv); ``tq``:
    (B, L, 1) int32 next timestamps; ``tk``: (B, 1, L) int32 timestamps;
    ``start``: (B,) int32 first valid position; ``p_blocks``: (L/T, T, T)
    float32 position-bias tiles; ``wt``: (1, 256) float32 time table.
    Returns (B, L, H*dv) in ``v``'s dtype."""
    _check(q, block)
    b, length, hd = q.shape
    hdv = v.shape[2]
    nb = length // block
    kern = functools.partial(
        _fwd_kernel, block=block, heads=heads, dqk=hd // heads,
        dv=hdv // heads, scale=1.0 / length, n_buckets=n_buckets)
    row = lambda i, j, *_: (i, 0, 0)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, block, hd), lambda i, j, *_: (i, j, 0)),
                pl.BlockSpec((1, length, hd), row),
                pl.BlockSpec((1, length, hdv), row),
                pl.BlockSpec((1, block, 1), lambda i, j, *_: (i, j, 0)),
                pl.BlockSpec((1, 1, length), row),
                pl.BlockSpec((nb, block, block), lambda i, j, *_: (0, 0, 0)),
                pl.BlockSpec((1, TABLE_LANES), lambda i, j, *_: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, block, hdv),
                                   lambda i, j, *_: (i, j, 0)),
            scratch_shapes=[pltpu.VMEM((block, hdv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, length, hdv), v.dtype),
        compiler_params=_params(sequential=False),
        interpret=interpret,
        name="hstu_attention_fwd",
    )(start, q, k, v, tq, tk, p_blocks, wt)


@functools.partial(jax.jit, static_argnames=("block", "heads", "n_buckets",
                                             "interpret"))
def hstu_attention_bwd(start, q, k, v, do, tq, tk, p_blocks, wt, *, block,
                       heads, n_buckets, interpret=False):
    """Backward of :func:`hstu_attention_fwd` for the output gradient
    ``do``: (dq, dk, dv, d p_blocks, d wt), all float32."""
    _check(q, block)
    b, length, hd = q.shape
    hdv = v.shape[2]
    nb = length // block
    kern = functools.partial(
        _bwd_kernel, block=block, heads=heads, dqk=hd // heads,
        dv=hdv // heads, scale=1.0 / length, n_buckets=n_buckets,
        n_blocks=nb)
    row = lambda i, j, *_: (i, 0, 0)
    blk = lambda i, j, *_: (i, j, 0)
    whole = lambda i, j, *_: (0, 0, 0)
    f32 = jnp.float32
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, length, hd), row),
                pl.BlockSpec((1, block, hd), blk),
                pl.BlockSpec((1, block, hdv), blk),
                pl.BlockSpec((1, length, hdv), row),
                pl.BlockSpec((1, length, 1), row),
                pl.BlockSpec((1, 1, block), lambda i, j, *_: (i, 0, j)),
                pl.BlockSpec((nb, block, block), whole),
                pl.BlockSpec((1, TABLE_LANES), lambda i, j, *_: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, length, hd), row),
                pl.BlockSpec((1, block, hd), blk),
                pl.BlockSpec((1, block, hdv), blk),
                pl.BlockSpec((nb, block, block), whole),
                pl.BlockSpec((1, TABLE_LANES), lambda i, j, *_: (0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, length, hd), f32),
            jax.ShapeDtypeStruct((b, length, hd), f32),
            jax.ShapeDtypeStruct((b, length, hdv), f32),
            jax.ShapeDtypeStruct((nb, block, block), f32),
            jax.ShapeDtypeStruct((1, TABLE_LANES), f32),
        ],
        compiler_params=_params(sequential=True),
        interpret=interpret,
        name="hstu_attention_bwd",
    )(start, q, k, v, do, tq, tk, p_blocks, wt)

"""Public wrapper for HSTU's pointwise attention kernel: the bias tables in,
the attention out, with its own backward (``jax.custom_vjp``). Everything
it runs, the position-bias tiles built from the table included, is under
``jax.named_scope("hstu_attention")``, forward and backward."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import runtime
from repro.kernels.hstu_attention.hstu_attention import (
    TABLE_LANES,
    hstu_attention_bwd,
    hstu_attention_fwd,
)

BLOCK = 256
SCOPE = "hstu_attention"


def block_for(seq_len: int, block: int = BLOCK) -> int:
    """The block the kernel runs at for rows of ``seq_len``."""
    return min(block, seq_len)


def toeplitz_blocks(rab_p: jax.Array, block: int) -> jax.Array:
    """(L,) bias by offset ``i - j`` -> (L/T, T, T) tiles, tile ``d``
    holding ``rab_p[d*T + a - b]`` at ``(a, b)`` (0 where the offset is
    negative). Pads, reshapes and slices only, so its transpose is too."""
    t = block
    nb = rab_p.shape[0] // t
    blocks = rab_p.reshape(nb, t)
    prev = jnp.concatenate([jnp.zeros((1, t), rab_p.dtype), blocks[:-1]], 0)
    # offsets d*T - (T-1) .. d*T + T-1, reversed and padded to 2T
    u = jnp.concatenate([prev[:, 1:], blocks], 1)[:, ::-1]
    u = jnp.pad(u, ((0, 0), (0, 1)))
    flat = jnp.broadcast_to(u[:, None, :], (nb, t, 2 * t)).reshape(nb, -1)
    skew = flat[:, :t * (2 * t - 1)].reshape(nb, t, 2 * t - 1)
    return skew[:, :, t - 1:]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _attend(block, heads, n_buckets, start, q, k, v, tq, tk, p_blocks, wt):
    return hstu_attention_fwd(start, q, k, v, tq, tk, p_blocks, wt,
                              block=block, heads=heads, n_buckets=n_buckets,
                              interpret=runtime.interpret_default())


def _attend_fwd(block, heads, n_buckets, start, q, k, v, tq, tk, p_blocks,
                wt):
    out = _attend(block, heads, n_buckets, start, q, k, v, tq, tk, p_blocks,
                  wt)
    return out, (start, q, k, v, tq, tk, p_blocks, wt)


def _attend_bwd(block, heads, n_buckets, res, do):
    start, q, k, v, tq, tk, p_blocks, wt = res
    with jax.named_scope(SCOPE):
        dq, dk, dv, dp, dwt = hstu_attention_bwd(
            start, q, k, v, do, tq, tk, p_blocks, wt, block=block,
            heads=heads, n_buckets=n_buckets,
            interpret=runtime.interpret_default())
    return (None, dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, dp, dwt)


_attend.defvjp(_attend_fwd, _attend_bwd)


def hstu_attention(q: jax.Array, k: jax.Array, v: jax.Array, ts: jax.Array,
                   start: jax.Array, rab_p: jax.Array, rab_t: jax.Array,
                   block: int = BLOCK) -> jax.Array:
    """HSTU attention over right-aligned rows.

    q, k: (B, L, H, dqk); v: (B, L, H, dv); ts: (B, L) int32 seconds,
    oldest first; start: (B,) int32, the first valid position (L - n);
    rab_p: (L,) float32 bias by offset ``i - j``; rab_t: (n_buckets,)
    float32 time table, at most 129 entries. Returns (B, L, H, dv) in
    ``v``'s dtype, zero at padding positions."""
    b, length, heads, dqk = q.shape
    dv = v.shape[-1]
    n_buckets = rab_t.shape[0]
    if n_buckets > 129:
        raise ValueError(f"a time table of {n_buckets} entries: the kernel "
                         "looks up at most 129")
    t = block_for(length, block)
    with jax.named_scope(SCOPE):
        ts = ts.astype(jnp.int32)
        tn = jnp.concatenate([ts[:, 1:], ts[:, -1:]], axis=1)
        wt = jnp.pad(rab_t.astype(jnp.float32),
                     (0, TABLE_LANES - n_buckets))[None]
        out = _attend(t, heads, n_buckets, start.astype(jnp.int32),
                      q.reshape(b, length, heads * dqk),
                      k.reshape(b, length, heads * dqk),
                      v.reshape(b, length, heads * dv),
                      tn[:, :, None], ts[:, None, :],
                      toeplitz_blocks(rab_p.astype(jnp.float32), t), wt)
        return out.reshape(b, length, heads, dv)

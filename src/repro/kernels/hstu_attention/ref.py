"""Pure-jnp oracle of HSTU's pointwise attention: the dense (L, L) form,
with every pair's bias looked up from the tables."""
import jax
import jax.numpy as jnp


def hstu_attention(q, k, v, ts, start, rab_p, rab_t):
    """Same contract as ``ops.hstu_attention``."""
    b, length = q.shape[:2]
    i = jnp.arange(length)[:, None]
    j = jnp.arange(length)[None, :]
    tn = jnp.concatenate([ts[:, 1:], ts[:, -1:]], axis=1)
    diff = jnp.abs((tn[:, :, None] - ts[:, None, :]).astype(jnp.float32))
    bucket = jnp.floor(jnp.log(jnp.maximum(diff, 1.0)) / 0.301)
    bucket = jnp.clip(bucket.astype(jnp.int32), 0, rab_t.shape[0] - 1)
    bias = rab_p[jnp.clip(i - j, 0, length - 1)][None] + rab_t[bucket]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) + bias[:, None]
    mask = (j <= i)[None] & (j[None] >= start[:, None, None])
    a = jnp.where(mask[:, None], jax.nn.silu(s) / length, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", a,
                      v.astype(jnp.float32)).astype(v.dtype)

"""Pure-jnp oracle for the flash-decode kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def flash_decode_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array) -> jax.Array:
    """q [B,KV,G,hd] vs cache [B,KV,hd,T] (T last, the serving cache's
    layout) with per-seq frontier masking."""
    hd = q.shape[-1]
    s = jnp.einsum("bkgd,bkdt->bkgt", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / (hd ** 0.5)
    t = k_cache.shape[-1]
    mask = jnp.arange(t)[None, :] < lengths[:, None]          # [B, T]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgt,bkdt->bkgd", p,
                      v_cache.astype(jnp.float32)).astype(q.dtype)

"""jit'd GQA-aware wrappers around the flash-attention kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "use_kernel",
                                             "interpret"))
def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, use_kernel: bool = True,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Grouped-query attention: q [B, Hq, S, d], k/v [B, Hkv, Skv, d]."""
    hq, hkv = q.shape[1], k.shape[1]
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    fn = flash_attention if use_kernel else attention_ref
    kw = {"interpret": interpret} if use_kernel else {}
    return fn(q, k, v, causal=causal, **kw)


__all__ = ["flash_attention", "attention_ref", "gqa_attention"]

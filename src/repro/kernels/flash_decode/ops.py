"""jit'd wrapper: drop-in decode attention for the serving path."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .flash_decode import flash_decode
from .ref import flash_decode_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *, use_kernel: bool = True,
                     interpret: Optional[bool] = None):
    if use_kernel:
        return flash_decode(q, k_cache, v_cache, lengths,
                            interpret=interpret)
    return flash_decode_ref(q, k_cache, v_cache, lengths)


__all__ = ["flash_decode", "flash_decode_ref", "decode_attention"]

"""Pallas TPU kernels (validated against jnp oracles):

  stencil         3x3 window pipelines — the dense CGRA benchmarks' compute
  flash_attention blocked online-softmax attention (prefill/train)
  flash_decode    single-token cache attention (the serving memory wall)

Every wrapper takes ``interpret=None``: the kernel is compiled by Mosaic
when the default backend is a TPU and run by the Pallas interpreter on any
other backend (the CPU tests).  Pass a bool to force one or the other.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret unless the default backend is a TPU."""
    return jax.default_backend() != "tpu" if interpret is None else interpret

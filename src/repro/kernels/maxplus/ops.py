"""jit'd public wrappers around the max-plus Pallas kernel.

``longest_path`` is the STA entry point: given the dense max-plus adjacency
built by ``repro.core.sta.timing_matrix`` it returns per-vertex worst-case
arrival times.  The relaxation is run as blocked matmuls so the whole
iteration stays on-device; vertex counts in real designs are a few thousand,
so we batch the arrival vector into a [K, lanes] tile to keep the kernel's
N dimension lane-aligned instead of doing skinny matvecs.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .maxplus import NEG_INF, maxplus_matmul
from .ref import longest_path_ref, maxplus_matmul_ref


@functools.partial(jax.jit, static_argnames=("src", "use_kernel", "interpret"))
def longest_path(m: jax.Array, src: int = 0, *, use_kernel: bool = True,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Worst-case arrival time of every vertex from ``src``.

    m[i, j] = delay of edge j -> i, NEG_INF when absent.  Runs the max-plus
    relaxation with doubling: M2 = M (x) M collapses two relaxation steps,
    so the fixpoint needs ceil(log2(diameter)) matmuls instead of diameter
    matvecs — the right trade on the TPU where one big matmul beats many
    skinny ones.
    """
    if not use_kernel:
        return longest_path_ref(m, src)
    n = m.shape[0]
    # I (+) M in the semiring: max(M, identity-with-0-diagonal)
    eye = jnp.where(jnp.eye(n, dtype=bool), 0.0, NEG_INF).astype(m.dtype)
    step = jnp.maximum(m, eye)

    # repeated squaring to the closure: (I+M)^(2^ceil(log2 n))
    n_doublings = max(1, math.ceil(math.log2(max(n, 2))))
    closure = step
    for _ in range(n_doublings):
        closure = maxplus_matmul(closure, closure, interpret=interpret)

    arr = jnp.full((n,), NEG_INF, m.dtype).at[src].set(0.0)
    return jnp.max(closure + arr[None, :], axis=1)


__all__ = ["longest_path", "maxplus_matmul", "maxplus_matmul_ref",
           "longest_path_ref", "NEG_INF"]

"""Pallas TPU kernel for max-plus (tropical) matrix multiplication.

Static timing analysis is longest-path on a DAG, which is a fixpoint of the
max-plus relaxation ``arr' = M (x) arr`` where ``(M (x) v)[i] = max_j
(M[i,j] + v[j])``.  The post-PnR pipelining pass re-runs STA after every
register insertion, making this the compiler's hot spot — and max-plus matmul
blocks exactly like a GEMM, so it tiles onto the TPU memory hierarchy the
same way (HBM -> VMEM tiles -> VPU max/add; the MXU cannot help because the
semiring replaces multiply/accumulate with add/max).

Tiling: grid (M/bm, N/bn, K/bk); the K axis is the innermost (sequential on
TPU) grid dimension, accumulating into the output tile, which stays resident
in VMEM across the K steps.  Block sizes default to 128 (lane-aligned) and
the inner product is a fori_loop of [bm, bn] VPU maximum updates, so peak
VMEM = bm*bk + bk*bn + bm*bn floats (~192 KB at 128^3) — far under ~16 MB.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e9


def _maxplus_kernel(a_ref, b_ref, o_ref, *, bk: int):
    """One (bm, bn) output tile: o = max(o, max_k(a[:, k] + b[k, :]))."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    a = a_ref[...]          # [bm, bk]
    b = b_ref[...]          # [bk, bn]

    def body(k, acc):
        # [bm, 1] + [1, bn] -> [bm, bn] add/max on the VPU
        return jnp.maximum(acc, a[:, k][:, None] + b[k, :][None, :])

    acc = jax.lax.fori_loop(0, bk, body, o_ref[...])
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def maxplus_matmul(a: jax.Array, b: jax.Array, *, bm: int = 128,
                   bn: int = 128, bk: int = 128,
                   interpret: Optional[bool] = None) -> jax.Array:
    """C[i, j] = max_k (A[i, k] + B[k, j]) over the (max, +) semiring.

    Inputs are padded with NEG_INF to block multiples; NEG_INF is the
    semiring zero so padding never affects the result.
    """
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad shapes {a.shape} x {b.shape}")
    m, k = a.shape
    _, n = b.shape
    dtype = jnp.promote_types(a.dtype, b.dtype)
    mp, kp, np_ = (-(-m // bm) * bm, -(-k // bk) * bk, -(-n // bn) * bn)
    a = jnp.pad(a.astype(dtype), ((0, mp - m), (0, kp - k)),
                constant_values=NEG_INF)
    b = jnp.pad(b.astype(dtype), ((0, kp - k), (0, np_ - n)),
                constant_values=NEG_INF)

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_maxplus_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), dtype),
        interpret=resolve_interpret(interpret),
        name="maxplus_matmul",
    )(a, b)
    return out[:m, :n]

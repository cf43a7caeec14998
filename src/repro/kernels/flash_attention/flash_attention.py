"""Pallas TPU flash-attention kernel (blocked online softmax).

Used by the LM substrate for long-sequence prefill: materializing the
[S, S] score matrix at 32k tokens is impossible, so scores are computed one
(bq, bk) tile at a time with the running (max, sum, weighted-accumulator)
online-softmax state held in VMEM scratch across the KV grid steps.

Grid: (batch*q_heads, S/bq, S/bk) with the KV axis innermost (sequential on
TPU), so (m, l, acc) scratch persists across KV steps of one Q tile.  Q/K/V
tiles are MXU matmuls ([bq, d] @ [d, bk] and [bq, bk] @ [bk, d]); masking and
the online-softmax rescale run on the VPU.  Peak VMEM per step is
q + k + v + o tiles + scratch = (3*bq + 2*bk) * d + 2*bq floats (~0.5 MB at
128/128/128) — the whole 32k x 32k problem streams through without ever
holding a score matrix.

GQA is handled by the wrapper (K/V heads repeated to the q-head count before
the call), keeping the kernel itself single-head-layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq: int, bk: int, scale: float, causal: bool,
                  kv_len: int, kv_steps: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip KV tiles entirely in the causal future of this Q tile
    run = (ki * bk) <= (qi * bq + bq - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)       # [bq, d]
        k = k_ref[0].astype(jnp.float32)       # [bk, d]
        v = v_ref[0].astype(jnp.float32)       # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < kv_len                   # dead padded keys
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = mask & (rows >= cols)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                    # [bq]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)        # rescale of old state
        p = jnp.exp(s - m_cur[:, None])        # [bq, bk]
        l_cur = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_cur
        l_scr[...] = l_cur
        acc_scr[...] = acc

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)     # fully-masked rows -> 0 output
        o_ref[0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Attention over q [B, H, S, d] with k, v [B, H, Skv, d].

    H must already equal the q-head count (GQA callers repeat K/V heads).
    S and Skv are padded to block multiples; padded key positions are masked
    inside the kernel, padded query rows are sliced off.
    """
    b, h, sq, d = q.shape
    skv = k.shape[2]
    if k.shape != (b, h, skv, d) or v.shape != (b, h, skv, d):
        raise ValueError(f"shape mismatch {q.shape} {k.shape} {v.shape}")
    scale = 1.0 / (d ** 0.5)
    sqp, skp = -(-sq // bq) * bq, -(-skv // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skp - skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skp - skv), (0, 0)))
    qp = qp.reshape(b * h, sqp, d)
    kp = kp.reshape(b * h, skp, d)
    vp = vp.reshape(b * h, skp, d)

    kv_steps = skp // bk
    grid = (b * h, sqp // bq, kv_steps)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale,
                          causal=causal, kv_len=skv, kv_steps=kv_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bhi, qi, ki: (bhi, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, qi, ki: (bhi, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bhi, qi, ki: (bhi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bhi, qi, ki: (bhi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),        # running max
            pltpu.VMEM((bq,), jnp.float32),        # running denominator
            pltpu.VMEM((bq, d), jnp.float32),      # weighted accumulator
        ],
        interpret=resolve_interpret(interpret),
        name="flash_attention",
    )(qp, kp, vp)
    return out.reshape(b, h, sqp, d)[:, :, :sq, :]

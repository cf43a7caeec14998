"""Pallas TPU kernel for single-token decode attention over a KV cache.

H2 (EXPERIMENTS.md §Perf) showed decode is memory-wall-bound once sharding
is fixed: the step reads the whole KV cache.  This kernel is the TPU-native
decode path — it streams the cache through VMEM exactly once per step in
[bk, hd] tiles, carrying the online-softmax state in scratch, and never
materializes scores in HBM (the XLA einsum path writes the [B,H,T] score
row + softmax temporaries back to HBM).

Layout matches the serving cache ([B, KV, T, hd], the H2 layout-fix
convention): no transposes.  Grid: (B*KV, T/bk) with the KV-block axis
innermost/sequential; q for all G group-heads of one kv head rides in VMEM
across the sweep.  The per-row frontier is a scalar-prefetch operand held in
SMEM: a (1, 1) VMEM block of a [B*KV, 1] array breaks the TPU's (8, 128)
block-tiling rule and Mosaic refuses it.  Peak VMEM per step = k + v tiles + q + acc ≈
2*bk*hd + 2*G*hd floats (~130 KB at bk=256, hd=128, G=8).
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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, bk: int, scale: float, kv_steps: int):
    row = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # [G, hd]
    k = k_ref[0].astype(jnp.float32)            # [bk, hd]
    v = v_ref[0].astype(jnp.float32)            # [bk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # mask cache slots at/after the frontier            [G, bk]
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < len_ref[row], s, NEG_INF)

    m_prev = m_scr[...]                         # [G]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])             # [G, bk]
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, *, bk: int = 256,
                 interpret: Optional[bool] = None) -> jax.Array:
    """One-token GQA decode attention, cache-layout native.

    q:        [B, KV, G, hd]   (new token's query, grouped by kv head)
    k_cache:  [B, KV, T, hd]
    v_cache:  [B, KV, T, hd]
    lengths:  [B]  int32       (per-sequence frontier; slots >= len masked)
    returns   [B, KV, G, hd]
    """
    b, kv, g, hd = q.shape
    t = k_cache.shape[2]
    if k_cache.shape != (b, kv, t, hd) or v_cache.shape != (b, kv, t, hd):
        raise ValueError(f"bad shapes {q.shape} {k_cache.shape}")
    scale = 1.0 / (hd ** 0.5)
    tp = -(-t // bk) * bk
    kp = jnp.pad(k_cache, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    vp = jnp.pad(v_cache, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    qf = q.reshape(b * kv, g, hd)
    kf = kp.reshape(b * kv, tp, hd)
    vf = vp.reshape(b * kv, tp, hd)
    lens = jnp.repeat(lengths.astype(jnp.int32), kv)            # [B*KV]

    kv_steps = tp // bk
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk, scale=scale,
                          kv_steps=kv_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * kv, kv_steps),
            in_specs=[
                pl.BlockSpec((1, g, hd), lambda i, ki, lens: (i, 0, 0)),
                pl.BlockSpec((1, bk, hd), lambda i, ki, lens: (i, ki, 0)),
                pl.BlockSpec((1, bk, hd), lambda i, ki, lens: (i, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, g, hd), lambda i, ki, lens: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g,), jnp.float32),       # running max
                pltpu.VMEM((g,), jnp.float32),       # denominator
                pltpu.VMEM((g, hd), jnp.float32),    # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * kv, g, hd), q.dtype),
        interpret=resolve_interpret(interpret),
        name="flash_decode",
    )(lens, qf, kf, vf)
    return out.reshape(b, kv, g, hd)

"""Pallas TPU kernel for single-token decode attention over a KV cache.

H2 (EXPERIMENTS.md §Perf) showed decode is memory-wall-bound once sharding
is fixed: the step reads the whole KV cache.  This kernel is the TPU-native
decode path — it streams the cache through VMEM exactly once per step in
[hd, bk] tiles, carrying the online-softmax state in scratch, and never
materializes scores in HBM (the XLA einsum path writes the [B,H,T] score
row + softmax temporaries back to HBM).

Layout matches the serving cache, [B, KV, hd, T] with T last: for a
64-wide head dim the chip's default layout of [.., T, hd] puts T minor
anyway (so hd is not padded to 128 lanes), and with T last that physical
layout is row-major and lane-dense, so the kernel reads the cache as it
lies.  The cache may be one layer's 4-D cache or the decode step's
stacked [L, B, KV, hd, T] cache with a layer index: the layer is a
second scalar-prefetch operand that the blocks' index_map reads, so the
step never slices a layer out of the stack.  Grid: (B*KV, cdiv(T, bk))
with the KV-block axis innermost/sequential; q for all G group-heads of
one kv head rides in VMEM across the sweep.  When bk does not divide T
the last block is partial: its columns past T are undefined, so columns
at or past the frontier are masked in the scores and zeroed in v (no pad
of the cache).  The per-row frontier is a scalar-prefetch operand held
in SMEM: a (1, 1) VMEM block of a [B*KV, 1] array breaks the TPU's
(8, 128) block-tiling rule and Mosaic refuses it.  Peak VMEM per step =
k + v tiles + q + acc ~= 2*bk*hd + 2*G*hd floats (~130 KB at bk=256,
hd=128, G=8).
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


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                   l_scr, acc_scr, *, bk: int, scale: float, kv_steps: int,
                   ragged: bool):
    del layer_ref                               # read by the index_maps
    row = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # [G, hd]
    k = k_ref[0].astype(jnp.float32)            # [hd, bk]
    v = v_ref[0].astype(jnp.float32)            # [hd, bk]
    s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # mask cache slots at/after the frontier            [G, bk]
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < len_ref[row], s, NEG_INF)
    if ragged:
        # the last block runs past T, where v is undefined (maybe NaN,
        # which p = 0 would not cancel)
        vcols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        v = jnp.where(vcols < len_ref[row], v, 0.0)

    m_prev = m_scr[...]                         # [G]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])             # [G, bk]
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_cur

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, layer: Optional[jax.Array] = None, *,
                 bk: int = 256, interpret: Optional[bool] = None
                 ) -> jax.Array:
    """One-token GQA decode attention, cache-layout native.

    q:        [B, KV, G, hd]   (new token's query, grouped by kv head)
    k_cache:  [B, KV, hd, T], or the stacked [L, B, KV, hd, T] with layer
    v_cache:  same shape as k_cache
    lengths:  [B]  int32       (per-sequence frontier; slots >= len masked)
    layer:    int32 scalar     (the layer of a stacked cache to read)
    returns   [B, KV, G, hd]

    The cache is read where it lies, in (1, hd, bk) blocks; bk is T when T
    is at most bk.
    """
    b, kv, g, hd = q.shape
    t = k_cache.shape[-1]
    if (k_cache.ndim != (4 if layer is None else 5)
            or k_cache.shape[-4:] != (b, kv, hd, t)
            or v_cache.shape != k_cache.shape):
        raise ValueError(f"bad shapes {q.shape} {k_cache.shape} "
                         f"{v_cache.shape} (layer given: {layer is not None})")
    scale = 1.0 / (hd ** 0.5)
    bk = min(bk, t)
    kv_steps = -(-t // bk)
    rows = b * kv
    qf = q.reshape(rows, g, hd)
    kf = k_cache.reshape(-1, hd, t)             # [L*B*KV, hd, T]: a bitcast
    vf = v_cache.reshape(-1, hd, t)
    lens = jnp.repeat(lengths.astype(jnp.int32), kv)            # [B*KV]
    at = jnp.zeros((1,), jnp.int32) if layer is None else (
        jnp.asarray(layer, jnp.int32).reshape(1))

    def cache_block(i, ki, lens, at):
        return (at[0] * rows + i, 0, ki)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk, scale=scale,
                          kv_steps=kv_steps, ragged=t % bk != 0),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, kv_steps),
            in_specs=[
                pl.BlockSpec((1, g, hd), lambda i, ki, lens, at: (i, 0, 0)),
                pl.BlockSpec((1, hd, bk), cache_block),
                pl.BlockSpec((1, hd, bk), cache_block),
            ],
            out_specs=pl.BlockSpec((1, g, hd),
                                   lambda i, ki, lens, at: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g,), jnp.float32),       # running max
                pltpu.VMEM((g,), jnp.float32),       # denominator
                pltpu.VMEM((g, hd), jnp.float32),    # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, g, hd), q.dtype),
        interpret=resolve_interpret(interpret),
        name="flash_decode",
    )(lens, at, qf, kf, vf)
    return out.reshape(b, kv, g, hd)

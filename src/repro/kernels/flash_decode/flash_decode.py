"""Pallas TPU kernel for single-token decode attention over a KV cache.

H2 (EXPERIMENTS.md §Perf) showed decode is memory-wall-bound once sharding
is fixed: the step reads the whole KV cache.  This kernel is the TPU-native
decode path — it streams each sequence's live cache through VMEM once per
step, carrying the online-softmax state in scratch, and never materializes
scores in HBM (the XLA einsum path writes the [B,H,T] score row + softmax
temporaries back to HBM).

Layout matches the serving cache, [B, KV, hd, T] with T last: for a
64-wide head dim the chip's default layout of [.., T, hd] puts T minor
anyway (so hd is not padded to 128 lanes), and with T last that physical
layout is row-major and lane-dense, so the kernel reads the cache as it
lies.  The cache may be one layer's 4-D cache or the decode step's
stacked [L, B, KV, hd, T] cache with a layer index: the layer is a
second scalar-prefetch operand that the blocks' index_map reads, so the
step never slices a layer out of the stack.

Grid: (B, cdiv(T, bk)) with the block axis innermost/sequential.  A grid
step takes all KV heads of one sequence, which share its frontier:
(1, KV, hd, bk) blocks of K and of V, and q and the output in
(1, KV, G, hd) blocks that stay in VMEM across the sweep.  The per-row
frontier (``lengths``, a scalar-prefetch operand in SMEM) enters the
blocks' index_map: a row's n = cdiv(len, bk) live blocks take the last n
steps of its sweep, and every step before them is clamped onto block 0,
max(ki - (kv_steps - n), 0).  Consecutive steps on one block index make
the pipeline copy nothing, a ``pl.when`` skips their compute, and the
row's last live step prefetches the next row's first block.  Columns at
or past the frontier (the live block's tail, or the undefined columns
past T of a partial last block) are masked in the scores and zeroed in
v: stale or non-finite cache there never reaches the output.

bk comes from the shape (``decode_plan``): the largest 128 x 2^j columns
whose VMEM, per column 2 (K, V) x 2 (double buffer) x KV x hd x itemsize
for the blocks plus 2 x KV x hd x 4 (v in float32, then masked) and
4 x KV x max(G, 8) x 4 (scores, mask, p, column index) for the body's
temporaries, stays within ``VMEM_BUDGET`` (8 MiB, half the v5e's default
scoped VMEM); T itself when that block covers T.  Granite's 8 x 64 heads
take bk = 512 (1 MB of K and V a step, 4.7 MB in all), zamba2's 8 x 224
a chip bk = 256 (7.6 MB).  Scores, the running max and sum, and p @ v
accumulate in float32; q and k enter their product as they come
(bfloat16 when serving) with float32 results, the exact products; v is
widened to float32 and p stays float32.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
#: VMEM that one grid step's blocks and temporaries may take
VMEM_BUDGET = 8 * 2 ** 20


def step_vmem_bytes(kv: int, g: int, hd: int, bk: int, itemsize: int) -> int:
    """VMEM of one grid step's K and V blocks and body temporaries."""
    return bk * (4 * kv * hd * itemsize + 2 * kv * hd * 4
                 + 4 * kv * max(g, 8) * 4)


def decode_plan(kv: int, g: int, hd: int, t: int,
                itemsize: int) -> Tuple[int, int]:
    """(bk, kv_steps) for a cache of KV heads of hd x T and G query heads
    per KV head: the largest 128 x 2^j columns within ``VMEM_BUDGET``,
    or T where that covers it."""
    bk = 128
    while bk < t and step_vmem_bytes(kv, g, hd, 2 * bk,
                                     itemsize) <= VMEM_BUDGET:
        bk *= 2
    bk = min(bk, t)
    return bk, -(-t // bk)


def _live_blocks(length, bk: int, kv_steps: int):
    """Blocks of a row that hold columns below its frontier (at least one,
    at most the grid's)."""
    return jnp.clip((length + bk - 1) // bk, 1, kv_steps)


def _decode_kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                   l_scr, acc_scr, *, bk: int, scale: float, kv_steps: int):
    del layer_ref                               # read by the index_maps
    length = len_ref[pl.program_id(0)]
    ki = pl.program_id(1)
    # the row's live blocks take the last steps of its sweep
    blk = ki - (kv_steps - _live_blocks(length, bk, kv_steps))

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(jnp.logical_and(blk >= 0, length > 0))
    def _step():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((2,), (1,)), ((0,), (0,))),  # q . k
            preferred_element_type=jnp.float32) * scale      # [KV, G, bk]
        # mask cache slots at/after the frontier
        cols = blk * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(cols < length, s, NEG_INF)
        v = v_ref[0].astype(jnp.float32)        # [KV, hd, bk]
        vcols = blk * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 2)
        v = jnp.where(vcols < length, v, 0.0)   # p = 0 would not cancel NaN

        m_prev = m_scr[...]                     # [KV, G, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)                  # [KV, G, bk]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)             # [KV, G, hd]
        m_scr[...] = m_cur

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, layer: Optional[jax.Array] = None, *,
                 bk: Optional[int] = None, interpret: Optional[bool] = None
                 ) -> jax.Array:
    """One-token GQA decode attention, cache-layout native.

    q:        [B, KV, G, hd]   (new token's query, grouped by kv head)
    k_cache:  [B, KV, hd, T], or the stacked [L, B, KV, hd, T] with layer
    v_cache:  same shape as k_cache
    lengths:  [B]  int32       (per-sequence frontier; slots >= len masked)
    layer:    int32 scalar     (the layer of a stacked cache to read)
    bk:       columns a grid step reads; ``decode_plan``'s by default
    returns   [B, KV, G, hd]

    The cache is read where it lies, in (1, KV, hd, bk) blocks, up to each
    sequence's frontier; bk is T when T is at most bk.
    """
    b, kv, g, hd = q.shape
    t = k_cache.shape[-1]
    if (k_cache.ndim != (4 if layer is None else 5)
            or k_cache.shape[-4:] != (b, kv, hd, t)
            or v_cache.shape != k_cache.shape):
        raise ValueError(f"bad shapes {q.shape} {k_cache.shape} "
                         f"{v_cache.shape} (layer given: {layer is not None})")
    scale = 1.0 / (hd ** 0.5)
    if bk is None:
        bk, kv_steps = decode_plan(kv, g, hd, t, k_cache.dtype.itemsize)
    else:
        bk = min(bk, t)
        kv_steps = -(-t // bk)
    kf = k_cache.reshape(-1, kv, hd, t)         # [L*B, KV, hd, T]: a bitcast
    vf = v_cache.reshape(-1, kv, hd, t)
    lens = lengths.astype(jnp.int32)
    at = jnp.zeros((1,), jnp.int32) if layer is None else (
        jnp.asarray(layer, jnp.int32).reshape(1))

    def cache_block(i, ki, lens, at):
        first = kv_steps - _live_blocks(lens[i], bk, kv_steps)
        return (at[0] * b + i, 0, 0, jnp.maximum(ki - first, 0))

    def row_block(i, ki, lens, at):
        return (i, 0, 0, 0)

    return pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk, scale=scale,
                          kv_steps=kv_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv_steps),
            in_specs=[
                pl.BlockSpec((1, kv, g, hd), row_block),
                pl.BlockSpec((1, kv, hd, bk), cache_block),
                pl.BlockSpec((1, kv, hd, bk), cache_block),
            ],
            out_specs=pl.BlockSpec((1, kv, g, hd), row_block),
            scratch_shapes=[
                pltpu.VMEM((kv, g, 1), jnp.float32),     # running max
                pltpu.VMEM((kv, g, 1), jnp.float32),     # denominator
                pltpu.VMEM((kv, g, hd), jnp.float32),    # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=resolve_interpret(interpret),
        name="flash_decode",
    )(lens, at, q, kf, vf)

"""Pallas TPU kernel for 3x3 stencils (the dense CGRA benchmark compute).

The paper's dense benchmarks (Gaussian, unsharp, Harris, camera pipeline)
are 3x3 window pipelines; this kernel is the TPU-native version of that
compute, used by the end-to-end examples to produce golden outputs the CGRA
functional simulator is checked against.

Tiling strategy (TPU memory hierarchy, no native halo exchange in
BlockSpec): the caller pads the image by 1 pixel and passes THREE
row-shifted views (rows r, r+1, r+2 of the padded image).  Each view gets an
identical BlockSpec of (bh, W+2) so every grid step holds a (bh, W+2) strip
of each vertical tap in VMEM; horizontal taps are in-block static slices.
The 9-term weighted sum runs on the VPU; peak VMEM is 4 strips —
(3 inputs + 1 output) * bh * (W+2) * 4 B, ~5.3 MB at bh=128, W=2560.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _stencil_kernel(x0_ref, x1_ref, x2_ref, w_ref, o_ref, *, width: int):
    w = w_ref[...]  # [3, 3]
    rows = (x0_ref[...], x1_ref[...], x2_ref[...])   # each [bh, W+2]
    acc = jnp.zeros_like(o_ref)
    for dy in range(3):
        for dx in range(3):
            acc = acc + w[dy, dx] * jax.lax.dynamic_slice_in_dim(
                rows[dy], dx, width, axis=1)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def stencil3x3(x: jax.Array, w: jax.Array, *, bh: int = 128,
               interpret: Optional[bool] = None) -> jax.Array:
    """Same-padded 3x3 correlation of a [H, W] image with a [3, 3] kernel."""
    if x.ndim != 2 or w.shape != (3, 3):
        raise ValueError(f"bad shapes {x.shape}, {w.shape}")
    h, width = x.shape
    hp = -(-h // bh) * bh
    xp = jnp.pad(x, ((1, 1 + hp - h), (1, 1)))       # zero halo + row padding
    x0 = xp[0:hp, :]
    x1 = xp[1:hp + 1, :]
    x2 = xp[2:hp + 2, :]
    w = w.astype(x.dtype)

    strip = pl.BlockSpec((bh, width + 2), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_stencil_kernel, width=width),
        grid=(hp // bh,),
        in_specs=[strip, strip, strip,
                  pl.BlockSpec((3, 3), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bh, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((hp, width), x.dtype),
        interpret=resolve_interpret(interpret),
        name="stencil3x3",
    )(x0, x1, x2, w)
    return out[:h]

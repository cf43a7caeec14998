"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — the dry-run launcher
sets XLA_FLAGS for 512 host devices *before* any jax initialization, and
smoke tests import the same module under the default single device.

Mesh shapes:
  single-pod : (16, 16)    axes ("data", "model")   — 256 chips (one v5e pod)
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") — 512 chips

The "model" axis carries tensor/expert parallelism (intra-pod, ICI-local by
construction); "data"/"pod" carry data parallelism (gradient all-reduces
cross pods over DCI — exactly the traffic the gradient-compression lever
targets).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    # the model resolves logical axes to PartitionSpecs and leaves layout
    # propagation to XLA, which needs Auto axes (make_mesh defaults to
    # Explicit, under which contracting over a sharded dim is an error)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_mesh_for(devices: Optional[int] = None, model_parallel: int = 16):
    """(data, model) mesh over the first `devices` visible chips (default:
    all of them), tensor parallel over up to `model_parallel` — what the
    serve and train launchers run on."""
    devs = jax.devices()[:devices] if devices else jax.devices()
    n = len(devs)
    mp = min(model_parallel, n)
    while n % mp:
        mp -= 1
    return _auto_mesh((n // mp, mp), ("data", "model"), devices=devs)

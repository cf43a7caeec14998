"""Environment configuration — the single place Cascade env vars are read.

Benchmarks, tests, and examples all need the same few knobs (where the disk
compile cache lives, how many batch workers to use, debug assertions in the
annealer); hand-rolling ``os.environ`` reads in each driver drifts.  Every
knob lives here and is re-exported from :mod:`repro.core`:

    CASCADE_CACHE_DIR    root of the disk compile cache
                         (default ``~/.cache/cascade-repro``)
    CASCADE_WORKERS      worker count for ``compile_batch`` and the
                         benchmark drivers (default: min(8, cpu count),
                         clamped to the job count)
    CASCADE_DISK_CACHE   truthy -> attach the disk tier to the process-wide
                         ``DEFAULT_CACHE`` at import (benchmarks attach it
                         explicitly regardless)
    CASCADE_PLACE_DEBUG  truthy -> the SA placer re-derives the full cost
                         at every temperature step and asserts the
                         incremental bookkeeping agrees
    CASCADE_POWER_CAP_MW default power budget (mW) for the power-capped
                         pipelining schedule.  Read only by drivers that
                         opt in (``examples/power_capped.py``, benchmark
                         CLIs) and written into the ``PassConfig`` they
                         build — never read inside the compiler itself, so
                         the compile-cache key always reflects the cap.
    CASCADE_PNR_BACKEND  default place-and-route kernel backend for the
                         benchmark/driver CLIs: "scalar", "numpy", or
                         "jax".  Driver-side only, like the power cap —
                         drivers copy it into ``PassConfig.pnr_backend``,
                         the compiler never reads it implicitly.
    CASCADE_SERVICE_BATCH_WINDOW_MS
                         how long the compile service's dispatcher holds
                         the queue open after the first request of a
                         batch, so concurrent arrivals coalesce into one
                         ``compile_batch`` (default 5 ms).  Driver-side
                         only: drivers pass it to the ``CompileService``
                         constructor, the service never reads env vars.
    CASCADE_SERVICE_MAX_BATCH
                         upper bound on requests per dispatched service
                         batch (default 8).  Driver-side only, as above.
    CASCADE_SCHED_LATENCY_WEIGHT
                         default latency weight of the traffic
                         ``objective()`` the online scheduler admits by:
                         requests/s of throughput one millisecond of mean
                         latency is worth (default 1.0).  Driver-side
                         only — drivers pass it into ``replay()`` /
                         ``FabricScheduler``; the library default stays
                         pinned at 1.0.
    CASCADE_HOST_DEVICES host CPU device count exposed to JAX (the
                         ``--xla_force_host_platform_device_count`` XLA
                         flag, snippet-2/bayespec idiom) so the jax
                         backend's parallel-tempering replicas shard
                         across a multi-device mesh even on a CPU-only
                         box.  Must take effect before jax initializes;
                         ``force_host_device_count()`` applies it.
"""

from __future__ import annotations

import os
import warnings
from pathlib import Path
from typing import Optional

_FALSY = ("", "0", "false", "no", "off")


def env_flag(name: str, default: bool = False) -> bool:
    """Boolean env var: unset -> ``default``; "0"/"false"/"no"/"off" -> False."""
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in _FALSY


def cache_dir() -> Path:
    """Disk-cache root: ``CASCADE_CACHE_DIR`` or ``~/.cache/cascade-repro``."""
    root = os.environ.get("CASCADE_CACHE_DIR")
    if root:
        return Path(root).expanduser()
    return Path.home() / ".cache" / "cascade-repro"


def worker_count(jobs: Optional[int] = None, cap: int = 8) -> int:
    """Batch worker count: ``CASCADE_WORKERS`` wins; otherwise min(cap, cpu
    count), never more than ``jobs`` when given, always at least 1.

    The ``jobs`` clamp applies to the env path too — ``CASCADE_WORKERS=8``
    with a 2-job batch still spawns 2 workers, not 8 idle ones — matching
    the contract above (this used to leak the raw env value).
    """
    env = os.environ.get("CASCADE_WORKERS")
    if env:
        try:
            w = int(env)
        except ValueError:
            w = None
        if w is not None:
            if jobs is not None:
                w = min(w, jobs)
            return max(1, w)
    w = min(cap, os.cpu_count() or cap)
    if jobs is not None:
        w = min(w, jobs)
    return max(1, w)


def env_float(name: str, default: Optional[float] = None) -> Optional[float]:
    """Float env var: unset or empty -> ``default``.

    An *unparsable* value also falls back to ``default``, but with a
    ``UserWarning`` naming the variable and the offending value — a typo
    like ``CASCADE_POWER_CAP_MW=250mW`` must not silently compile uncapped.
    """
    v = os.environ.get(name)
    if v is None or not v.strip():
        return default
    try:
        return float(v)
    except ValueError:
        warnings.warn(
            f"ignoring unparsable {name}={v!r} (not a float); "
            f"falling back to default {default!r}",
            UserWarning, stacklevel=2)
        return default


def env_int(name: str, default: Optional[int] = None) -> Optional[int]:
    """Int env var: unset or empty -> ``default``; unparsable values warn
    (naming the variable and value) and fall back, like :func:`env_float`."""
    v = os.environ.get(name)
    if v is None or not v.strip():
        return default
    try:
        return int(v)
    except ValueError:
        warnings.warn(
            f"ignoring unparsable {name}={v!r} (not an int); "
            f"falling back to default {default!r}",
            UserWarning, stacklevel=2)
        return default


def service_batch_window_s(default: float = 0.005) -> float:
    """Dispatcher batch window in *seconds* for the compile service
    (``CASCADE_SERVICE_BATCH_WINDOW_MS``, milliseconds in the env).

    Driver-side only: CLIs pass the value to the ``CompileService``
    constructor — the service itself never reads the environment, so its
    behaviour is fully determined by its arguments.
    """
    ms = env_float("CASCADE_SERVICE_BATCH_WINDOW_MS")
    return default if ms is None else max(0.0, ms / 1e3)


def service_max_batch(default: int = 8) -> int:
    """Max requests per dispatched service batch
    (``CASCADE_SERVICE_MAX_BATCH``), driver-side only; always >= 1."""
    n = env_int("CASCADE_SERVICE_MAX_BATCH", default)
    return max(1, n if n is not None else default)


def sched_latency_weight(default: float = 1.0) -> float:
    """Default objective latency weight for scheduler drivers
    (``CASCADE_SCHED_LATENCY_WEIGHT``).

    Driver-side only: benchmark CLIs pass it into ``replay()`` /
    ``FabricScheduler`` — the library's own default stays pinned at 1.0
    (regression-tested), so cached results and admission decisions never
    depend on ambient environment state.
    """
    w = env_float("CASCADE_SCHED_LATENCY_WEIGHT", default)
    return default if w is None else w


def default_power_cap_mw(default: Optional[float] = None) -> Optional[float]:
    """Default power budget for the power-capped schedule
    (``CASCADE_POWER_CAP_MW``); ``None`` means unconstrained.

    Drivers that honour the knob must copy the value into the
    ``PassConfig`` they compile with (``PassConfig.power_capped(...)``) —
    the compiler never reads it implicitly, keeping cache keys faithful.
    """
    return env_float("CASCADE_POWER_CAP_MW", default)


#: The place-and-route kernel backends (``PassConfig.pnr_backend`` /
#: ``PlaceParams.backend`` / ``RouteParams.backend``).  ``scalar`` and
#: ``numpy`` are the bit-identical SA/A* pair from PR 2; ``jax`` is the
#: jitted parallel-tempering placer + batched wavefront router.
PNR_BACKENDS = ("scalar", "numpy", "jax")


#: The simulator backends (``repro.core.sim`` ``backend=`` argument).
#: ``interpreter`` is the deque-and-dict oracle; ``numpy`` and ``jax``
#: are the vectorized lowerings in :mod:`repro.core.sim_vec`,
#: bit-identical to it over the 16-bit value domain.
SIM_BACKENDS = ("interpreter", "numpy", "jax")


#: The application-STA backends (``PassConfig.sta_backend`` / the
#: ``backend=`` argument of :func:`repro.core.sta.analyze`).  ``scalar``
#: is the node-by-node Python oracle; ``numpy`` runs the lowered
#: level-propagation of :mod:`repro.core.sta_vec`, bit-identical to it
#: (the sampled-delay ``rng`` mode always falls back to scalar).
STA_BACKENDS = ("scalar", "numpy")


def pnr_backend(default: str = "numpy") -> str:
    """Default PnR kernel backend (``CASCADE_PNR_BACKEND``).

    Driver-side only: benchmark CLIs and examples copy the value into the
    ``PassConfig.pnr_backend`` they compile with — the compiler never
    reads it implicitly, keeping cache keys faithful.  An unknown value
    warns and falls back to ``default`` (a typo must not silently switch
    kernels).
    """
    v = os.environ.get("CASCADE_PNR_BACKEND")
    if v is None or not v.strip():
        return default
    v = v.strip().lower()
    if v not in PNR_BACKENDS:
        warnings.warn(
            f"ignoring unknown CASCADE_PNR_BACKEND={v!r} "
            f"(expected one of {PNR_BACKENDS}); falling back to "
            f"{default!r}", UserWarning, stacklevel=2)
        return default
    return v


def host_device_count(n: Optional[int] = None, cap: int = 8) -> int:
    """Resolve the host device count for the JAX mesh.

    ``CASCADE_HOST_DEVICES`` wins when set (explicit ``n`` beats it);
    otherwise 1.  Like :func:`worker_count`, the result is clamped — to
    ``cap`` and to at least 1 — and an unparsable env value warns rather
    than silently meaning one device.  Values above the physical CPU
    count are allowed (XLA happily time-slices virtual host devices; CI
    forces a 2-device mesh on a 1-core box) but warn so a surprising
    oversubscription is visible.
    """
    if n is None:
        v = os.environ.get("CASCADE_HOST_DEVICES")
        if v is None or not v.strip():
            return 1
        try:
            n = int(v)
        except ValueError:
            warnings.warn(
                f"ignoring unparsable CASCADE_HOST_DEVICES={v!r} "
                f"(not an int); falling back to 1 device",
                UserWarning, stacklevel=2)
            return 1
    n = max(1, min(int(n), cap))
    cpus = os.cpu_count() or 1
    if n > cpus:
        warnings.warn(
            f"host_device_count({n}) exceeds the {cpus} physical CPU(s); "
            f"XLA will time-slice the extra host devices",
            UserWarning, stacklevel=2)
    return n


def force_host_device_count(n: Optional[int] = None) -> int:
    """Make host CPUs look like an ``n``-device JAX mesh (bayespec idiom:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    Resolves ``n`` through :func:`host_device_count` and prepends the XLA
    flag to ``XLA_FLAGS`` (replacing any previous forced count).  Only
    effective *before* jax initializes its backends — if jax is already
    imported the flag cannot take effect any more, so this warns and
    leaves the environment unchanged.  Returns the resolved count.
    """
    import sys

    n = host_device_count(n)
    if "jax" in sys.modules:
        import jax
        live = len(jax.devices())
        if live != n:
            warnings.warn(
                f"force_host_device_count({n}) called after jax "
                f"initialized with {live} device(s); the XLA flag cannot "
                f"take effect any more", UserWarning, stacklevel=2)
        return live
    flag = f"--xla_force_host_platform_device_count={n}"
    prev = [p for p in os.environ.get("XLA_FLAGS", "").split()
            if not p.startswith("--xla_force_host_platform_device_count")]
    os.environ["XLA_FLAGS"] = " ".join(prev + [flag]).strip()
    return n


def devices() -> list:
    """The live JAX device list (imports jax on first use).

    Call :func:`force_host_device_count` first to widen a CPU-only mesh;
    once jax is imported the device count is frozen for the process.
    """
    import jax

    return jax.devices()


def disk_cache_enabled(default: bool = False) -> bool:
    return env_flag("CASCADE_DISK_CACHE", default)


def place_debug(default: bool = False) -> bool:
    return env_flag("CASCADE_PLACE_DEBUG", default)

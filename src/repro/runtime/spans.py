"""Host spans on the profiler's clock.

``span(name, **attrs)`` is a context manager that enters
``jax.profiler.TraceAnnotation(name, **attrs)`` and measures its own
``perf_counter`` seconds, readable as ``.seconds`` after exit.  While a
profiler trace is active the span, with its keyword arguments as event
stats, lands in the trace beside the device's ops; otherwise it costs one
annotation object and two clock reads.  It keeps no buffer and exports
nothing.

jax is never imported here: in a process that has not imported it (the
numpy-only compile paths) a span only reads the clock.  Span names start
with ``cascade.``.
"""

from __future__ import annotations

import sys
import time


class span:
    """``with span("cascade.pass.place") as s: ...``; then ``s.seconds``."""

    __slots__ = ("name", "attrs", "seconds", "_ann", "_t0")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.seconds = None
        self._ann = None

    def __enter__(self) -> "span":
        jax = sys.modules.get("jax")
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)

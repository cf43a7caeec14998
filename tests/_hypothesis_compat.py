"""Optional-``hypothesis`` shim for the test suite.

When hypothesis is installed the real ``given``/``example``/``settings``/``st`` are
re-exported unchanged.  When it is absent, property tests decorated with the
fallback ``given`` skip gracefully at call time, and the fallback ``st``
accepts any strategy-construction expression at module import time — so the
rest of the suite (compiler integration, unit tests) still collects and runs.
"""

from __future__ import annotations

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover - env dependent
    import pytest

    HAVE_HYPOTHESIS = False

    def settings(*args, **kwargs):
        def deco(fn):
            return fn
        return deco

    example = settings

    def given(*args, **kwargs):
        def deco(fn):
            # deliberately no functools.wraps: pytest must not see the
            # wrapped function's strategy parameters (it would look for
            # fixtures of those names); *a/**k still accept whatever
            # fixtures/parametrize/self pytest does pass
            def skipper(*a, **k):
                pytest.skip("hypothesis not installed")
            skipper.__name__ = fn.__name__
            skipper.__doc__ = fn.__doc__
            return skipper
        return deco

    class _Strategy:
        """Inert stand-in: any call/attribute chain yields another one."""

        def __call__(self, *args, **kwargs):
            return self

        def __getattr__(self, name):
            return self

    class _Strategies:
        def composite(self, fn):
            return _Strategy()

        def __getattr__(self, name):
            return _Strategy()

    st = _Strategies()

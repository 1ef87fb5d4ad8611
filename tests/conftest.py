"""Shared test helpers.

``hypothesis`` is unavailable in offline environments; provide no-op
stand-ins so the property-test modules still *collect* (the hypothesis
tests themselves are skipped, and each module carries a deterministic
fallback case that always runs).

``assert_no_retrace`` is the shared jit-cache discipline check: it
snapshots ``repro.core.simulator._JAX_TRACES`` and asserts the counters
did not move, i.e. the block re-used already-compiled kernels."""
import contextlib

import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # offline fallback
    HAVE_HYPOTHESIS = False

    def settings(*args, **kwargs):
        def deco(fn):
            return fn
        return deco

    def given(*args, **kwargs):
        def deco(fn):
            return pytest.mark.skip(
                reason="hypothesis not installed")(fn)
        return deco

    class _Strategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _Strategies()


@pytest.fixture
def assert_no_retrace():
    """Context-manager factory: the wrapped block must not re-trace any
    simulator jax kernel.

    Usage::

        def test_x(assert_no_retrace):
            warmup()                  # compile (or hit the cache)
            with assert_no_retrace():
                hot_calls()           # counters must not move

    Pass ``kernels=("singlehop",)`` to pin only a subset of the counters."""
    pytest.importorskip("jax")
    from repro.core.simulator import _JAX_TRACES

    @contextlib.contextmanager
    def _guard(kernels=None):
        names = tuple(kernels) if kernels is not None else tuple(_JAX_TRACES)
        before = {k: _JAX_TRACES[k] for k in names}
        yield
        after = {k: _JAX_TRACES[k] for k in names}
        assert after == before, (
            f"jax kernels re-traced inside a no-retrace block: "
            f"before={before} after={after}")

    return _guard


__all__ = ["given", "settings", "st", "HAVE_HYPOTHESIS"]

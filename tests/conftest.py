"""Shared test scaffolding.

``hypothesis`` is an optional dependency: when it is missing, the
property tests in test_fused_ops.py / test_kernels.py import no-op
stand-ins for ``given``/``settings``/``st`` from here (module-level
``pytest.importorskip`` would skip those files' non-hypothesis tests
too).  ``given`` marks the test as skipped; ``st`` strategies evaluate
to inert placeholders so decorator arguments still build.
"""
import gc

import pytest


@pytest.fixture(autouse=True)
def _stay_under_the_map_count_limit():
    """XLA:CPU keeps memory mappings for every compiled program, and a
    worker that holds the programs of a long test file crosses the
    kernel's ``vm.max_map_count`` (65530 by default), after which XLA
    segfaults inside compile.  Once a test leaves the process past a
    quarter of that many mappings, drop JAX's compiled-program caches."""
    yield
    try:
        with open("/proc/self/maps") as f:
            n = sum(1 for _ in f)
    except OSError:
        return
    if n > 16_000:
        import jax

        jax.clear_caches()
        gc.collect()


class _StrategyStub:
    """Evaluates any strategy expression (st.integers(...), st.sampled_from
    chains) to an inert placeholder."""

    def __getattr__(self, name):
        return lambda *a, **k: None


st = _StrategyStub()


def settings(*_a, **_k):
    return lambda f: f


def given(*_a, **_k):
    return pytest.mark.skip(reason="hypothesis not installed")

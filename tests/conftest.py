"""Shared test configuration.

Property tests run with no hypothesis deadline: the first example of
most of them includes a jit compile, which takes far longer than any
per-example deadline and says nothing about the property."""

import pytest
from hypothesis import settings

settings.register_profile("repro", deadline=None)
settings.load_profile("repro")


@pytest.fixture(scope="module", autouse=True)
def _bounded_compile_state():
    # Executables are never shared across test modules (each builds its
    # own model shapes), but jit caches pin every one of them for the
    # whole pytest process.  With ~400 tests the accumulated XLA CPU
    # state eventually segfaults backend_compile mid-suite, so drop the
    # caches at each module boundary to keep live state per-module.
    yield
    import jax

    jax.clear_caches()

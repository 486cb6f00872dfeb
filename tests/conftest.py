"""Shared pytest fixtures and the hypothesis profile.

The container's CPU JIT accumulates compiled dylibs across the whole
session and eventually dies with ``LLVM compilation error: Cannot allocate
memory`` (~200 distinct jits on this 1-core box).  Clearing the jax
compilation caches between test modules keeps the full suite inside the
limit without re-jitting within a module.

Property tests draw through JAX, whose first draw of a shape compiles; a
per-example deadline would time that compile, not the property, so the
profile turns the deadline off.
"""
import jax
import pytest
from hypothesis import settings

settings.register_profile("repro", deadline=None)
settings.load_profile("repro")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    yield
    jax.clear_caches()

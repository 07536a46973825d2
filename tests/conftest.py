"""Shared test configuration: pinned hypothesis profiles.

CI runs with ``HYPOTHESIS_PROFILE=ci`` (see .github/workflows/ci.yml):
``derandomize=True`` makes every property run the same example
sequence on every build, so the resize round-trip properties in
``tests/test_resize.py`` (and any future property tests) cannot flake
the gate with a fresh random seed.  Local runs keep the randomized
``dev`` profile — that is where new counterexamples get found.
"""

import os

import jax
import pytest

try:
    from hypothesis import settings
except ImportError:  # requirements-dev.txt installs it; degrade quietly
    settings = None


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop JAX's compiled-program caches between test modules.

    A full-suite run accumulates hundreds of compiled programs across
    modules; on CPU that pile-up can segfault a later large
    ``lax.switch`` trace (reproducibly at suite scale, never in
    isolation).  Per-module isolation costs some recompilation but
    keeps every module's compile behavior independent of suite order.
    """
    yield
    jax.clear_caches()

if settings is not None:
    settings.register_profile("ci", derandomize=True, deadline=None)
    settings.register_profile("dev", deadline=None)
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))

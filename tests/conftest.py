"""Shared test fixtures.

NOTE: no XLA_FLAGS device-count forcing here — smoke tests and benchmarks
must see the real single CPU device.  The multi-device mini dry-run test runs
in a subprocess with its own XLA_FLAGS (see test_dryrun_mini.py).

Sanitizer integration: ``FAASM_SANITIZE=1`` runs the whole suite with the
``repro.analysis.sanitizer`` runtime checks enabled; tests marked
``@pytest.mark.sanitize`` get them regardless.  The autouse fixture resets
the sanitizer per test and fails the test on any report it didn't consume
(seeded-violation tests drain theirs with ``take_reports()``).
"""
import os

import numpy as np
import pytest

_SANITIZE_ENV = os.environ.get("FAASM_SANITIZE") == "1"


def pytest_collection_modifyitems(config, items):
    """Mark the interpret-mode kernel matrix (and the hypothesis kernel
    sweeps) ``slow`` so scripts/tier1.sh can keep the default gate fast;
    plain ``pytest`` still runs everything."""
    for item in items:
        if "pallas_interpret" in item.nodeid or \
                "test_kernels_property" in item.nodeid:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _faults_disarmed():
    """Safety net: no fault plan leaks from one test into the next (a
    leaked plan would make unrelated tests fail nondeterministically)."""
    from repro import faults
    yield
    faults.disarm()


@pytest.fixture(autouse=True)
def _telemetry_disarmed():
    """Safety net: tracing armed by one test never leaks into the next
    (a leaked tracer keeps every hook site writing span rings)."""
    from repro import telemetry
    yield
    telemetry.disable()


@pytest.fixture(autouse=True)
def _faasm_sanitize(request):
    """Per-test sanitizer lifecycle (see module docstring)."""
    marked = request.node.get_closest_marker("sanitize") is not None
    if not (_SANITIZE_ENV or marked):
        yield
        return
    from repro.analysis import sanitizer
    sanitizer.enable()
    sanitizer.reset()
    try:
        yield
        leftovers = sanitizer.take_reports()
    finally:
        if not _SANITIZE_ENV:
            sanitizer.disable()      # marker-only: don't leak into raw tests
    if leftovers:
        pytest.fail("sanitizer reports:\n\n"
                    + "\n\n".join(str(r) for r in leftovers), pytrace=False)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def jax_():
    import jax
    return jax

"""Test configuration.

Tests run on the CPU: JAX on a virtual 8-device CPU mesh
(``JAX_PLATFORMS=cpu`` plus ``--xla_force_host_platform_device_count``),
set before jax initializes, hence the os.environ writes at import time.
Numerics in the scheduling contract are pure int32, so CPU results are
bit-identical to TPU results by construction.  The chip is reached only
through ``chip_smoke.py``.

The persistent compilation cache stays off for the whole session (the
environment variable reaches subprocesses a test starts, too): CPU
test programs have no business in the chip's cache directory.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# in case jax came in before this file
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
assert len(jax.devices()) == 8, jax.devices()

import numpy as np
import pytest

from ray_tpu.common.config import Config


@pytest.fixture(autouse=True)
def _fresh_config():
    Config.reset()
    yield
    Config.reset()


@pytest.fixture(autouse=True)
def _fresh_fault_state():
    """Chaos and circuit-breaker state are process-global (like Config):
    a chaos test must never leak drops into the next test, and a
    breaker opened by one test's dead peer must not quarantine an
    unrelated test that lands on a reused ephemeral port."""
    from ray_tpu.rpc import breaker, chaos
    chaos.disable()
    breaker.reset_registry()
    yield
    chaos.disable()
    breaker.reset_registry()


@pytest.fixture(autouse=True)
def _real_seams():
    """The clock and transport seams are process-global (like chaos):
    a sim test that dies mid-campaign must not leave a VirtualClock or
    SimTransport installed for the next (real-socket) test."""
    from ray_tpu.common import clock
    from ray_tpu.rpc import transport
    yield
    clock.uninstall()
    transport.uninstall()


@pytest.fixture(autouse=True)
def _runtime_lock_order():
    """rtlint's dynamic mode: when the ``rtlint_runtime_lock_order``
    knob is on (RT_RTLINT_RUNTIME_LOCK_ORDER=1), every lock constructed
    during a test is instrumented; after the test the OBSERVED
    acquisition-order digraph must be acyclic.  Asserting per test (then
    resetting) attributes a cycle to the test whose workload produced
    it.  Off by default: zero overhead."""
    from ray_tpu.common import lockorder
    installed = lockorder.maybe_install_from_config()
    yield
    if installed:
        try:
            lockorder.assert_acyclic()
        finally:
            lockorder.reset()


@pytest.fixture(autouse=True)
def _runtime_locksets():
    """rtlint's OTHER dynamic mode: when the ``rtlint_runtime_locksets``
    knob is on (RT_RTLINT_RUNTIME_LOCKSETS=1), instances of
    @locksets.track classes constructed during a test sample the
    per-thread held-lock set at every tracked attribute write; after
    the test no attribute may have been written from two threads with
    an empty lockset intersection (Eraser).  Asserting per test (then
    resetting) attributes a race to the test whose workload produced
    it.  Off by default: zero overhead."""
    from ray_tpu.common import locksets
    installed = locksets.maybe_install_from_config()
    yield
    if installed:
        try:
            locksets.assert_no_races()
        finally:
            locksets.reset()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def random_cluster(rng, n_nodes, n_resources, max_total_units=64):
    """Random dense cluster state in cu with some zero-capacity columns."""
    from ray_tpu.scheduling.oracle import ClusterState
    totals = rng.integers(0, max_total_units * 100,
                          size=(n_nodes, n_resources)).astype(np.int32)
    # some nodes lack some resources entirely
    totals[rng.random(totals.shape) < 0.2] = 0
    used_frac = rng.random((n_nodes, n_resources))
    avail = (totals * (1 - used_frac)).astype(np.int32)
    return ClusterState(totals, avail)


def random_requests(rng, n_tasks, n_resources, n_classes=8,
                    max_req_units=8):
    """Random request batch drawn from a small set of scheduling classes."""
    classes = rng.integers(0, max_req_units * 100,
                           size=(n_classes, n_resources)).astype(np.int32)
    classes[rng.random(classes.shape) < 0.5] = 0
    picks = rng.integers(0, n_classes, size=n_tasks)
    return classes[picks]


@pytest.fixture
def make_cluster(rng):
    return lambda *a, **k: random_cluster(rng, *a, **k)


@pytest.fixture
def make_requests(rng):
    return lambda *a, **k: random_requests(rng, *a, **k)

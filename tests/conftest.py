import os
import sys

# Tests run on the single real CPU device (the dry-run's 512-device
# override must NOT leak here).  Distributed behaviour is exercised in
# tests/test_distributed.py via subprocesses with their own XLA_FLAGS.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the repo root too: tests read profiles with the benchmark's reader
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _reset_fallback_warnings():
    """ops._warn_fallback_once is process-global warn-once state (and,
    since PR 6, always-on obs counters); reset it around every test so
    warn-once/counter assertions (and their absence) are independent of
    test execution order."""
    from repro.kernels import ops
    ops.reset_fallback_warnings()
    yield
    ops.reset_fallback_warnings()


@pytest.fixture(autouse=True)
def _reset_obs():
    """repro.obs holds process-global tracer state and kernel dispatch
    records; leave both clean after every test (tests that enable
    tracing use obs.capture() or enable/disable themselves), and zero
    the always-on counts of how layer weights reached the kernel."""
    from repro import obs
    obs.reset_counters("matmul.weight_")
    yield
    obs.reset_records()
    obs.disable()
    obs.reset_counters("matmul.weight_")

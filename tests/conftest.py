"""Shared test fixtures.

NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
single real device.  Multi-device tests spawn subprocesses that set
``--xla_force_host_platform_device_count`` themselves (see _subproc.py).
"""

import os
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


@pytest.fixture(scope="session")
def rng():
    import numpy as np

    return np.random.RandomState(0)


@pytest.fixture(scope="session")
def fault_seed():
    """Seed for fault-injection tests.  Deterministic default keeps tier-1
    green; the CI chaos leg sets REPRO_FAULT_SEED to vary the schedules
    (the recovery properties must hold for *any* seed)."""
    return int(os.environ.get("REPRO_FAULT_SEED", "0"))

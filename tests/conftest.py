import sys
from pathlib import Path

# NOTE: no xla_force_host_platform_device_count here — smoke tests must see
# exactly 1 device (the 512-device flag belongs to launch/dryrun.py only).
# Multi-device tests spawn subprocesses (tests/_subproc.py helpers).
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")

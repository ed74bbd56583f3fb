import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the benchmark's generator and query references (``bench``) are tests'
# reference data too
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import pytest

from repro.core import startup


@pytest.fixture
def db():
    return startup()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session", autouse=True)
def _lock_order_witness():
    """Opt-in lock-order witness (REPRO_WITNESS=1): instrument every
    Database built during the session plus the module-level locks, and
    fail the run at teardown on acquisition-order cycles or blocking
    condition waits taken while other witnessed locks are held."""
    if os.environ.get("REPRO_WITNESS") != "1":
        yield
        return
    from repro.analysis import witness as wmod
    w = wmod.LockWitness()
    wmod.install(w)
    yield
    wmod.uninstall()
    sys.stderr.write("\n" + w.report() + "\n")
    w.assert_ok()

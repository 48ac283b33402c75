"""With the timed path broken underneath, ``correct`` comes out false:
every fault a cell can have, at a tiny configuration on the CPU."""
import pytest

from bench import faults

from .conftest import run_tiny


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_makes_run_incorrect(fault):
    r = run_tiny("ycsb-c-zipf", fault=fault)
    assert not r["correct"], r["checks"]
    assert r["checks"]["read_wrong"]["value"] > 0

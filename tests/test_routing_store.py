"""Runs the "store" group of the 8-device sharded-store validation
(`tests/multidev/store_prog.py`, through conftest's `run_store_prog`);
`test_exec_modes.py` runs "residency" (with the fused paths) and
`test_obs.py` "metrics" (with recovery), beside the checks of the same
contracts on one device. pytest-xdist's loadfile queue hands out files
with the most tests first, so this one-test file starts last: it holds
the shortest group.
"""
import pytest


@pytest.mark.slow
def test_sharded_store_multidevice(run_store_prog):
    out = run_store_prog("store")
    assert "STORE-OK" in out
    assert "RANGE-OK" in out
    assert "UNEVEN-OK" in out
    assert "PQ-OK" in out

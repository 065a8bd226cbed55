"""Shared pytest hygiene for the tier-1 suite.

The suite compiles hundreds of XLA CPU executables in one process (every
backend x exec-mode x plan shape). The CPU client's JIT code memory is
only reclaimed when the cached executables are dropped; past a few
hundred live executables the next large compile can crash the process.
Clearing jax's compilation caches at module boundaries bounds that
growth — later modules simply recompile what they actually use.

`run_store_prog` launches a group of the 8-device store validation for the
three test files that split it between them.
"""
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_cache_growth():
    yield
    jax.clear_caches()


@pytest.fixture
def run_store_prog():
    """Runs one group of `tests/multidev/store_prog.py`'s checks (8 fake
    devices, so a subprocess: the device count is fixed before JAX starts);
    returns its stdout."""
    def run(group: str) -> str:
        prog = os.path.join(ROOT, "tests", "multidev", "store_prog.py")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
        out = subprocess.run([sys.executable, prog, group], env=env,
                             capture_output=True, text=True, timeout=1500)
        assert out.returncode == 0, (f"stdout:\n{out.stdout}\n"
                                     f"stderr:\n{out.stderr}")
        return out.stdout
    return run

"""The benchmark's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the executable store keys on the program's sources, not on what a test patched in: keep it out
os.environ["RL_TPU_NO_EXEC_STORE"] = "1"
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_executable_store():
    """Every test compiles what IT traced: the program's store also keeps
    executables in memory by signature, which a patched function shares
    with the unpatched one."""
    from rl_tpu.compile import ExecutableStore, set_default_store

    set_default_store(ExecutableStore(memory_cache=False))
    yield

"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's distributed-tests-without-a-cluster strategy
(reference test/test_distributed.py spawns process groups on one machine);
here we instead ask XLA for 8 host devices so every sharding/pjit test runs
the real partitioner without TPU hardware.

Tiers (reference CI's per-job isolation, SURVEY §4):
- smoke:  ``pytest -m "smoke and not slow"`` — core data/env/value/config
  coverage, <2 min on this 1-core box (the marker is auto-applied below)
- fast:   ``pytest -m "not slow and not mesh"`` (~4-5 min on 1 core)
- mesh:   ``pytest -m mesh`` — multi-device sharding/pjit tests
- full:   ``pytest tests/`` — everything (what the driver runs, ~20 min)
Compile artifacts persist between runs where
``rl_tpu.config.enable_compile_cache`` puts them, and XLA's backend
optimization level is dropped for tests (hundreds of tiny programs; codegen
quality is irrelevant to correctness).
"""

import os

# XLA_FLAGS must be set before the CPU client initializes (first device use).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in flags:
    # tests compile hundreds of tiny programs; codegen quality is irrelevant
    flags += " --xla_backend_optimization_level=0 --xla_llvm_disable_expensive_passes=true"
os.environ["XLA_FLAGS"] = flags

# headless container: no EGL/GLX. Render-less mujoco keeps the
# dm_control/gymnasium-robotics/pettingzoo suites importable (none of the
# tests here render frames).
os.environ.setdefault("MUJOCO_GL", "disabled")

import jax  # noqa: E402

# The suite runs on the CPU backend whatever the machine has; jax.config
# wins over JAX_PLATFORMS and backends initialize lazily.
jax.config.update("jax_platforms", "cpu")

# Persistent compile cache: big fused-program tests (trainer loops, GRPO)
# compile once per content hash instead of once per run. Placed by the one
# rule the program uses (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
from rl_tpu.config import enable_compile_cache  # noqa: E402

enable_compile_cache()

# The executable store (rl_tpu.compile) is a SECOND persistent layer; tests
# must never share serialized-executable state across runs or with the
# user's real cache (a stale entry would mask a cold-path regression), so
# the tier-1 env pins it to a fresh tmpdir per session.
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

_exec_store_dir = tempfile.mkdtemp(prefix="rl_tpu_exec_store_")
os.environ["RL_TPU_EXEC_STORE_DIR"] = _exec_store_dir
atexit.register(shutil.rmtree, _exec_store_dir, ignore_errors=True)

import pytest  # noqa: E402

# the <2-min core-coverage tier: one file per load-bearing layer
_SMOKE_MODULES = {
    "test_specs",
    "test_envs",
    "test_values",
    "test_config",
    "test_import_hygiene",
    "test_collector_ppo",
    "test_transforms",
}


def pytest_collection_modifyitems(items):
    for it in items:
        if it.module.__name__.rpartition(".")[-1] in _SMOKE_MODULES:
            it.add_marker(pytest.mark.smoke)


def pytest_sessionfinish(session, exitstatus):
    """Tier-1 IR gate: every program the default ProgramRegistry compiled
    during this test run was audited (R101–R105) against the checked-in
    baseline; any unsuppressed finding fails the session even if each
    individual test passed. Tests that deliberately compile poisoned
    fixture programs use their own ``ProgramRegistry(auditor=...)`` so
    they never land here."""
    import sys

    ir = sys.modules.get("rl_tpu.analysis.ir")
    if ir is None:  # no test compiled through the registry
        return
    aud = ir.get_ir_auditor(create=False)
    if aud is None:
        return
    unsup = aud.unsuppressed()
    if unsup:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        write = tr.write_line if tr is not None else print
        write("")
        write(
            f"rlint IR gate: {len(unsup)} unsuppressed R10x finding(s) over "
            f"{aud.programs_audited()} audited program(s):"
        )
        for f in unsup:
            write("  " + f.format())
        session.exitstatus = 1


@pytest.fixture
def undonated_programs(monkeypatch):
    """A function that, once called, makes ``ProgramRegistry.register``
    drop ``donate_argnums``: the programs as they were before the engine
    donated its KV pools, for tests that hold the two against each other."""
    from rl_tpu.compile import ProgramRegistry

    register = ProgramRegistry.register

    def plain(self, name, fn, **kw):
        kw.pop("donate_argnums", None)
        return register(self, name, fn, **kw)

    return lambda: monkeypatch.setattr(ProgramRegistry, "register", plain)


@pytest.fixture(autouse=True)
def _hot_path_transfer_guard(request):
    """``@pytest.mark.hot_path_guard``: run the test body under
    ``jax.transfer_guard("disallow")`` so any implicit device↔host
    transfer (the runtime shadow of rlint's R001) raises instead of
    silently serializing. Explicit ``jax.device_get``/``device_put``
    stay allowed — the guard targets *implicit* syncs."""
    if request.node.get_closest_marker("hot_path_guard") is None:
        yield
        return
    with jax.transfer_guard("disallow"):
        yield


@pytest.fixture
def lock_witness():
    """Arm the rlint LockWitness for the duration of a test: every
    ``threading.Lock``/``RLock`` *created during the test* is wrapped to
    record the observed lock-order graph. Teardown disarms and fails the
    test on any observed lock-order inversion (latent deadlock)."""
    from rl_tpu.analysis import LockWitness

    w = LockWitness()
    w.arm()
    try:
        yield w
    finally:
        w.disarm()
        inv = w.inversions()
        assert not inv, (
            "lock-order inversion(s) observed (latent deadlock): "
            + "; ".join(
                f"{a} vs {b} (A→B on {i['a_then_b']}, B→A on {i['b_then_a']})"
                for i in inv
                for a, b in [i["locks"]]
            )
        )


@pytest.fixture
def rng():
    return jax.random.key(0)


@pytest.fixture
def mesh8():
    import numpy as np
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()).reshape(4, 2)
    return Mesh(devs, ("data", "model"))

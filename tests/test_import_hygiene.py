"""Import-hygiene regression tests.

Round-1 postmortem: a single module-level ``jnp.log`` initialized the JAX
backend during ``import rl_tpu.*``, which crashed bench.py on TPU and hung
the multichip dryrun. Every module must import
without touching a device so the driver can force platforms *after* import.
"""

import subprocess
import sys

_WALK = """
import jax, importlib, pkgutil
from jax._src import xla_bridge as xb
import rl_tpu
mods = [m.name for m in pkgutil.walk_packages(rl_tpu.__path__, 'rl_tpu.')]
bad = []
for name in mods:
    try:
        importlib.import_module(name)
    except Exception as e:
        bad.append((name, repr(e)))
    if xb._backends:
        print('BACKEND_INIT_AT', name)
        raise SystemExit(1)
for name, err in bad:
    print('IMPORT_FAIL', name, err)
raise SystemExit(2 if bad else 0)
"""


def test_no_backend_init_on_import():
    out = subprocess.run(
        [sys.executable, "-c", _WALK],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=None,
    )
    assert out.returncode == 0, out.stdout + out.stderr


def test_graft_entry_import_is_clean():
    # the driver imports __graft_entry__ then forces a platform; any
    # import-time backend touch breaks it
    code = (
        "import jax, __graft_entry__\n"
        "from jax._src import xla_bridge as xb\n"
        "raise SystemExit(1 if xb._backends else 0)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300
    )
    assert out.returncode == 0, out.stdout + out.stderr

"""What keeps a chip run honest, checked without a chip.

``chip_smoke.py`` and ``bench.py`` must FAIL where there is no
accelerator (never rerun on the host), the kernel registry must never
hand the chip the Pallas interpreter, an unknown device must never get an
invented peak, and one rule must place every cache. The four-chip
sharding check of ``chip_smoke.py --chips 4`` is rehearsed here at tiny
width on the 8-device CPU mesh.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from rl_tpu.kernels import registry as kreg  # noqa: E402


def _run(args, *, cwd=REPO, timeout=120, **env):
    full = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=full, timeout=timeout,
        capture_output=True, text=True,
    )


# -- chip_smoke.py ------------------------------------------------------------


def test_chip_smoke_fails_without_an_accelerator():
    p = _run(["chip_smoke.py"], timeout=60)
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    # the last line carries the verdict and the device, nothing else
    # (count: conftest's XLA_FLAGS give the child 8 host devices)
    assert set(last) == {"ok", "device"} and last["ok"] is False
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    assert '"ok": true' not in p.stdout
    # it stopped at the device check: no phase ran, no model was built
    assert [json.loads(ln)["phase"] for ln in p.stdout.strip().splitlines()[:-1]] == ["device"]


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    p = _run(["chip_smoke.py"], cwd=str(tmp_path), timeout=60, PYTHONPATH="")
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout


def test_four_chip_sharding_check_at_tiny_width():
    """``--chips 4``'s phase on four of the eight host devices: parity
    with the single-device update, every leaf in four distinct quarters
    (cutoff 0 so that a tiny model shards at all), none on device 0 only."""
    cfg = chip_smoke.model_110m(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq_len=128, dtype=jnp.float32,
    )
    out = chip_smoke.fsdp_phase(
        cfg, devices=jax.devices()[:4], num_prompts=2, group_repeats=4,
        max_prompt_len=8, max_new_tokens=8, microbatch_size=4, fsdp_min_size_mb=0.0,
    )
    assert out["loss_parity"], out
    assert out["large_leaves"] > 0 and out["large_leaves_sharded_four_ways"], out
    # the CPU backend reports no memory statistics: nothing is claimed
    assert out["bytes_in_use_per_device"] is None and "memory_balanced" not in out


def test_kernels_in_hlo_reads_custom_calls_only():
    hlo = "\n".join([
        '%_fused_sample_kernel.1 = (s32[16,1]) custom-call(%a), custom_call_target="tpu_custom_call"',
        '%fusion.3 = f32[8] fusion(%b), metadata={op_name="jit(f)/_paged_decode_kernel/mul"}',
    ])
    assert chip_smoke.kernels_in_hlo(hlo) == {"sampling"}


# -- the peaks table ----------------------------------------------------------


def test_peaks_table_knows_the_v5e():
    from rl_tpu.utils.peaks import device_peaks

    row = device_peaks("TPU v5 lite")
    assert row["flops"] == 197e12 and row["bytes_per_s"] == 819e9


def test_peaks_table_raises_on_an_unknown_device():
    from rl_tpu.utils.peaks import device_peaks

    with pytest.raises(KeyError, match="TPU v9"):
        device_peaks("TPU v9")
    with pytest.raises(KeyError):
        device_peaks("cpu")


# -- bench.py -----------------------------------------------------------------


def test_bench_without_a_chip_exits_nonzero():
    p = _run(["bench.py"], timeout=120, BENCH_TIMEOUT="100")
    assert p.returncode != 0
    assert "no accelerator" in p.stdout
    # nothing was measured on the host in the chip's name
    assert '"platform": "cpu"' not in p.stdout


def test_bench_full_tier_never_selects_the_cpu_platform():
    src = open(os.path.join(REPO, "bench.py")).read()
    # one place sets a platform, and only to what the caller asked for
    sets = re.findall(r'jax\.config\.update\("jax_platforms", (\w+)\)', src)
    assert sets == ["plat"]
    assert 'plat = os.environ.get("BENCH_PLATFORM")' in src
    # a child is only ever handed the CPU platform off the full tier
    lines = src.splitlines()
    sites = [i for i, ln in enumerate(lines) if '["BENCH_PLATFORM"] =' in ln]
    assert sites
    for i in sites:
        assert any('if _TIER != "full":' in ln for ln in lines[i - 5:i]), lines[i]


def test_bench_exit_code_sees_a_nested_sub_bench_error():
    import bench

    assert not bench._failed({"ppo": {"value": 1.0, "error": None}})
    assert bench._failed({"ppo": {"value": 1.0}, "serve": {"error": "boom"}})
    assert bench._failed({"metric": "m", "error": "4: only 1 device"})


def test_bench_orchestrator_imports_touch_no_backend(tmp_path):
    code = (
        "import os, sys; sys.argv=['bench.py']; import bench\n"
        "bench._maybe_write_metrics({'serve': {'metrics': {'a': 1}}})\n"
        "import jax; from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print(open(os.environ['BENCH_METRICS_OUT']).read()[:20])\n"
    )
    p = _run(["-c", code], BENCH_METRICS_OUT=str(tmp_path / "m.json"))
    assert p.returncode == 0, p.stderr[-2000:]


# -- the kernel registry on the chip ------------------------------------------


@pytest.mark.parametrize("name", sorted(kreg.registered_kernels()))
def test_selection_never_yields_interpret_on_tpu(monkeypatch, name):
    monkeypatch.delenv(kreg.ENV_NO_KERNELS, raising=False)
    monkeypatch.delenv(kreg.ENV_INTERPRET, raising=False)
    assert kreg.selection(name, backend="tpu") == "native"
    monkeypatch.setenv(kreg.ENV_INTERPRET, "1")
    with pytest.raises(RuntimeError, match="interpreter"):
        kreg.selection(name, backend="tpu")
    # an explicit opt-out still outranks it: nothing is selected at all
    monkeypatch.setenv(kreg.ENV_NO_KERNELS, name)
    assert kreg.selection(name, backend="tpu") is None


def test_flash_interpret_config_field_is_an_error_on_tpu(monkeypatch):
    from rl_tpu.models import transformer

    on = chip_smoke.model_110m(flash_interpret=True)
    assert transformer._flash_interpret(on) is True  # the CPU test fixture
    monkeypatch.setattr(kreg, "_backend", lambda: "tpu")
    assert transformer._flash_interpret(chip_smoke.model_110m()) is False
    with pytest.raises(RuntimeError, match="interpreter"):
        transformer._flash_interpret(on)


# -- one rule places every cache ----------------------------------------------

_PRINT_CACHE = (
    "import jax; from rl_tpu.config import enable_compile_cache as e; "
    "print(e()); print(jax.config.jax_compilation_cache_dir)"
)


def test_cache_rule_variable_set(tmp_path):
    p = _run(["-c", _PRINT_CACHE], JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert p.stdout.split() == [str(tmp_path), str(tmp_path)], p.stderr[-1000:]


def test_cache_rule_variable_unset():
    p = _run(["-c", _PRINT_CACHE])
    want = os.path.join(REPO, ".jax_cache")
    assert p.stdout.split() == [want, want], p.stderr[-1000:]


def test_cache_rule_already_configured_is_left_alone(tmp_path):
    from rl_tpu.config import compile_cache_dir, enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert compile_cache_dir() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_executable_store_defaults_under_the_cache_dir(monkeypatch):
    from rl_tpu.compile import ExecutableStore
    from rl_tpu.config import compile_cache_dir

    monkeypatch.delenv("RL_TPU_EXEC_STORE_DIR", raising=False)
    assert ExecutableStore().root == os.path.join(compile_cache_dir(), "executables")


def test_executable_store_key_follows_the_sources(monkeypatch):
    """An executable must not outlive the code it was built from: the
    same program signature keys differently once the sources change."""
    from rl_tpu.compile import ExecutableStore, store

    s = ExecutableStore(root="/nonexistent", memory_cache=False)
    args = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    before = s.key_for("prog", args, backend="tpu")
    assert before == s.key_for("prog", args, backend="tpu")
    monkeypatch.setattr(store, "_code_version", lambda: "another checkout")
    assert s.key_for("prog", args, backend="tpu") != before

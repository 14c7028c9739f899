"""Every Pallas kernel on a default path compiles for the chip.

No chip is attached in tier-1, but the chip's compiler is installed: it
compiles for a TPU v5e that is *described* (``v5e:2x2``, ``device_kind``
"TPU v5 lite"), and refuses what the chip would refuse — an op Mosaic has
no lowering for, a block that breaks the (8, 128) tiling, a kernel that
does not fit VMEM. Interpret mode shows none of that. Each case compiles
one kernel at the width its default path runs it (the 110M model: 12
heads x 64, vocab 32768, ctx 1024, bf16, 16 slots, KV blocks of 16; the
PER bench: capacity 2**20, batch 256; the paged decode kernel also at the
benchmark cells' shapes, 16 x 128 among them, and at a GQA width) and asserts the kernel is in the
program as a ``tpu_custom_call``.

Nothing runs, so this says nothing about results or speed
(``chip_smoke.py`` on a chip does that); a pass here is not a chip run.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest

from rl_tpu.kernels import registry as kreg
from rl_tpu.kernels.paged_attention import paged_flash_decode_int8
from rl_tpu.kernels.sampling import fused_sample
from rl_tpu.kernels.sumtree import sumtree_update
from rl_tpu.ops.attention import flash_attention, flash_decode, paged_flash_decode

# the 110M serving/GRPO widths
B, T, H, D, V = 16, 1024, 12, 64, 32768
SLOTS, BLOCK, MAX_BLOCKS = 16, 16, 64
N_BLOCKS = SLOTS * MAX_BLOCKS + 1


@pytest.fixture(scope="module")
def chip():
    """A described one-chip v5e sharding; the compile cache is off around
    the module (an entry written for a described chip cannot be read back
    without one, and every later compile would warn about it)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this host: nothing to ask
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """The registry asks ``jax.default_backend()``, which is the CPU here;
    answer for the chip so ``selection()`` takes the branch it takes there."""
    monkeypatch.delenv(kreg.ENV_INTERPRET, raising=False)
    monkeypatch.delenv(kreg.ENV_NO_KERNELS, raising=False)
    monkeypatch.setattr(kreg, "_backend", lambda: "tpu")


def _compile(fn, chip, *avals):
    """HLO text of ``fn`` compiled for the described chip."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in avals]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_loss(q, k, v):
    return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()


def _flash_loss_masked(q, k, v, mask):
    # the GRPO training forward: a ragged batch rides the kernel as
    # segment ids (one microbatch of 8 rows, prompt + response = 128)
    o = flash_attention(q, k, v, causal=True, kv_mask=mask)
    return o.astype(jnp.float32).sum()


_QKV = [((B, T, H, D), jnp.bfloat16)] * 3
_POOL = (N_BLOCKS, H, BLOCK, D)
_TABLE = [((SLOTS, MAX_BLOCKS), jnp.int32), ((SLOTS,), jnp.int32)]

ATTENTION_CASES = {
    "flash_fwd": (functools.partial(flash_attention, causal=True), _QKV),
    "flash_fwd_bwd": (jax.grad(_flash_loss, argnums=(0, 1, 2)), _QKV),
    "flash_fwd_bwd_masked": (
        jax.grad(_flash_loss_masked, argnums=(0, 1, 2)),
        [((8, 128, H, D), jnp.bfloat16)] * 3 + [((8, 128), jnp.bool_)],
    ),
    "flash_decode": (
        flash_decode,
        [((B, 1, H, D), jnp.bfloat16)]
        + [((B, T, H, D), jnp.bfloat16)] * 2
        + [((), jnp.int32)],
    ),
    "paged_flash_decode": (
        paged_flash_decode,
        [((SLOTS, 1, H, D), jnp.bfloat16)] + [(_POOL, jnp.bfloat16)] * 2 + _TABLE,
    ),
    # the benchmark's two cells: gpt2-medium (16 heads x 64, bf16), the GRPO
    # collector's 8 slots and the rollout engine's 32, 64-entry tables
    "paged_flash_decode_grpo_cell": (
        paged_flash_decode,
        [((8, 1, 16, 64), jnp.bfloat16)]
        + [((513, 16, 16, 64), jnp.bfloat16)] * 2
        + [((8, 64), jnp.int32), ((8,), jnp.int32)],
    ),
    "paged_flash_decode_rollout_cell": (
        paged_flash_decode,
        [((32, 1, 16, 64), jnp.bfloat16)]
        + [((2049, 16, 16, 64), jnp.bfloat16)] * 2
        + [((32, 64), jnp.int32), ((32,), jnp.int32)],
    ),
    # a Queue B width: GQA (32 query heads on 8), head width 128
    "paged_flash_decode_gqa_d128": (
        paged_flash_decode,
        [((SLOTS, 1, 32, 128), jnp.bfloat16)]
        + [((N_BLOCKS, 8, BLOCK, 128), jnp.bfloat16)] * 2
        + _TABLE,
    ),
    # the looped-decoder cell: 16 heads x 128 on 16 KV heads, 16 slots, one
    # stacked pool a side (192 entries of 161 blocks), 64-entry tables
    "paged_flash_decode_looped_cell": (
        paged_flash_decode,
        [((16, 1, 16, 128), jnp.bfloat16)]
        + [((192 * 161, 16, 16, 128), jnp.bfloat16)] * 2
        + [((16, 64), jnp.int32), ((16,), jnp.int32)],
    ),
    "paged_flash_decode_int8": (
        paged_flash_decode_int8,
        [((SLOTS, 1, H, D), jnp.bfloat16)]
        + [(_POOL, jnp.int8)] * 2
        + [((N_BLOCKS, H), jnp.float32)] * 2
        + _TABLE,
    ),
}


@pytest.mark.parametrize("name", list(ATTENTION_CASES))
def test_attention_kernel_compiles_for_v5e(chip, name):
    fn, avals = ATTENTION_CASES[name]
    assert "tpu_custom_call" in _compile(fn, chip, *avals)


@pytest.mark.parametrize(
    "kw",
    [dict(greedy=True), dict(greedy=False), dict(greedy=False, top_k=8)],
    ids=["greedy", "sampled", "top_k"],
)
def test_fused_sample_compiles_for_v5e(chip, on_tpu, kw):
    def fn(logits, key_data):
        key = jax.random.wrap_key_data(key_data)
        return fused_sample(logits, key, temperature=0.8, **kw)

    hlo = _compile(fn, chip, ((SLOTS, V), jnp.bfloat16), ((2,), jnp.uint32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("capacity", [2**18, 2**20])
def test_sumtree_update_compiles_for_v5e(chip, on_tpu, capacity):
    fanout, batch = 16, 256
    hlo = _compile(
        functools.partial(sumtree_update, fanout=fanout),
        chip,
        ((capacity,), jnp.float32),
        ((capacity // fanout,), jnp.float32),
        ((batch,), jnp.int32),
        ((batch,), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


def test_registry_selects_native_for_every_kernel_on_tpu(on_tpu):
    """What the cases above compile is what the chip runs: with the
    backend answering "tpu" every registered kernel resolves native."""
    assert {kreg.selection(n) for n in kreg.registered_kernels()} == {"native"}

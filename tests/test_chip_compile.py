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

The paged cache's programs are also compiled whole (the row write, then
the kernel or the gather read, as ``_paged_attention`` makes them) to see
how the compiler lays the KV pools out: row-major as the kernel reads
them, with no whole-pool ``copy`` but the undonated output's.

Nothing runs, so this says nothing about results or speed
(``chip_smoke.py`` on a chip does that); a pass here is not a chip run.
"""

import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest

from rl_tpu.kernels import registry as kreg
from rl_tpu.kernels.paged_attention import paged_flash_decode_int8
from rl_tpu.kernels.sampling import fused_sample
from rl_tpu.kernels.sumtree import sumtree_update
from rl_tpu.models.transformer import TransformerConfig, TransformerLM, _paged_attention
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
    # the same two as the cells' pools are stored: two heads to a lane row
    "paged_flash_decode_grpo_cell_packed": (
        paged_flash_decode,
        [((8, 1, 16, 64), jnp.bfloat16)]
        + [((513, 8, 16, 128), jnp.bfloat16)] * 2
        + [((8, 64), jnp.int32), ((8,), jnp.int32)],
    ),
    "paged_flash_decode_rollout_cell_packed": (
        paged_flash_decode,
        [((32, 1, 16, 64), jnp.bfloat16)]
        + [((2049, 8, 16, 128), jnp.bfloat16)] * 2
        + [((32, 64), jnp.int32), ((32,), jnp.int32)],
    ),
    # the 110M widths packed: 12 heads x 64 as 6 rows of 128
    "paged_flash_decode_packed": (
        paged_flash_decode,
        [((SLOTS, 1, H, D), jnp.bfloat16)]
        + [((N_BLOCKS, H // 2, BLOCK, 2 * D), jnp.bfloat16)] * 2
        + _TABLE,
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


# the benchmark's cells: heads x width, blocks a pool, slots (or admitted
# rows), new tokens a row, decode steps a chunk, cache entries stacked in
# one pool. gpt2-medium stores two 64-wide heads to a pool row; the looped
# decoder's 128-wide heads lie one to a row in one stacked pool a side
POOL_PROGRAMS = {
    "gpt2_grpo_decode_chunk": dict(H=16, D=64, n_blocks=513, S=8, T=1, steps=2),
    "gpt2_rollout_decode_chunk": dict(H=16, D=64, n_blocks=2049, S=32, T=1, steps=4),
    "gpt2_grpo_prefill": dict(H=16, D=64, n_blocks=513, S=8, T=64),
    "gpt2_rollout_prefill": dict(H=16, D=64, n_blocks=2049, S=4, T=128),
    "ouro_decode_chunk": dict(H=16, D=128, n_blocks=161, S=16, T=1, steps=2, entries=192),
    "ouro_prefill": dict(H=16, D=128, n_blocks=161, S=2, T=64, entries=192),
}


def _whole_copies(hlo, shape, prefetches=True):
    """{computation: its ``copy`` ops as large as an array of ``shape``},
    the entry computation under "ENTRY" (the async form counts once, at
    its start). A loop body or a fusion is a computation of its own.
    ``prefetches=False`` leaves out a copy into or out of the chip's fast
    memory (``S(1)`` in a layout: the compiler's own prefetch of an array
    small enough, not a second copy of it in HBM)."""
    n, found, comp = math.prod(shape), {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = "ENTRY" if head.group(1) else head.group(2)
            continue
        op = re.search(r"= \(?\w+\[([\d,]+)\]\S* .*?\b(copy|copy-start)\(", line)
        if op and math.prod(map(int, op.group(1).split(","))) == n:
            if prefetches or "S(1)" not in line[: op.end()]:
                found[comp] = found.get(comp, 0) + 1
    return found


@pytest.mark.parametrize("name", list(POOL_PROGRAMS))
def test_kv_pools_lie_as_the_kernel_reads_them(chip, on_tpu, name):
    """A decode chunk (``lax.scan`` of row write + ``paged_flash_decode``)
    and a prefill step (row write + gather read), pools in and out
    undonated as the engine's programs have them: the pools enter
    row-major, nothing in a loop copies one whole, and the entry
    computation copies each at most once (the output it may not alias)."""
    c = POOL_PROGRAMS[name]
    H, D, S, T, entries = c["H"], c["D"], c["S"], c["T"], c.get("entries", 1)
    cfg = TransformerConfig(
        vocab_size=128, d_model=H * D, n_layers=entries, n_heads=H, d_head=D,
        d_ff=128, max_seq_len=1024, dtype=jnp.bfloat16, scan_layers=entries > 1,
    )
    pool = jax.eval_shape(
        lambda: TransformerLM(cfg).init_paged_cache(S, c["n_blocks"], BLOCK, MAX_BLOCKS)
    )[0]["pool_k"].shape

    def step(pools, table, lens, q, k, v):
        cache = dict(pool_k=pools[0], pool_v=pools[1], block_table=table, len=lens)
        if entries > 1:
            cache["entry"] = jnp.int32(entries // 2)
        o, cache = _paged_attention(cfg, q, k, v, cache, None)
        return o, (cache["pool_k"], cache["pool_v"]), cache["len"]

    def program(pool_k, pool_v, table, lens, q, k, v):
        if T > 1:
            return step((pool_k, pool_v), table, lens, q, k, v)[:2]

        def body(carry, _):
            o, pools, lens = step(*carry, q, k, v)
            return (pools, table, lens), o

        (pools, _, _), o = jax.lax.scan(
            body, ((pool_k, pool_v), table, lens), None, length=c["steps"]
        )
        return o, pools

    hlo = _compile(
        program, chip,
        *[(pool, jnp.bfloat16)] * 2,
        ((S, MAX_BLOCKS), jnp.int32), ((S,), jnp.int32),
        *[((S, T, H, D), jnp.bfloat16)] * 3,
    )
    assert ("tpu_custom_call" in hlo) == (T == 1)
    dims = ",".join(map(str, pool))
    entry = hlo[hlo.index("\nENTRY "):]
    layouts = re.findall(rf"bf16\[{dims}\]\{{([\d,]+)[^}}]*\}} parameter\(", entry)
    assert layouts == ["3,2,1,0"] * 2, layouts
    copies = _whole_copies(hlo, pool)
    assert set(copies) <= {"ENTRY"} and copies.get("ENTRY", 0) <= 2, copies


@pytest.mark.parametrize("name", list(POOL_PROGRAMS))
def test_engine_programs_alias_their_pools(chip, on_tpu, name):
    """The twin of the test above with the pools DONATED, on the engine's
    own registered programs (``serving.decode.k*``, ``serving.prefill.*``:
    whatever ``ContinuousBatchingEngine`` registers them with is what
    compiles here, at the cells' pool shapes): every pool is an
    ``input_output_alias`` of the executable and nothing, the entry
    computation included, copies one whole in HBM. Undonated, each pool
    has no alias and one such copy. The model is two GPT-2-wide blocks,
    or the looped stack over one stacked pool a side; only shapes are
    handed over, the engine itself is built with a pool of 3 blocks."""
    from rl_tpu.analysis.ir import honored_alias_count
    from rl_tpu.models.serving import ContinuousBatchingEngine, _pools_from

    c = POOL_PROGRAMS[name]
    H, D, S, T, entries = c["H"], c["D"], c["S"], c["T"], c.get("entries", 1)
    depth = dict(n_layers=entries // 4, loop_steps=4, scan_layers=True) if entries > 1 else dict(n_layers=2)
    cfg = TransformerConfig(
        vocab_size=512, d_model=H * D, n_heads=H, d_head=D, d_ff=256,
        max_seq_len=1024, dtype=jnp.bfloat16, **depth,
    )
    model = TransformerLM(cfg)

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree
        )

    params = on_chip(
        jax.eval_shape(model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    eng = ContinuousBatchingEngine(
        model, params, n_slots=S, block_size=BLOCK, n_blocks=3, max_seq_len=1024,
        prompt_buckets=(64, 128),
    )
    pools = on_chip(jax.eval_shape(
        lambda: _pools_from(model.init_paged_cache(S, c["n_blocks"], BLOCK, MAX_BLOCKS))
    ))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    table, vec, flag = (
        jax.ShapeDtypeStruct(s, d, sharding=chip)
        for s, d in (((S, MAX_BLOCKS), jnp.int32), ((S,), jnp.int32), ((S,), bool))
    )
    if T == 1:
        prog = eng._get_decode_prog(c["steps"])
        args = (params, pools, table, vec, flag, vec, vec, flag, key, on_chip(eng.dev_obs))
    else:
        prog = eng._get_prefill_prog(S, T)
        tokens = jax.ShapeDtypeStruct((S, T), jnp.int32, sharding=chip)
        mask = jax.ShapeDtypeStruct((S, T), bool, sharding=chip)
        args = (params, pools, table, tokens, mask, key)
    hlo = prog._jit.lower(*args).compile().as_text()
    assert "_fused_sample_kernel" in hlo
    assert ("_paged_decode_kernel" in hlo) == (T == 1)
    n_pools = len(jax.tree.leaves(pools))
    assert honored_alias_count(hlo) == n_pools, hlo.splitlines()[0][:400]
    assert _whole_copies(hlo, pools[0][0].shape, prefetches=False) == {}


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

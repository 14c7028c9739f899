"""Paged-attention decode dispatch + the int8 dequant-in-kernel variant.

The f32 kernel itself lives in :mod:`rl_tpu.ops.attention`
(``paged_flash_decode`` — gather-free reads straight off the PR 11 block
tables via scalar-prefetch index maps, a slot's heads and several table
entries a grid step). This module adds the registry
glue (:func:`decode_mode` decides kernel vs stock-XLA gather per trace)
and :func:`paged_flash_decode_int8`: the same online-softmax recurrence
over a grid of one (slot, head, table entry) a step, where K/V blocks
arrive as int8 and are dequantized IN the kernel from scalar-prefetched
per-(block, kv-head) scales — the dequant multiply rides the
VMEM-resident block, so the f32 pool never exists in HBM.
"""

from __future__ import annotations

import functools

from . import registry

__all__ = ["decode_mode", "paged_flash_decode_int8"]


def decode_mode(*, int8: bool):
    """Selection for the paged decode read path: ``"native"`` /
    ``"interpret"`` / ``None`` (XLA gather fallback)."""
    return registry.selection("kv_int8" if int8 else "paged_attention")


def _decode_softmax_update(q, k_blk, v_blk, valid, m_ref, l_ref, acc_ref):
    """The online-softmax recurrence of one (row, table entry) grid cell:
    score one KV block, mask, and fold it into the running (m, l, acc)
    scratch state."""
    import jax
    import jax.numpy as jnp

    from ..ops.attention import _NEG_INF

    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = jnp.where(valid, s, _NEG_INF)
    m = m_ref[:]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m - m_new)
    m_ref[:] = m_new
    l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1)
    acc_ref[:] = acc_ref[:] * corr[:, None] + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _paged_decode_int8_kernel(
    table_ref, len_ref, sk_ref, sv_ref, *refs, block_k, n_heads, group
):
    """One grid step = one BLOCK-TABLE entry for one (slot, q-head), over
    int8 K/V. The index map picks the cell's kv block from the
    scalar-prefetched block table (trailing/unassigned entries re-point at
    the slot's last valid block and ``pl.when`` skips their compute);
    scales are scalar-prefetched flat [N*Hk] and looked up by the SAME
    block index the index map fetched, then folded into the f32 upcast.
    This is the grid `ops.attention._paged_decode_kernel` had before it
    took a slot a step; the port is ROADMAP A1b."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from ..ops.attention import _NEG_INF

    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    slot = b // n_heads
    kvh = (b % n_heads) // group
    attend_len = len_ref[slot]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_start = j * block_k
    assigned = table_ref[slot, j] > 0

    @pl.when((kv_start < attend_len) & assigned)
    def _compute():
        # inside the guard, the clamped index map fetched exactly block
        # table[slot, j] — so its scale is the right one
        flat = jnp.maximum(table_ref[slot, j], 0) * (n_heads // group) + kvh
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32) * sk_ref[flat]
        v_blk = v_ref[0].astype(jnp.float32) * sv_ref[flat]
        kv_pos = kv_start + jax.lax.iota(jnp.int32, block_k)
        valid = kv_pos[None, :] < attend_len
        _decode_softmax_update(q, k_blk, v_blk, valid, m_ref, l_ref, acc_ref)

    @pl.when(j == num_j - 1)
    def _finish():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)


def paged_flash_decode_int8(
    q,
    pool_k,
    pool_v,
    scale_k,
    scale_v,
    block_table,
    attend_lens,
    scale=None,
    interpret: bool = False,
):
    """:func:`rl_tpu.ops.attention.paged_flash_decode` over int8 pools.

    q: [S, 1, H, D] (f32/bf16); pool_k/pool_v: [N, Hk, block, D] int8;
    scale_k/scale_v: [N, Hk] f32. Returns [S, 1, H, D] in q's dtype.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..ops.attention import _scratch

    S, Tq, H, D = q.shape
    if Tq != 1:
        raise ValueError(f"paged_flash_decode_int8 is the T=1 step; got T={Tq}")
    N, Hk, block_k, _ = pool_k.shape
    if H % Hk:
        raise ValueError(f"q heads ({H}) must be a multiple of kv heads ({Hk})")
    group = H // Hk
    max_blocks = block_table.shape[1]
    scale = scale if scale is not None else D**-0.5

    q_b = jnp.moveaxis(q * scale, 2, 1).reshape(S * H, 1, D)
    q_b = jnp.pad(q_b, ((0, 0), (0, 7), (0, 0)))
    table = jnp.asarray(block_table, jnp.int32)
    lens = jnp.asarray(attend_lens, jnp.int32).reshape(S)
    k_flat = pool_k.reshape(N * Hk, block_k, D)
    v_flat = pool_v.reshape(N * Hk, block_k, D)
    sk_flat = scale_k.reshape(N * Hk).astype(jnp.float32)
    sv_flat = scale_v.reshape(N * Hk).astype(jnp.float32)

    def kv_index(b, j, table_ref, len_ref, sk_ref, sv_ref):
        slot = b // H
        kvh = (b % H) // group
        last = jnp.maximum(len_ref[slot] - 1, 0) // block_k
        jj = jnp.minimum(j, last)
        blk = jnp.maximum(table_ref[slot, jj], 0)
        return (blk * Hk + kvh, 0, 0)

    def q_index(b, j, table_ref, len_ref, sk_ref, sv_ref):
        return (b, 0, 0)

    kernel = functools.partial(
        _paged_decode_int8_kernel, block_k=block_k, n_heads=H, group=group
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S * H, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 8, D), q_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 8, D), q_index),
        scratch_shapes=[_scratch((8,)), _scratch((8,)), _scratch((8, D))],
    )
    out = pl.pallas_call(
        kernel,
        name="_paged_decode_int8_kernel",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S * H, 8, D), q.dtype),
        interpret=interpret,
    )(table, lens, sk_flat, sv_flat, q_b, k_flat, v_flat)
    return jnp.moveaxis(out[:, :1].reshape(S, H, 1, D), 1, 2)

"""Pallas flash-attention kernels (TPU) with interpret-mode CPU fallback.

The hot-op kernel slot (pallas_guide.md playbook): a blockwise
online-softmax attention forward that keeps the running (m, l, acc)
statistics in VMEM and streams K/V blocks through the MXU — O(T_block)
memory instead of materializing the [T, T] score matrix. The reference
delegates its fused attention to external engines (vLLM/SGLang) or Triton
(SURVEY.md §2.0); this is the native TPU form.

Three entry points:

- :func:`flash_attention` — training/prefill attention over [B, T, H, D]
  with optional **GQA/MQA** (fewer KV heads than Q heads), **padding
  masks** (``kv_mask`` [B, S]) and **packed-sequence segment ids**
  (``segment_ids`` [B, T]) threaded into both the forward and the flash
  backward kernels — ragged RLHF batches run the kernel path end to end.
- :func:`flash_decode` — the T=1 generation step over a preallocated KV
  cache: grid over KV blocks with the block index CLAMPED at the cache
  fill level (scalar-prefetch index map), so DMA streams only the
  ``cache_len`` prefix of the cache instead of the whole buffer — the
  decode path is bandwidth-bound and this is the bandwidth saver.
- Gradients: ``flash_attention`` carries a ``jax.custom_vjp`` with flash
  backward kernels (FlashAttention-2 recompute scheme): the forward saves
  per-row logsumexp, the backward recomputes P blockwise and accumulates
  dQ (one kernel, kv-sequential) and dK/dV (one kernel, q-sequential) in
  VMEM. Measured on a v5e chip at [4, 4096, 16, 128] bf16 causal:
  fwd 6.3 ms vs 10.7 dense-XLA (1.7x); fwd+bwd 18.3 vs 40.9 (2.2x).

Masking semantics (one mechanism): queries and keys carry int32 segment
ids; position pairs attend only when ids match. A padding ``kv_mask``
lowers to ids (query side all-1, masked keys -1) so padded keys are
invisible to every real query while padded QUERY rows still produce
finite rows (their gradients are zeroed by the loss mask — same contract
as dense attention). Tested against the dense oracle in interpret mode
(values + all three gradients); identical kernels lower to Mosaic on TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["flash_attention", "flash_decode", "paged_flash_decode"]

_NEG_INF = -1e30


def _scratch(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _lane8(x2d):
    """[B, T] -> [B, T, 8]: Mosaic wants the last two block dims (8k, 128k)
    or equal to the array's — a bare [B, T] with (1, block) blocks violates
    that on real TPUs. All 8 lanes carry the value; kernels read lane 0."""
    return jnp.broadcast_to(x2d[..., None], (*x2d.shape, 8))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    *refs, block_q, block_k, seq_len, causal, scale, has_seg
):
    # refs: q [1, block_q, D]; k/v [1, block_k, D] (BLOCKED over the kv grid
    # dim — only one KV tile in VMEM at a time); optional qseg [1, block_q] /
    # kseg [1, block_k]; o [1, block_q, D]; m/l/acc are VMEM scratch
    # persisting across the sequential kv grid dim.
    # seg refs are lane-padded [1, block, 8] (Mosaic minor-dim layout, like
    # lse) — all 8 lanes carry the id; kernels read lane 0
    if has_seg:
        q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    iq = pl.program_id(1)
    j = pl.program_id(2)
    num_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_pos = iq * block_q + jax.lax.iota(jnp.int32, block_q)
    kv_start = j * block_k
    # causal: KV tiles strictly above the diagonal contribute nothing
    needed = jnp.logical_or(not causal, kv_start <= iq * block_q + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        kv_pos = kv_start + jax.lax.iota(jnp.int32, block_k)
        valid = kv_pos[None, :] < seq_len
        if causal:
            valid = valid & (q_pos[:, None] >= kv_pos[None, :])
        if has_seg:
            valid = valid & (qseg_ref[0, :, 0][:, None] == kseg_ref[0, :, 0][None, :])
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

    @pl.when(j == num_kv - 1)
    def _finish():
        l = l_ref[:]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)
        # logsumexp per row, saved for the flash backward. Minor dim 8 is
        # layout padding only (Mosaic wants the last two block dims to be
        # (8k, 128k) or equal to the array's) — all lanes carry the value.
        lse = m_ref[:] + jnp.log(l)
        lse_ref[0] = jnp.broadcast_to(lse[:, None], (lse.shape[0], 8))


def _flash_fwd_bhtd(
    q, k, v, qseg, kseg, *, group, causal, scale, block_q, block_k, interpret
):
    """q [BH, T, D]; k/v [BHk, T, D] with BH = BHk*group; qseg/kseg [B, T]
    int32 or None (both or neither)."""
    BH, T, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    # pad to a common block multiple: out-of-bounds dynamic slices CLAMP
    # their start, which would silently read wrong rows on ragged tails
    lcm = math.lcm(block_q, block_k)
    T_pad = ((T + lcm - 1) // lcm) * lcm
    has_seg = qseg is not None
    B = qseg.shape[0] if has_seg else 1
    heads = BH // B if has_seg else 1  # q heads per batch row (for seg maps)
    if T_pad != T:
        pad = ((0, 0), (0, T_pad - T), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        if has_seg:
            # pads get segment -2: never matches any real id or kv pad (-1)
            seg_pad = ((0, 0), (0, T_pad - T))
            qseg = jnp.pad(qseg, seg_pad, constant_values=-2)
            kseg = jnp.pad(kseg, seg_pad, constant_values=-2)
    grid = (BH, T_pad // block_q, T_pad // block_k)
    kernel = functools.partial(
        _fwd_kernel,
        block_q=block_q,
        block_k=block_k,
        seq_len=T,  # the true length: kv tail masking uses it
        causal=causal,
        scale=scale,
        has_seg=has_seg,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // group, j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // group, j, 0)),
    ]
    operands = [q, k, v]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, block_q, 8), lambda b, i, j: (b // heads, i, 0)),
            pl.BlockSpec((1, block_k, 8), lambda b, i, j: (b // heads, j, 0)),
        ]
        operands += [_lane8(qseg), _lane8(kseg)]
    out, lse = pl.pallas_call(
        kernel,
        name="_fwd_kernel",
        out_shape=(
            jax.ShapeDtypeStruct((BH, T_pad, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T_pad, 8), jnp.float32),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 8), lambda b, i, j: (b, i, 0)),
        ),
        scratch_shapes=[
            _scratch((block_q,)),
            _scratch((block_q,)),
            _scratch((block_q, D)),
        ],
        interpret=interpret,
    )(*operands)
    return out[:, :T], lse[:, :T, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    *refs, block_q, block_k, seq_len, causal, scale, has_seg
):
    """dQ: one q block (grid dim 1) accumulating over kv blocks (dim 2)."""
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref,
         dq_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs
    iq = pl.program_id(1)
    j = pl.program_id(2)
    num_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_pos = iq * block_q + jax.lax.iota(jnp.int32, block_q)
    kv_start = j * block_k
    needed = jnp.logical_or(not causal, kv_start <= iq * block_q + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        kv_pos = kv_start + jax.lax.iota(jnp.int32, block_k)
        valid = (kv_pos[None, :] < seq_len) & (q_pos[:, None] < seq_len)
        if causal:
            valid = valid & (q_pos[:, None] >= kv_pos[None, :])
        if has_seg:
            valid = valid & (qseg_ref[0, :, 0][:, None] == kseg_ref[0, :, 0][None, :])
        p = jnp.where(valid, jnp.exp(s - lse_ref[0, :, 0][:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, :, 0][:, None]) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == num_kv - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    *refs, block_q, block_k, seq_len, causal, scale, has_seg
):
    """dK/dV: one kv block (grid dim 1) accumulating over q blocks (dim 2).

    Runs on the per-Q-head expanded view; GQA reduction over the head
    group happens outside the kernel (avoids cross-program races on the
    shared KV block).
    """
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    jk = pl.program_id(1)
    i = pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    kv_pos = jk * block_k + jax.lax.iota(jnp.int32, block_k)
    q_start = i * block_q
    # causal: q blocks strictly above this kv block contribute nothing
    needed = jnp.logical_or(not causal, q_start + block_q - 1 >= jk * block_k)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        q_pos = q_start + jax.lax.iota(jnp.int32, block_q)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        valid = (kv_pos[None, :] < seq_len) & (q_pos[:, None] < seq_len)
        if causal:
            valid = valid & (q_pos[:, None] >= kv_pos[None, :])
        if has_seg:
            valid = valid & (qseg_ref[0, :, 0][:, None] == kseg_ref[0, :, 0][None, :])
        p = jnp.where(valid, jnp.exp(s - lse_ref[0, :, 0][:, None]), 0.0)
        # dV += P^T @ dO
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, :, 0][:, None]) * scale
        # dK += dS^T @ Q
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == num_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_bhtd(
    q, k, v, o, lse, do, qseg, kseg, *, group, causal, scale, block_q, block_k,
    interpret,
):
    """Flash backward over [BH, T, D] (FlashAttention-2 recompute scheme).

    k/v arrive per Q head (GQA groups already expanded by the caller);
    returns per-Q-head dk/dv — caller reduces over the group.
    """
    BH, T, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    lcm = math.lcm(block_q, block_k)
    T_pad = ((T + lcm - 1) // lcm) * lcm
    has_seg = qseg is not None
    B = qseg.shape[0] if has_seg else 1
    heads = BH // B if has_seg else 1
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if T_pad != T:
        pad3 = ((0, 0), (0, T_pad - T), (0, 0))
        pad2 = ((0, 0), (0, T_pad - T))
        q, k, v, do = (jnp.pad(x, pad3) for x in (q, k, v, do))
        lse = jnp.pad(lse, pad2)
        delta = jnp.pad(delta, pad2)
        if has_seg:
            qseg = jnp.pad(qseg, pad2, constant_values=-2)
            kseg = jnp.pad(kseg, pad2, constant_values=-2)
    # lane-pad to [BH, T_pad, 8] (Mosaic minor-dim layout, see fwd)
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, 8))
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 8))
    kw = dict(
        block_q=block_q, block_k=block_k, seq_len=T, causal=causal,
        scale=scale, has_seg=has_seg,
    )
    common_in = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),   # q (by i)
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // group, j, 0)),  # k
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // group, j, 0)),  # v
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),   # do (by i)
        pl.BlockSpec((1, block_q, 8), lambda b, i, j: (b, i, 0)),   # lse (by i)
        pl.BlockSpec((1, block_q, 8), lambda b, i, j: (b, i, 0)),   # delta (by i)
    ]
    operands = [q, k, v, do, lse, delta]
    if has_seg:
        common_in += [
            pl.BlockSpec((1, block_q, 8), lambda b, i, j: (b // heads, i, 0)),
            pl.BlockSpec((1, block_k, 8), lambda b, i, j: (b // heads, j, 0)),
        ]
        operands += [_lane8(qseg), _lane8(kseg)]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        name="_bwd_dq_kernel",
        out_shape=jax.ShapeDtypeStruct((BH, T_pad, D), q.dtype),
        grid=(BH, T_pad // block_q, T_pad // block_k),
        in_specs=common_in,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[_scratch((block_q, D))],
        interpret=interpret,
    )(*operands)
    # dkv grid: (BH, kv block, q block) — q-side refs index by the LAST dim
    dkv_in = [
        pl.BlockSpec((1, block_q, D), lambda b, jk, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, jk, i: (b // group, jk, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, jk, i: (b // group, jk, 0)),
        pl.BlockSpec((1, block_q, D), lambda b, jk, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 8), lambda b, jk, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 8), lambda b, jk, i: (b, i, 0)),
    ]
    dkv_operands = [q, k, v, do, lse, delta]
    if has_seg:
        dkv_in += [
            pl.BlockSpec((1, block_q, 8), lambda b, jk, i: (b // heads, i, 0)),
            pl.BlockSpec((1, block_k, 8), lambda b, jk, i: (b // heads, jk, 0)),
        ]
        dkv_operands += [_lane8(qseg), _lane8(kseg)]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        name="_bwd_dkv_kernel",
        out_shape=(
            jax.ShapeDtypeStruct((BH, T_pad, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T_pad, D), v.dtype),
        ),
        grid=(BH, T_pad // block_k, T_pad // block_q),
        in_specs=dkv_in,
        out_specs=(
            pl.BlockSpec((1, block_k, D), lambda b, jk, i: (b, jk, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, jk, i: (b, jk, 0)),
        ),
        scratch_shapes=[_scratch((block_k, D)), _scratch((block_k, D))],
        interpret=interpret,
    )(*dkv_operands)
    return dq[:, :T], dk[:, :T], dv[:, :T]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _seg_from_args(kv_mask, segment_ids, B, T, S):
    """Lower (kv_mask | segment_ids) to (qseg, kseg) int32 or (None, None).

    Padding mask: queries all segment 1, masked keys segment -1 — padded
    keys invisible to every query; padded QUERY rows still get finite
    outputs (ignored + zero-grad via the loss mask, like dense attention).
    """
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)
        return seg, seg
    if kv_mask is not None:
        kseg = jnp.where(kv_mask.astype(bool), 1, -1).astype(jnp.int32)
        qseg = jnp.ones((B, T), jnp.int32)
        return qseg, kseg
    return None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_core(q, k, v, qseg, kseg, causal, scale, block_q, block_k, interpret):
    out, _ = _flash_core_fwd(
        q, k, v, qseg, kseg, causal, scale, block_q, block_k, interpret
    )
    return out


def _expand_heads(x, B, Hk, group):
    """[B, S, Hk, D] -> [B*Hk, S, D] (kv layout for the kernels)."""
    return jnp.moveaxis(x, 2, 1).reshape(B * Hk, x.shape[1], x.shape[-1])


def _flash_core_fwd(q, k, v, qseg, kseg, causal, scale, block_q, block_k, interpret):
    B, T, H, D = q.shape
    Hk = k.shape[2]
    group = H // Hk
    q_b = jnp.moveaxis(q, 2, 1).reshape(B * H, T, D)
    k_b = _expand_heads(k, B, Hk, group)
    v_b = _expand_heads(v, B, Hk, group)
    o, lse = _flash_fwd_bhtd(
        q_b, k_b, v_b, qseg, kseg,
        group=group, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    out = jnp.moveaxis(o.reshape(B, H, T, D), 1, 2)
    return out, (q, k, v, qseg, kseg, o, lse)


def _flash_core_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, qseg, kseg, o_bhtd, lse = res
    B, T, H, D = q.shape
    Hk = k.shape[2]
    group = H // Hk
    q_b = jnp.moveaxis(q, 2, 1).reshape(B * H, T, D)
    k_b = _expand_heads(k, B, Hk, group)
    v_b = _expand_heads(v, B, Hk, group)
    do = jnp.moveaxis(g, 2, 1).reshape(B * H, T, D)
    dq, dk, dv = _flash_bwd_bhtd(
        q_b, k_b, v_b, o_bhtd, lse, do, qseg, kseg,
        group=group, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    dq = jnp.moveaxis(dq.reshape(B, H, T, D), 1, 2)
    # dk/dv come back per Q head: reduce over each KV head's group
    dk = jnp.moveaxis(dk.reshape(B, Hk, group, T, D).sum(axis=2), 1, 2)
    dv = jnp.moveaxis(dv.reshape(B, Hk, group, T, D).sum(axis=2), 1, 2)
    none_seg = (
        None
        if qseg is None
        else np.zeros(qseg.shape, jax.dtypes.float0)
    )
    return dq, dk, dv, none_seg, (
        None if kseg is None else np.zeros(kseg.shape, jax.dtypes.float0)
    )


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
    kv_mask: jax.Array | None = None,
    segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Blockwise-online-softmax attention over [B, T, H, D] inputs.

    Args:
        q: [B, T, H, D] queries.
        k, v: [B, T, Hk, D] — ``Hk == H`` for MHA; any divisor of H for
            GQA/MQA (each KV head serves ``H // Hk`` query heads).
        kv_mask: optional [B, T] bool — False keys are invisible to every
            query (left- or right-padded ragged batches).
        segment_ids: optional [B, T] int — attention only within matching
            ids (packed sequences). Mutually exclusive with ``kv_mask``.

    Default 1024x1024 blocks, tuned on a v5e chip at [4, 4096, 16, 128]
    bf16 causal: 6.0 ms/iter vs 9.7 ms for dense XLA attention (1.6x) —
    128x128 blocks ran 45.7 ms (grid-step overhead dominates), so keep
    blocks large; VMEM use at 1024 is ~6 MB. Blocks are clamped to T.
    """
    if kv_mask is not None and segment_ids is not None:
        raise ValueError("pass kv_mask or segment_ids, not both")
    B, T, H, D = q.shape
    Hk = k.shape[2]
    if H % Hk:
        raise ValueError(f"q heads ({H}) must be a multiple of kv heads ({Hk})")
    scale = scale if scale is not None else D**-0.5
    qseg, kseg = _seg_from_args(kv_mask, segment_ids, B, T, k.shape[1])
    return _flash_core(
        q, k, v, qseg, kseg, causal, scale, block_q, block_k, interpret
    )


# ---------------------------------------------------------------------------
# decode (T=1 over a KV cache)
# ---------------------------------------------------------------------------


def _decode_kernel(len_ref, *refs, block_k, has_seg):
    """One grid step = one KV block of the cache for one (batch, q-head).

    q block is [1, 8, D] (row 0 real — Mosaic sublane padding); the kv
    block index is CLAMPED at the cache fill level by the index map, so
    trailing grid steps re-point at the last needed block (Pallas skips
    the re-fetch) and `pl.when` skips their compute: DMA cost tracks
    cache_len, not cache capacity.
    """
    if has_seg:
        q_ref, k_ref, v_ref, kseg_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    j = pl.program_id(1)
    num_kv = pl.num_programs(1)
    cache_len = len_ref[0]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_start = j * block_k

    @pl.when(kv_start < cache_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [8, D]
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        kv_pos = kv_start + jax.lax.iota(jnp.int32, block_k)
        valid = kv_pos[None, :] < cache_len
        if has_seg:
            valid = valid & (kseg_ref[0, :, 0] > 0)[None, :]
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

    @pl.when(j == num_kv - 1)
    def _finish():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    scale: float | None = None,
    kv_mask: jax.Array | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Single-token decode attention over a preallocated KV cache.

    Args:
        q: [B, 1, H, D] — the current step's queries.
        k_cache, v_cache: [B, S, Hk, D] preallocated cache (``Hk`` may be
            a divisor of H — GQA).
        cache_len: int32 scalar — number of filled cache slots. Blocks at
            or beyond it are neither fetched nor computed (scalar-prefetch
            clamped index map): decode bandwidth tracks the fill level.
        kv_mask: optional [B, S] bool — False slots are invisible (e.g.
            left-padding in the prompt region).

    Returns [B, 1, H, D].
    """
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, H, D = q.shape
    if Tq != 1:
        raise ValueError(f"flash_decode is the T=1 step; got T={Tq}")
    S = k_cache.shape[1]
    Hk = k_cache.shape[2]
    if H % Hk:
        raise ValueError(f"q heads ({H}) must be a multiple of kv heads ({Hk})")
    group = H // Hk
    scale = scale if scale is not None else D**-0.5
    block_k = min(block_k, S)
    if S % block_k:
        raise ValueError(f"cache size {S} must be a multiple of block_k {block_k}")
    num_blocks = S // block_k

    # [B, 1, H, D] -> [BH, 8, D] (sublane-pad the single row)
    q_b = jnp.moveaxis(q * scale, 2, 1).reshape(B * H, 1, D)
    q_b = jnp.pad(q_b, ((0, 0), (0, 7), (0, 0)))
    k_b = _expand_heads(k_cache, B, Hk, group)
    v_b = _expand_heads(v_cache, B, Hk, group)
    has_seg = kv_mask is not None

    lengths = jnp.asarray(cache_len, jnp.int32).reshape(1)

    def clamp(j, len_ref):
        # last block that contains filled slots; never negative
        last = jnp.maximum(len_ref[0] - 1, 0) // block_k
        return jnp.minimum(j, last)

    kernel = functools.partial(_decode_kernel, block_k=block_k, has_seg=has_seg)
    in_specs = [
        pl.BlockSpec((1, 8, D), lambda b, j, len_ref: (b, 0, 0)),
        pl.BlockSpec(
            (1, block_k, D),
            lambda b, j, len_ref: (b // group, clamp(j, len_ref), 0),
        ),
        pl.BlockSpec(
            (1, block_k, D),
            lambda b, j, len_ref: (b // group, clamp(j, len_ref), 0),
        ),
    ]
    operands = [q_b, k_b, v_b]
    if has_seg:
        kseg = jnp.where(kv_mask.astype(bool), 1, -1).astype(jnp.int32)
        in_specs.append(
            pl.BlockSpec(
                (1, block_k, 8),
                lambda b, j, len_ref: (b // H, clamp(j, len_ref), 0),
            )
        )
        operands.append(_lane8(kseg))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, num_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 8, D), lambda b, j, len_ref: (b, 0, 0)),
        scratch_shapes=[_scratch((8,)), _scratch((8,)), _scratch((8, D))],
    )
    out = pl.pallas_call(
        kernel,
        name="_decode_kernel",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 8, D), q.dtype),
        interpret=interpret,
    )(lengths, *operands)
    return jnp.moveaxis(out[:, :1].reshape(B, H, 1, D), 1, 2)


def _dense_reference(q, k, v, causal, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    T, S = s.shape[-2], s.shape[-1]
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool))
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


# K and V blocks of one paged-decode step (both, double-buffered by the
# pipeline) may take this much VMEM; the step's f32 temporaries take about
# as much again, and the two stay well inside a v5e's 16 MiB scoped default.
_PAGED_TILE_BYTES = 4 << 20
# keys a paged-decode step attends at least (one full lane row of scores)
_PAGED_STEP_KEYS = 128


def paged_heads_per_row(n_kv: int, D: int, dtype) -> int:
    """KV heads a paged pool stores side by side in one lane row: ``r``.

    A ``[N, Hk, block, D]`` pool with ``D < 128`` does not lie on the
    device as the decode kernel reads it. The TPU's default layout of an
    array whose minor dimension is under 128 lanes wide moves another
    dimension minor-most (at ``[2049, 16, 16, 64]`` bf16 the block index:
    ``{0,3,2,1}``) rather than pad every row to 128 lanes, while a Pallas
    operand is held to row-major; so every program that calls the kernel
    relays each pool whole on the way in and again on the way out, and
    the row-major copy the kernel reads is lane-padded to ``128 / D``
    times the pool's bytes. Stored as ``[N, Hk // r, block, r * D]`` with
    ``r = 128 // D`` (kv heads ``r*j .. r*j + r - 1`` in one 128-lane row)
    the pool's default layout IS row-major, and nothing is relaid or
    padded. ``r`` is 1 (the pool as it was) when the rows are already
    128 wide or wider, when ``D`` does not divide 128, when the kv heads
    do not come in whole rows, and for int8 pools, whose scales and
    kernel are per (block, kv head).
    """
    if D >= 128 or 128 % D or jnp.dtype(dtype) == jnp.int8:
        return 1
    r = 128 // D
    return r if n_kv % r == 0 else 1


def unpack_kv_heads(x: jax.Array, r: int) -> jax.Array:
    """Packed pool blocks ``[..., Hk // r, block, r * D]`` as
    ``[..., Hk, block, D]``: lanes ``i*D .. (i+1)*D`` of row j are kv
    head ``r*j + i``. A copy: for gathered blocks, never a whole pool."""
    *lead, Hp, block, W = x.shape
    x = x.reshape(*lead, Hp, block, r, W // r)
    return jnp.moveaxis(x, -2, -3).reshape(*lead, Hp * r, block, W // r)


def _paged_pages(max_blocks, block_k, n_kv, D, itemsize):
    """Table entries one paged-decode step takes: enough for
    ``_PAGED_STEP_KEYS`` keys, halved until the double-buffered K and V
    blocks fit ``_PAGED_TILE_BYTES``, and never more than the table has.
    ``n_kv`` and ``D`` are the pool's rows and lane width as it is stored
    (packed: ``Hk // r`` and ``r * D``); only a pool left under 128 lanes
    wide is padded to them in VMEM."""
    pages = min(max(1, -(-_PAGED_STEP_KEYS // block_k)), max_blocks)
    entry = 2 * 2 * n_kv * block_k * max(D, 128) * itemsize
    while pages > 1 and pages * entry > _PAGED_TILE_BYTES:
        pages //= 2
    return pages


def _paged_decode_kernel(
    table_ref, len_ref, slot_ref, chunk_ref, row_ref, q_ref, *refs, block_k, pages
):
    """One grid step = one CHUNK of one slot: ``pages`` block-table entries,
    for every kv head and every query head of its GQA group at once.

    The grid is the list of live chunks (``slot_ref``/``chunk_ref``, one
    pair a step, a slot's chunks in a row), as long as the slots' lengths
    make it: there is no step for table entries a slot does not reach, so
    a call's time follows the live keys. Each of a chunk's entries is its
    own operand, a whole ``[Hk, block, D]`` run of the head-major pool
    that the index map picks from the scalar-prefetched block table — the
    pool is read IN PLACE, no per-step gather of the slot's KV into a
    contiguous buffer (the copy the XLA paged path pays). ``table_ref``
    holds 0 (the scratch block: finite, never attended) wherever an entry
    is not attendable: unassigned, scratch, or starting at or past
    ``attend_len``; such an entry's keys are masked, as are the keys past
    ``attend_len`` inside the last entry, and a slot with no valid key
    returns zeros. Every step writes the slot's output as far as it has
    come; ``row_ref`` (read by the output's index map alone) sends all
    but a slot's last step to a spare output block, so each real block is
    written back once, by the step that completes it.
    """
    k_refs, v_refs = refs[:pages], refs[pages : 2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages :]
    step = pl.program_id(0)
    slot, chunk = slot_ref[step], chunk_ref[step]
    span = pages * block_k

    @pl.when(chunk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    key = jax.lax.broadcasted_iota(jnp.int32, (1, 1, span), 2)
    assigned = jnp.zeros((1, 1, span), jnp.int32)
    for i in range(pages):
        assigned = jnp.where(
            key // block_k == i, table_ref[slot, chunk * pages + i], assigned
        )
    valid = (assigned > 0) & (chunk * span + key < len_ref[slot])
    q = q_ref[...]  # [Hk, Gp, D], scaled
    k = jnp.concatenate([r[...] for r in k_refs], axis=1)  # [Hk, span, D]
    v = jnp.concatenate([r[...].astype(jnp.float32) for r in v_refs], axis=1)
    # bf16 x bf16 products are exact in the f32 accumulator: a bf16 query
    # on a bf16 pool scores as the f32 product does, unconverted
    score_dtype = jnp.promote_types(q.dtype, k.dtype)
    s = jax.lax.dot_general(
        q.astype(score_dtype), k.astype(score_dtype),
        (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32,
    )  # [Hk, Gp, span]
    s = jnp.where(valid, s, _NEG_INF)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # a masked key weighs exactly 0, also while every key so far is masked
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m - m_new)
    l = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # [Hk, Gp, D]
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l, acc
    o_ref[...] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_flash_decode(
    q: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    block_table: jax.Array,
    attend_lens: jax.Array,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-token decode attention over a PAGED KV pool (the vLLM
    paged-attention read, Pallas-native — the chip-side upgrade of
    ``rl_tpu.models.transformer._paged_attention``'s XLA gather path).

    Args:
        q: [S, 1, H, D] — one query per sequence slot.
        pool_k, pool_v: [N, Hk // r, block, r * D] HEAD-MAJOR shared block
            pools, ``r`` kv heads side by side in a lane row
            (:func:`paged_heads_per_row`; ``r`` is read off the shapes,
            the pool's lane width over ``q``'s, and is 1 for the plain
            ``[N, Hk, block, D]``). ``Hk`` may divide H (GQA). One table
            entry is one contiguous ``[Hk // r, block, r * D]`` run;
            viewed as [N*Hk/r, block, r*D] (the Mosaic block dims are the
            last two). Block 0 is reserved scratch (never attended).
        block_table: [S, max_blocks] int32 — per-slot pool indices;
            -1 = unassigned.
        attend_lens: [S] int32 — attendable positions per slot (for the
            decode-after-write step this is ``len + 1``).

    Returns [S, 1, H, D]. A grid step (:func:`_paged_decode_kernel`)
    DMAs ``pages`` whole table entries of one slot, each by an index map
    that reads the scalar-prefetched block table, and attends every head
    to them; the grid's length is dynamic, the sum over slots of
    ``cdiv(attend_lens, pages * block)``, so there is no contiguous
    per-slot copy and no fetch or compute past a slot's length. ``pages``
    follows from ``block``, the stored row shape and the pool's dtype
    (:func:`_paged_pages`).

    A packed pool is, to the kernel, a pool of ``Hk // r`` heads of width
    ``r * D``, and the kernel is called as it is. The query is made
    BLOCK-DIAGONAL: packed head j gets ``r * group`` query rows, those of
    kv head ``r*j + i`` holding their ``D`` values in lanes ``i*D ..
    (i+1)*D`` and zeros elsewhere. A row's score against a packed key row
    is then its own head's score plus exact zeros, its softmax is its own
    head's, and lanes ``i*D .. (i+1)*D`` of its output row are its head's
    output (the other lanes, the same weights on the neighbours' values,
    are dropped). The MXU contracts 128 lanes either way.
    """
    from jax.experimental.pallas import tpu as pltpu

    S, Tq, H, D = q.shape
    if Tq != 1:
        raise ValueError(f"paged_flash_decode is the T=1 step; got T={Tq}")
    N, Hp, block_k, W = pool_k.shape  # Hp rows of r kv heads, W = r * D lanes
    if W % D:
        raise ValueError(f"pool rows ({W} wide) must hold whole heads of {D}")
    r = W // D
    Hk = Hp * r
    if H % Hk:
        raise ValueError(f"q heads ({H}) must be a multiple of kv heads ({Hk})")
    group = H // Hk
    scale = scale if scale is not None else D**-0.5
    pages = _paged_pages(
        block_table.shape[1], block_k, Hp, W, pool_k.dtype.itemsize
    )

    # [S, 1, H, D] -> [S*Hp, Gp, W]: the query heads of a pool row's kv
    # heads share its K/V as the rows of one product (sublane-padded to a
    # multiple of 8), each in its own head's lanes (block-diagonal)
    q_b = (q * scale).reshape(S * Hp, r, group, 1, D)
    if r > 1:
        own = jnp.eye(r, dtype=bool)[:, None, :, None]  # [r, 1, r, 1]
        q_b = jnp.where(own, q_b, 0)  # [.., r, group, r, D]
    q_b = q_b.reshape(S * Hp, r * group, W)
    q_b = jnp.pad(q_b, ((0, 0), (0, -(r * group) % 8), (0, 0)))
    rows = q_b.shape[1]
    lens = jnp.asarray(attend_lens, jnp.int32).reshape(S)
    # the table as the index maps read it: 0 (scratch) for every entry
    # that is unassigned (-1) or starts at or past the slot's length,
    # padded to a whole number of chunks
    table = jnp.asarray(block_table, jnp.int32)
    starts = jnp.arange(table.shape[1], dtype=jnp.int32) * block_k
    table = jnp.where(starts[None, :] < lens[:, None], jnp.maximum(table, 0), 0)
    table = jnp.pad(table, ((0, 0), (0, -table.shape[1] % pages)))
    # the grid: each slot's live chunks in a row (one even where it has no
    # key, so that its output is written). Step w belongs to the slot that
    # follows those whose chunks end at or before w, and is its chunk
    # number w less the chunks of those slots.
    n_chunks = table.shape[1] // pages
    live = jnp.clip(-(-lens // (pages * block_k)), 1, n_chunks)  # [S]
    ends = jnp.cumsum(live)
    w = jnp.arange(S * n_chunks, dtype=jnp.int32)
    done = ends[:, None] <= w[None, :]  # [S, S * n_chunks]
    slot_of = jnp.minimum(jnp.sum(done, axis=0, dtype=jnp.int32), S - 1)
    chunk_of = w - jnp.sum(jnp.where(done, live[:, None], 0), axis=0, dtype=jnp.int32)
    chunk_of = jnp.minimum(chunk_of, n_chunks - 1)  # past the grid's end: unused
    # output block of a step: the slot's own from its last chunk, the spare
    # block S from the others. The pipeline may write a block back after
    # every step, and two write-backs of one block in flight are unordered.
    last = jnp.any(ends[:, None] == w[None, :] + 1, axis=0)
    row_of = jnp.where(last, slot_of, S)
    # head-major pool -> [N*Hp, block, W] (a reshape, not a copy)
    k_flat = pool_k.reshape(N * Hp, block_k, W)
    v_flat = pool_v.reshape(N * Hp, block_k, W)

    def q_index(w, table_ref, len_ref, slot_ref, chunk_ref, row_ref):
        return (slot_ref[w], 0, 0)

    def o_index(w, table_ref, len_ref, slot_ref, chunk_ref, row_ref):
        return (row_ref[w], 0, 0)

    def kv_spec(i):
        def index(w, table_ref, len_ref, slot_ref, chunk_ref, row_ref):
            entry = chunk_ref[w] * pages + i
            return (table_ref[slot_ref[w], entry], 0, 0)  # in units of Hp rows

        return pl.BlockSpec((Hp, block_k, W), index)

    kv_specs = [kv_spec(i) for i in range(pages)]
    kernel = functools.partial(_paged_decode_kernel, block_k=block_k, pages=pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(ends[-1],),
        in_specs=[pl.BlockSpec((Hp, rows, W), q_index), *kv_specs, *kv_specs],
        out_specs=pl.BlockSpec((Hp, rows, W), o_index),
        scratch_shapes=[
            _scratch((Hp, rows, 1)), _scratch((Hp, rows, 1)), _scratch((Hp, rows, W))
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="_paged_decode_kernel",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(((S + 1) * Hp, rows, W), q.dtype),
        interpret=interpret,
    )(table, lens, slot_of, chunk_of, row_of, q_b, *[k_flat] * pages, *[v_flat] * pages)
    # a query row's own lanes: row block i, lane block i
    out = out[: S * Hp, : r * group].reshape(S, Hp, r, group, r, D)
    out = jnp.stack([out[:, :, i, :, i] for i in range(r)], axis=2)
    return out.reshape(S, 1, H, D)

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


def _decode_softmax_update(q, k_blk, v_blk, valid, m_ref, l_ref, acc_ref):
    """The shared decode-side online-softmax recurrence: score one KV
    block, mask, and fold it into the running (m, l, acc) scratch state
    (used by both the dense-cache and paged decode kernels)."""
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


def _paged_decode_kernel(table_ref, len_ref, *refs, block_k, n_heads):
    """One grid step = one BLOCK-TABLE entry for one (slot, q-head).

    The kv block fetched for grid cell (b, j) is chosen by the index map
    from the scalar-prefetched block table — the pool is read IN PLACE,
    no per-step gather of the slot's KV into a contiguous buffer (the
    copy the XLA paged path pays). Trailing/unassigned entries re-point
    at the slot's last valid block (Pallas skips the re-fetch) and
    ``pl.when`` skips their compute.
    """
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    slot = b // n_heads
    attend_len = len_ref[slot]  # number of attendable positions

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_start = j * block_k
    assigned = table_ref[slot, j] > 0  # 0 = reserved scratch, -1 = unassigned

    @pl.when((kv_start < attend_len) & assigned)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [8, D]
        k_blk = k_ref[0].astype(jnp.float32)  # [block_k, D]
        v_blk = v_ref[0].astype(jnp.float32)
        kv_pos = kv_start + jax.lax.iota(jnp.int32, block_k)
        valid = kv_pos[None, :] < attend_len
        _decode_softmax_update(q, k_blk, v_blk, valid, m_ref, l_ref, acc_ref)

    @pl.when(j == num_j - 1)
    def _finish():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)


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
        pool_k, pool_v: [N, Hk, block, D] HEAD-MAJOR shared block pools
            (``Hk`` may divide H — GQA); viewed as [N*Hk, block, D] so
            the Mosaic block dims are (block, D). Block 0 is reserved
            scratch (never read).
        block_table: [S, max_blocks] int32 — per-slot pool indices;
            -1 = unassigned.
        attend_lens: [S] int32 — attendable positions per slot (for the
            decode-after-write step this is ``len + 1``).

    Returns [S, 1, H, D]. The index map reads the scalar-prefetched
    block table, so each (slot, head, j) grid cell DMAs exactly its
    block's single KV head from the pool — no contiguous per-slot copy.
    """
    from jax.experimental.pallas import tpu as pltpu

    S, Tq, H, D = q.shape
    if Tq != 1:
        raise ValueError(f"paged_flash_decode is the T=1 step; got T={Tq}")
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
    # head-major pool -> [N*Hk, block, D] (a reshape, not a copy)
    k_flat = pool_k.reshape(N * Hk, block_k, D)
    v_flat = pool_v.reshape(N * Hk, block_k, D)

    def kv_index(b, j, table_ref, len_ref):
        slot = b // H
        kvh = (b % H) // group
        # clamp trailing entries at the slot's last data-bearing block so
        # Pallas re-points (and skips) instead of fetching garbage
        last = jnp.maximum(len_ref[slot] - 1, 0) // block_k
        jj = jnp.minimum(j, last)
        blk = jnp.maximum(table_ref[slot, jj], 0)
        return (blk * Hk + kvh, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, block_k=block_k, n_heads=H
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S * H, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 8, D), lambda b, j, table_ref, len_ref: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 8, D), lambda b, j, table_ref, len_ref: (b, 0, 0)),
        scratch_shapes=[_scratch((8,)), _scratch((8,)), _scratch((8, D))],
    )
    out = pl.pallas_call(
        kernel,
        name="_paged_decode_kernel",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S * H, 8, D), q.dtype),
        interpret=interpret,
    )(table, lens, q_b, k_flat, v_flat)
    return jnp.moveaxis(out[:, :1].reshape(S, H, 1, D), 1, 2)

"""Ring attention: exact attention over a context-parallel mesh axis.

The reference has NO native sequence/context parallelism (SURVEY.md §2.13 —
long sequences are delegated to vLLM/SGLang or avoided via slice sampling);
this is the greenfield native component the TPU framework needs for
RLHF-scale training (Liu et al. 2023, "Ring Attention with Blockwise
Transformers"; Sebulba/Podracer-style ICI usage).

Design: the sequence axis is sharded over mesh axis ``context``. Each device
keeps its Q shard fixed; K/V shards rotate around the ring with
``lax.ppermute`` (neighbor-to-neighbor ICI hops, bandwidth-optimal), and a
blockwise online-softmax accumulates exact attention — numerically identical
to full attention, with memory O(T_local) instead of O(T).

``ring_attention`` is the shard_map-wrapped public entry;
``_ring_attention_inner`` is the per-device program (usable directly inside
an existing shard_map). Causal masking uses global positions derived from
``axis_index``, so it is correct regardless of rotation step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "attention_reference"]


def attention_reference(q, k, v, causal: bool = True, scale: float | None = None):
    """Plain full attention [B, T, H, D] — the correctness oracle."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        T, S = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block_attn(q, k, v, q_pos, kv_pos, scale, causal, kv_mask=None):
    """Scores+weighted values for one (Q_local, KV_block) pair with running
    softmax stats. Returns (o_blk, m_blk, l_blk)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = q_pos[:, None] >= kv_pos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    if kv_mask is not None:  # padding mask over this KV block [B, Tk]
        s = jnp.where(kv_mask[:, None, None, :], s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)  # [B, H, Tq]
    # guard fully-masked rows (all -inf): exp(-inf - -inf) -> use where
    safe_m = jnp.where(jnp.isfinite(m_blk), m_blk, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l_blk = jnp.sum(p, axis=-1)  # [B, H, Tq]
    o_blk = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o_blk, jnp.where(jnp.isfinite(m_blk), m_blk, -jnp.inf), l_blk


def _ring_attention_inner(
    q, k, v, kv_mask, axis_name: str, causal: bool, scale: float | None
):
    B, Tq, H, D = q.shape
    scale = scale if scale is not None else D**-0.5
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    q_pos = my_idx * Tq + jnp.arange(Tq)

    def combine(carry, o_blk, m_blk, l_blk):
        o, m, l = carry  # o [B,Tq,H,D]; m,l [B,H,Tq]
        m_new = jnp.maximum(m, m_blk)
        # correction factors (0 when the old/new side was empty)
        c_old = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
        c_blk = jnp.where(jnp.isfinite(m_blk), jnp.exp(m_blk - m_new), 0.0)
        l_new = l * c_old + l_blk * c_blk
        o_new = (
            o * jnp.moveaxis(c_old, 1, -1)[..., None]
            + o_blk * jnp.moveaxis(c_blk, 1, -1)[..., None]
        )
        return o_new, m_new, l_new

    def body(i, carry):
        o, m, l, k_blk, v_blk, mask_blk = carry
        kv_idx = (my_idx - i) % n
        kv_pos = kv_idx * Tq + jnp.arange(Tq)
        o_blk, m_blk, l_blk = _block_attn(
            q, k_blk, v_blk, q_pos, kv_pos, scale, causal, mask_blk
        )
        o, m, l = combine((o, m, l), o_blk, m_blk, l_blk)
        # rotate KV (and its padding mask) to the next device (neighbor hop)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        if mask_blk is not None:
            mask_blk = lax.ppermute(mask_blk, axis_name, perm)
        return o, m, l, k_blk, v_blk, mask_blk

    o0 = jnp.zeros_like(q)
    m0 = jnp.full((B, H, Tq), -jnp.inf, q.dtype)
    l0 = jnp.zeros((B, H, Tq), q.dtype)
    o, m, l, _, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v, kv_mask))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros, not NaN
    return o / jnp.moveaxis(l, 1, -1)[..., None]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "context",
    causal: bool = True,
    scale: float | None = None,
    kv_mask: jax.Array | None = None,
) -> jax.Array:
    """Exact attention with the sequence sharded over ``axis_name``.

    Inputs/outputs are GLOBAL arrays [B, T, H, D]; shard_map splits T over
    the mesh axis (T must divide evenly). ``kv_mask`` [B, T] masks padded key
    positions (rotates around the ring with K/V). Compose inside jit — XLA
    overlaps the ppermute hops with the block computation.
    """
    spec = P(None, axis_name, None, None)
    if kv_mask is None:
        inner = functools.partial(
            _ring_attention_inner,
            kv_mask=None,
            axis_name=axis_name,
            causal=causal,
            scale=scale,
        )
        return shard_map(
            lambda q, k, v: inner(q, k, v),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    inner = functools.partial(
        _ring_attention_inner, axis_name=axis_name, causal=causal, scale=scale
    )
    return shard_map(
        lambda q, k, v, m: inner(q, k, v, m),
        mesh=mesh,
        in_specs=(spec, spec, spec, P(None, axis_name)),
        out_specs=spec,
        check_vma=False,
    )(q, k, v, kv_mask.astype(bool))

"""Ulysses-style sequence parallelism: head-scatter all-to-all.

The alternative to ring attention (SURVEY.md §5 "long-context"): instead of
rotating KV, one ``all_to_all`` converts sequence sharding into head
sharding, full-sequence attention runs locally per head group, and a second
``all_to_all`` restores sequence sharding. Two collective hops total —
cheaper than a ring when heads >= devices and T_local is small; ring wins at
very long T (constant memory).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import attention_reference

__all__ = ["ulysses_attention"]


def _inner(q, k, v, axis_name: str, causal: bool):
    # [B, T_loc, H, D] --all_to_all--> [B, T, H_loc, D]
    def seq_to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    q, k, v = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    o = attention_reference(q, k, v, causal=causal)
    return heads_to_seq(o)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "context",
    causal: bool = True,
) -> jax.Array:
    """Exact attention, sequence sharded over ``axis_name``; requires the
    head count to be divisible by the axis size."""
    n = mesh.shape[axis_name]
    if q.shape[2] % n:
        raise ValueError(f"num_heads={q.shape[2]} not divisible by |{axis_name}|={n}")
    spec = P(None, axis_name, None, None)
    inner = functools.partial(_inner, axis_name=axis_name, causal=causal)
    return shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)

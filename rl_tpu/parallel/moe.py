"""Mixture-of-Experts FFN with expert parallelism (round 4; §2.13 EP).

The reference has NO expert parallelism (SURVEY §2.13 notes the gap and
this framework reserved the mesh axis for it) — this module goes beyond
parity, TPU-first: Switch/Mixtral-style top-k routing with fixed expert
capacity (static shapes: overflow tokens drop, the XLA-native form of
load balancing), experts SHARDED over the ``expert`` mesh axis, and the
dispatch/return movement as ``lax.all_to_all`` collectives inside
``shard_map`` — the canonical scaling-book EP recipe (tokens a2a to their
experts' devices, FFN there, a2a back, gate-combine).

Two execution paths share one parameter layout (W1 [E, d, f], W2 [E, f, d],
router [d, E]):

- :func:`moe_ffn_dense` — single-device einsum reference (the ORACLE);
- :func:`moe_ffn_ep` — shard_map + all_to_all expert-parallel execution,
  verified token-exact against the oracle for every kept token.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "init_moe_params",
    "moe_param_specs",
    "moe_ffn_dense",
    "moe_ffn_ep",
    "moe_dispatch",
    "moe_load_balancing_loss",
]


def moe_param_specs(d_model: int, d_ff: int, n_experts: int):
    """The single source of truth for MoE parameter shapes + init scales
    (shared by :func:`init_moe_params` and the in-model flax _MoEFFN)."""
    return {
        "router": ((d_model, n_experts), d_model**-0.5),
        "w1": ((n_experts, d_model, d_ff), d_model**-0.5),
        "w2": ((n_experts, d_ff, d_model), d_ff**-0.5),
    }


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int, dtype=jnp.float32):
    specs = moe_param_specs(d_model, d_ff, n_experts)
    keys = jax.random.split(key, len(specs))
    return {
        name: (jax.random.normal(k, shape) * std).astype(dtype)
        for k, (name, (shape, std)) in zip(keys, specs.items())
    }


def moe_dispatch(logits, top_k: int, capacity: int):
    """Top-k gating with fixed per-expert capacity (Switch-style).

    Args:
        logits: [n, E] router logits.
        top_k: experts per token.
        capacity: max tokens PER EXPERT (static; overflow drops — first
            choices claim capacity before second choices, the standard
            slot-major priority).

    Returns:
        dispatch: [n, E, C] one-hot token→(expert, slot) assignment.
        combine: [n, E, C] gate-weighted dispatch (the return weights).
    """
    n, E = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, top_k)  # [n, k]
    topv = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)

    # slot-major ordering: all first choices rank before any second choice
    flat_e = topi.T.reshape(-1)  # [k*n]
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # [k*n, E]
    pos = jnp.cumsum(oh, axis=0) - oh  # position within the expert queue
    slot = jnp.sum(pos * oh, axis=-1)  # [k*n]
    keep = slot < capacity
    disp_flat = (
        jax.nn.one_hot(flat_e, E, dtype=jnp.float32)[:, :, None]
        * jax.nn.one_hot(jnp.minimum(slot, capacity - 1), capacity)[:, None, :]
        * keep[:, None, None]
    )  # [k*n, E, C]
    disp = disp_flat.reshape(top_k, n, E, capacity)
    dispatch = disp.sum(0)  # token can hold at most one slot per expert
    combine = (disp * topv.T.reshape(top_k, n, 1, 1)).sum(0)
    # both masks in the ACTIVATION dtype: a f32 dispatch would promote the
    # expert einsums to f32 and silently lose the bf16 MXU path
    return dispatch.astype(logits.dtype), combine.astype(logits.dtype)


def _expert_ffn(xin, w1, w2):
    """xin [E, C, d] through each expert's MLP."""
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin, w1))
    return jnp.einsum("ecf,efd->ecd", h, w2)


def moe_ffn_dense(
    params,
    x,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    capacity: int | None = None,
    logits: Any | None = None,
):
    """Single-device MoE forward — the oracle the EP path must match.

    ``x`` [n, d_model] -> [n, d_model]. ``capacity=None`` derives the
    Switch capacity from ``capacity_factor``; pass ``capacity=n`` for
    exact no-drop routing (the decode/serving path, where a dropped token
    would make generation depend on batch composition). ``logits``
    overrides the router projection so callers that also need the logits
    (aux loss, sowing) compute them ONCE."""
    n, d = x.shape
    E = params["router"].shape[-1]
    if capacity is None:
        capacity = max(1, int(capacity_factor * top_k * n / E))
    if logits is None:
        logits = x @ params["router"]
    dispatch, combine = moe_dispatch(logits, top_k, capacity)
    xin = jnp.einsum("nd,nec->ecd", x, dispatch)
    out = _expert_ffn(xin, params["w1"], params["w2"])
    return jnp.einsum("ecd,nec->nd", out, combine)


def moe_ffn_ep(
    params,
    x,
    mesh,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    axis: str = "expert",
):
    """Expert-parallel MoE forward over ``mesh``.

    Experts are sharded over ``axis`` (W1/W2 leading dim); tokens are
    sharded over the SAME axis (each member routes its own token shard).
    Movement: dispatch locally to [E, C, d], ``all_to_all`` so each member
    holds [E_local, ep*C, d] (its experts' queues from every peer), run
    the local experts, ``all_to_all`` back, combine with local gates.
    Output matches :func:`moe_ffn_dense` exactly for kept tokens (modulo
    per-shard capacity rounding; see test oracle).
    """
    from jax import shard_map

    ep = mesh.shape[axis]
    n, d = x.shape
    E = params["router"].shape[-1]
    if E % ep:
        raise ValueError(f"n_experts ({E}) must divide by mesh axis {axis}={ep}")
    if n % ep:
        raise ValueError(f"token count ({n}) must divide by mesh axis {axis}={ep}")
    # per-SHARD capacity so the global budget matches the dense path's
    capacity = max(1, int(capacity_factor * top_k * (n // ep) / E))

    # every spec names only the expert axis: other mesh axes (data/model)
    # see replicated values here — compose dp outside via vmap/jit sharding
    specs = {
        "router": P(),
        "w1": P(axis),
        "w2": P(axis),
    }

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=({k: specs[k] for k in specs}, P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    def run(p, x_loc):
        logits = x_loc @ p["router"]  # [n_loc, E]
        dispatch, combine = moe_dispatch(logits, top_k, capacity)
        xin = jnp.einsum("nd,nec->ecd", x_loc, dispatch)  # [E, C, d]
        # a2a out: split the expert dim over peers, receive every peer's
        # queue for MY experts -> [E_local, ep*C, d] (source-member-ordered)
        xin = jax.lax.all_to_all(xin, axis, split_axis=0, concat_axis=1, tiled=True)
        out = _expert_ffn(xin, p["w1"], p["w2"])  # local experts only
        # a2a back: return each source member's slots -> [E, C, d]
        out = jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=0, tiled=True)
        return jnp.einsum("ecd,nec->nd", out, combine)

    return run(params, x)


def moe_load_balancing_loss(logits, mask=None):
    """Switch-Transformer auxiliary load-balancing loss (Fedus et al.):
    ``E * sum_e f_e * P_e`` where ``f_e`` is the fraction of tokens whose
    TOP-1 choice is expert e and ``P_e`` the mean router probability —
    minimized (value 1) at perfectly uniform routing. Add
    ``aux_coeff * loss`` to the training objective to keep experts from
    collapsing onto a few favorites.

    ``mask`` [n] (or broadcastable) excludes positions — pass the
    flattened attention mask so PADDING tokens don't count toward the
    balance (balancing pads would leave real-token routing skewed).
    """
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    onehot = jax.nn.one_hot(top1, E, dtype=jnp.float32)
    if mask is None:
        f = jnp.mean(onehot, axis=0)
        p = jnp.mean(probs, axis=0)
    else:
        m = jnp.reshape(mask, (-1, 1)).astype(jnp.float32)
        denom = jnp.clip(m.sum(), 1.0)
        f = jnp.sum(onehot * m, axis=0) / denom
        p = jnp.sum(probs * m, axis=0) / denom
    return E * jnp.sum(f * p)

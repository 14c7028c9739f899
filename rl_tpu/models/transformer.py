"""Decoder-only transformer for RLHF policies (flax), TP/SP-ready.

The native policy model the reference delegates to external engines for
(reference: torchrl/modules/llm/policies/transformers_wrapper.py:40 wraps a
HF model; vllm backends report tensor_parallel_size,
modules/llm/backends/vllm/vllm_async.py:176). Here the model itself is
mesh-native:

- ``param_sharding_rules`` returns Megatron-style PartitionSpecs (attention
  QKV/MLP-up column-split on axis "model", proj/MLP-down row-split) —
  jit with these placements gives tensor parallelism with XLA-inserted
  all-reduces over ICI.
- ``attention_impl="ring"`` routes attention through
  :func:`rl_tpu.parallel.ring_attention` over the "context" axis for
  long-sequence training (the reference has no native equivalent).
- bfloat16 activations by default (MXU-native), fp32 params.

``TransformerLM.apply_with_cache`` is the single-token decode step backing
:mod:`rl_tpu.models.generate`.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["TransformerConfig", "TransformerLM", "param_sharding_rules"]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int | None = None  # < n_heads => GQA/MQA (shared KV heads)
    d_ff: int = 2048
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16  # activation dtype; params stay fp32
    attention_impl: str = "local"  # "local" | "ring" | "flash"
    flash_decode: bool = False  # pallas decode kernel for T=1 cache steps
    flash_interpret: bool = False  # pallas interpret mode (CPU testing)
    # int8 paged KV pools with per-(block, kv-head) scales (quantize on
    # write, dequantize in the read kernel) — ~4x effective KV blocks per
    # chip; accuracy-gated, off by default (rl_tpu.kernels.kvcache)
    kv_int8: bool = False
    mesh: Any = None  # required for "ring"
    context_axis: str = "context"
    # Mixture-of-Experts FFN (0 = dense FFN). Experts shard over the
    # "expert" mesh axis via param_sharding_rules; rl_tpu.parallel.moe
    # holds the explicit all_to_all EP path + the dense oracle this
    # in-model formulation matches.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # rematerialize each block's activations in the backward pass (training
    # forward only — cache paths never differentiate). remat_policy picks
    # what XLA may keep: "none" recomputes everything, "dots" saves matmul
    # outputs (jax.checkpoint_policies.checkpoint_dots) — the usual MFU/
    # memory trade for gradient-accumulation microbatching.
    remat: bool = False
    remat_policy: str = "none"
    # -- the block's variants, as data. The defaults are GPT-2's: pre-LN
    # LayerNorm, learned positions, a biased GELU FFN, a tied head, heads
    # d_model / n_heads wide, the stack run once.
    d_head: int | None = None  # head width; None = d_model // n_heads
    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    norm_eps: float = 1e-6
    # "pre": a norm before each branch. "sandwich": one before AND one
    # after each branch (four a block), the residual added after the second
    norm_placement: str = "pre"
    position: str = "learned"  # "learned" (wpe, capped at max_seq_len) | "rotary"
    rope_theta: float = 10000.0
    ffn: str = "gelu"  # "gelu" (up/down, biased) | "swiglu" (gate/up/down, no bias)
    tie_embeddings: bool = True  # False: an untied [d_model, vocab] head
    # looped decoder: the SAME n_layers run loop_steps times, the final norm
    # after each loop; every (loop, layer) pair keeps its own K/V, cache
    # entry ``loop * n_layers + layer``. An exit gate weighs the loops: the
    # non-cache forward leaves at the first loop whose cumulative exit
    # probability reaches the threshold; the cache paths run every loop and
    # take the threshold only as 1.0 (= the last loop).
    loop_steps: int = 1
    early_exit_threshold: float = 1.0
    # one `lax.scan` over the layers: parameters stacked under "layers"
    # with a leading [n_layers] axis, the caches ONE entry whose pools hold
    # every (loop, layer) pair's blocks side by side (entry e's block b is
    # row e * n_blocks + b), so a program's size does not grow with depth
    scan_layers: bool = False

    def __post_init__(self):
        for field, allowed in (
            ("norm", ("layernorm", "rmsnorm")),
            ("norm_placement", ("pre", "sandwich")),
            ("position", ("learned", "rotary")),
            ("ffn", ("gelu", "swiglu")),
        ):
            if getattr(self, field) not in allowed:
                raise ValueError(
                    f"{field} must be one of {'|'.join(allowed)}, got {getattr(self, field)!r}"
                )
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps must be >= 1, got {self.loop_steps}")
        if self.scan_layers and self.kv_int8:
            raise ValueError("scan_layers does not take kv_int8 pools")

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def cache_entries(self) -> int:
        """K/V sets a token leaves in a cache: one a (loop, layer) pair."""
        return self.loop_steps * self.n_layers


def _flash_interpret(cfg) -> bool:
    """``cfg.flash_interpret``, which is a CPU test fixture: on the chip
    the Pallas interpreter is never a way to run a kernel."""
    if cfg.flash_interpret:
        from ..kernels.registry import refuse_interpret_on_tpu

        refuse_interpret_on_tpu("TransformerConfig.flash_interpret")
    return cfg.flash_interpret


def _paged_attention(cfg, q, k, v, cache, active):
    """Attention over a paged KV cache + block-table writes.

    Layout: ``pool_k``/``pool_v`` [n_blocks, Hk // r, block, r * D]
    (HEAD-MAJOR, ``r`` kv heads side by side in a lane row so that a row
    is 128 lanes wide: ``init_paged_cache``; ``r`` is read off the shapes
    and is 1 for a 128-wide head, an odd head count and int8 pools)
    shared across slots; ``block_table`` [S, max_blocks] int32 (block 0 =
    reserved scratch); ``len`` [S] int32 per-slot lengths. New tokens (q/k/v
    [S, T, ...]) land at slot-local positions ``len[s] + t``, written as
    WHOLE ROWS of the pool seen as [rows, r * D] (a token's K is
    ``Hk // r`` such rows): a scatter of whole rows keeps the pool in the
    row-major layout the read kernel is held to, where a scatter of
    ``[Hk, D]`` windows of the 4-d pool makes XLA lay the block axis
    outside the heads and relay the whole pool before every kernel call.
    The read gathers the slot's table blocks in ONE shot (unpacking the
    gathered blocks, never a pool, to [.., Hk, block, D]) and runs a
    single masked softmax over the assembled range — one gather + two
    einsums per layer instead of an op chain per block. The T=1 read
    instead runs the Pallas ``paged_flash_decode`` kernel where the
    registry selects it (or ``cfg.flash_decode`` forces it), whose index
    map reads the block table directly (the pool is read in place, packed
    as it lies, no gather copy at all).
    """
    pool_k, pool_v = cache["pool_k"], cache["pool_v"]
    table, lens = cache["block_table"], cache["len"]
    int8 = "scale_k" in cache
    scale_k = cache.get("scale_k")
    scale_v = cache.get("scale_v")
    S, T = q.shape[0], q.shape[1]
    n_blocks, block, lanes = pool_k.shape[0], pool_k.shape[2], pool_k.shape[3]
    max_blocks = table.shape[1]
    # stacked pools (cfg.scan_layers): this call is cache entry ``entry``,
    # whose blocks are rows [base, base + n_blocks) of the pools; its block
    # 0 is its own scratch
    entry = cache.get("entry")
    base = 0
    if entry is not None:
        n_blocks //= cfg.cache_entries
        base = entry * n_blocks
    # `active` is [S] (whole slots) or [S, T] (token-level — bucketed
    # prefill pads prompts up to the bucket; padded tokens must not land
    # in the cache or advance the length)
    if active is None:
        active_t = jnp.ones((S, T), bool)
    elif active.ndim == 1:
        active_t = jnp.broadcast_to(active[:, None], (S, T))
    else:
        active_t = active

    # -- write the new K/V into the pool --------------------------------------
    pos = lens[:, None] + jnp.arange(T)[None, :]  # [S, T] slot-local
    blk_slot = pos // block
    off = pos % block
    blk_global = jnp.take_along_axis(
        table, jnp.clip(blk_slot, 0, max_blocks - 1), axis=1
    )  # [S, T]
    # inactive tokens AND positions beyond the table range write into
    # scratch block 0 (reserved, never read) — without the range guard a
    # clipped out-of-range position would silently corrupt the LAST
    # block's rows (chunked decode can speculate past a slot's budget)
    blk_global = jnp.where(active_t & (blk_slot < max_blocks), blk_global, 0)
    flat_blk = blk_global.reshape(-1) + base
    flat_off = off.reshape(-1)
    # pools are HEAD-MAJOR [N, Hp, block, lanes] (the Pallas kernel views
    # them as [N*Hp, block, lanes] for free — Mosaic needs the last two)
    Hp = pool_k.shape[1]
    if int8:
        from ..kernels.kvcache import quantize_block_write

        pool_k, scale_k = quantize_block_write(
            pool_k, scale_k, flat_blk, flat_off, k.reshape(S * T, *k.shape[2:])
        )
        pool_v, scale_v = quantize_block_write(
            pool_v, scale_v, flat_blk, flat_off, v.reshape(S * T, *v.shape[2:])
        )
    else:
        # a token's K (or V) is Hp whole rows of the pool seen as
        # [N * Hp * block, lanes]: its kv heads r*j .. r*j + r - 1 are
        # contiguous in k and fill row j's lanes as they come
        rows = (flat_blk[:, None] * Hp + jnp.arange(Hp)[None, :]) * block
        rows = (rows + flat_off[:, None]).reshape(-1)
        pool_k = pool_k.reshape(-1, lanes).at[rows].set(
            k.reshape(-1, lanes), mode="drop"
        ).reshape(pool_k.shape)
        pool_v = pool_v.reshape(-1, lanes).at[rows].set(
            v.reshape(-1, lanes), mode="drop"
        ).reshape(pool_v.shape)

    # -- read: Pallas paged-decode kernel or the XLA block loop ---------------
    # kernel selection is registry-driven (rl_tpu.kernels.registry —
    # backend feature detection + RL_TPU_NO_KERNELS/RL_TPU_KERNELS_INTERPRET);
    # cfg.flash_decode keeps forcing the kernel for callers that predate it
    from ..kernels.paged_attention import decode_mode

    mode = decode_mode(int8=int8) if T == 1 else None
    if T == 1 and (mode is not None or cfg.flash_decode):
        # the block table drives the DMA; the pool is read in place
        interpret = (mode == "interpret") or _flash_interpret(cfg)
        attend = lens + 1  # decode-after-write: positions 0..len inclusive
        if entry is not None:
            table = jnp.where(table > 0, table + base, table)
        if int8:
            from ..kernels.paged_attention import paged_flash_decode_int8

            o = paged_flash_decode_int8(
                q, pool_k, pool_v, scale_k, scale_v, table, attend,
                interpret=interpret,
            ).astype(cfg.dtype)
        else:
            from ..ops.attention import paged_flash_decode

            o = paged_flash_decode(
                q, pool_k, pool_v, table, attend, interpret=interpret
            ).astype(cfg.dtype)
        return o, _advance_paged_cache(
            cache, pool_k, pool_v, lens, active_t, scale_k, scale_v
        )

    # ONE gather materializes every table block, then a single masked
    # softmax attends over the whole [L = max_blocks*block] range. This
    # replaces the old per-block online-softmax python loop, whose
    # max_blocks x (gather + 2 einsums + renormalize) unrolled HLO
    # dominated small-step decode wall-clock (and compile time) — the
    # dispatch overhead of ~6*max_blocks tiny ops per layer per token
    # dwarfed the flops. Rows with no valid key (inactive slots, all
    # table entries unassigned) softmax over a uniform -1e9 score row and
    # produce finite garbage; their outputs are never consumed (the
    # engine discards inactive slots' tokens).
    from ..ops.attention import unpack_kv_heads

    Hk = cfg.kv_heads
    r = Hk // Hp  # kv heads a pool row holds
    rep = cfg.n_heads // cfg.kv_heads
    scale = cfg.head_dim**-0.5
    L = max_blocks * block
    safe_table = jnp.clip(table, 0, n_blocks - 1) + base  # -1 (unassigned) -> scratch
    k_all = unpack_kv_heads(pool_k[safe_table], r)  # [S, max_blocks, Hk, block, D]
    v_all = unpack_kv_heads(pool_v[safe_table], r)
    if int8:
        from ..kernels.kvcache import dequantize

        k_all = dequantize(k_all, scale_k[safe_table])
        v_all = dequantize(v_all, scale_v[safe_table])
    k_all = jnp.moveaxis(k_all, 2, 1).reshape(S, Hk, L, -1).astype(jnp.float32)
    v_all = jnp.moveaxis(v_all, 2, 1).reshape(S, Hk, L, -1).astype(jnp.float32)
    # grouped heads: [S, T, H, D] -> [S, Hk, rep, T, D] (no KV repeat)
    qf = jnp.moveaxis(q, 1, 2).astype(jnp.float32)
    qf = qf.reshape(S, Hk, rep, T, cfg.head_dim)
    s_all = jnp.einsum("shrtd,shld->shrtl", qf, k_all) * scale
    kv_pos = jnp.arange(L)
    # causal: q token t (at position len+t) sees kv_pos <= len + t;
    # unassigned/scratch table entries are never valid keys
    valid = kv_pos[None, None, :] <= pos[:, :, None]  # [S, T, L]
    valid = valid & jnp.repeat(table > 0, block, axis=1)[:, None, :]
    s_all = jnp.where(valid[:, None, None], s_all, -1e9)
    p = jax.nn.softmax(s_all, axis=-1)
    o = jnp.einsum("shrtl,shld->shrtd", p, v_all)
    o = o.reshape(S, cfg.n_heads, T, cfg.head_dim)
    o = jnp.moveaxis(o, 1, 2).astype(cfg.dtype)  # [S, T, H, D]
    return o, _advance_paged_cache(
        cache, pool_k, pool_v, lens, active_t, scale_k, scale_v
    )


def _advance_paged_cache(cache, pool_k, pool_v, lens, active_t,
                         scale_k=None, scale_v=None):
    """The one statement of the cache-advance rule (shared by the kernel
    and XLA read branches)."""
    new_cache = dict(cache)
    new_cache.update(
        pool_k=pool_k,
        pool_v=pool_v,
        len=lens + active_t.sum(axis=1, dtype=lens.dtype),
    )
    if scale_k is not None:
        new_cache.update(scale_k=scale_k, scale_v=scale_v)
    return new_cache


def _norm(cfg, name: str):
    """The block's norm: statistics in float32, the result in ``cfg.dtype``."""
    cls = nn.RMSNorm if cfg.norm == "rmsnorm" else nn.LayerNorm
    return cls(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)


def _rotary(x, positions, theta: float):
    """Rotary positions, rotate-half over the whole head: x [B, T, H, D],
    positions [T] or [B, T] (each token's absolute position). Angles and
    the rotation in float32, the result in x's dtype."""
    D = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [(B,) T, D/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]  # [(B,) T, 1, D]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * jnp.cos(ang) + rot * jnp.sin(ang)).astype(x.dtype)


class _Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, cache=None, positions=None):
        cfg = self.cfg
        B, T, _ = x.shape
        Hk = cfg.kv_heads
        width = cfg.n_heads * cfg.head_dim
        if Hk == cfg.n_heads:
            qkv = nn.Dense(
                3 * width, use_bias=False, dtype=cfg.dtype, name="qkv"
            )(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:  # GQA/MQA: fewer KV heads — smaller cache, less decode traffic
            q = nn.Dense(width, use_bias=False, dtype=cfg.dtype, name="wq")(x)
            kv = nn.Dense(
                2 * Hk * cfg.head_dim, use_bias=False, dtype=cfg.dtype, name="wkv"
            )(x)
            k, v = jnp.split(kv, 2, axis=-1)

        q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
        k = k.reshape(B, T, Hk, cfg.head_dim)
        v = v.reshape(B, T, Hk, cfg.head_dim)
        if cfg.position == "rotary":
            # before the cache write: a cached key is stored rotated, by
            # the position its token has in its own sequence
            q = _rotary(q, positions, cfg.rope_theta)
            k = _rotary(k, positions, cfg.rope_theta)

        def dense_gqa(q, k, v, attn_mask):
            """XLA attention with KV-head grouping ([B,H,T,S] scores)."""
            if Hk != cfg.n_heads:
                k_ = jnp.repeat(k, cfg.n_heads // Hk, axis=2)
                v_ = jnp.repeat(v, cfg.n_heads // Hk, axis=2)
            else:
                k_, v_ = k, v
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k_) * cfg.head_dim**-0.5
            s = jnp.where(attn_mask, s, -1e9)
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(cfg.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v_)

        new_cache = None
        if cache is not None and "pool_k" in cache:
            # PAGED cache (vLLM-style, reference delegates to vllm's paged
            # attention — modules/llm/backends/vllm/vllm_async.py:515): KV
            # lives in a shared block pool; each SLOT (batch row) owns a
            # block table and its own length, so rows admitted at
            # different times coexist in one decode batch (continuous
            # batching). Block 0 is a reserved scratch target for
            # inactive slots' writes.
            if mask is not None:
                raise ValueError(
                    "the paged cache path ignores attention_mask — padding "
                    "is expressed through cache['active'] and per-slot "
                    "lens; pass attention_mask=None"
                )
            o, new_cache = _paged_attention(
                cfg, q, k, v, cache, cache.get("active")
            )
        elif cache is not None:
            # decode step: append to the KV cache at slot `cache["len"]`
            ck, cv, cache_len = cache["k"], cache["v"], cache["len"]
            entry = cache.get("entry")
            if entry is None:
                ck = jax.lax.dynamic_update_slice_in_dim(ck, k, cache_len, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cv, v, cache_len, axis=1)
                k, v = ck, cv
            else:  # stacked [entries, B, S, Hk, D] (cfg.scan_layers)
                at = (entry, 0, cache_len, 0, 0)
                ck = jax.lax.dynamic_update_slice(ck, k[None], at)
                cv = jax.lax.dynamic_update_slice(cv, v[None], at)
                k = jax.lax.dynamic_index_in_dim(ck, entry, keepdims=False)
                v = jax.lax.dynamic_index_in_dim(cv, entry, keepdims=False)
            new_cache = {"k": ck, "v": cv, "len": cache_len + T}
            S = k.shape[1]
            if (
                cfg.flash_decode
                and T == 1
                and S % min(512, S) == 0
            ):
                from ..ops.attention import flash_decode

                o = flash_decode(
                    q,
                    k,
                    v,
                    new_cache["len"],
                    kv_mask=mask,
                    interpret=_flash_interpret(cfg),
                ).astype(cfg.dtype)
            else:
                kv_pos = jnp.arange(S)
                q_pos = cache_len + jnp.arange(T)
                causal = q_pos[:, None] >= kv_pos[None, :]
                valid = kv_pos[None, :] < (cache_len + T)
                attn_mask = (causal & valid)[None, None]
                if mask is not None:  # padding mask over cached keys [B, S]
                    attn_mask = attn_mask & mask[:, None, None, :]
                o = dense_gqa(q, k, v, attn_mask)
        elif cfg.attention_impl == "flash":
            from ..ops.attention import flash_attention

            # ragged batches ride the kernel: padding mask -> segment ids
            o = flash_attention(
                q, k, v, causal=True, interpret=_flash_interpret(cfg),
                kv_mask=None if mask is None else mask,
            ).astype(cfg.dtype)
        elif cfg.attention_impl == "ring":
            from ..parallel import ring_attention

            if Hk != cfg.n_heads:
                k = jnp.repeat(k, cfg.n_heads // Hk, axis=2)
                v = jnp.repeat(v, cfg.n_heads // Hk, axis=2)
            o = ring_attention(
                q.astype(jnp.float32),
                k.astype(jnp.float32),
                v.astype(jnp.float32),
                cfg.mesh,
                axis_name=cfg.context_axis,
                causal=True,
                kv_mask=mask[:, : k.shape[1]] if mask is not None else None,
            ).astype(cfg.dtype)
        else:
            causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
            if mask is not None:
                causal = causal & mask[:, None, None, :]
            o = dense_gqa(q, k, v, causal)

        o = o.reshape(B, T, width)
        o = nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype, name="proj")(o)
        return o, new_cache


class _MoEFFN(nn.Module):
    """Switch/Mixtral-style MoE FFN (the §2.13 EP slot — beyond the
    reference, which has no expert parallelism).

    The dense-einsum formulation from rl_tpu.parallel.moe: with w1/w2
    sharded over the "expert" mesh axis (param_sharding_rules), GSPMD
    partitions the expert einsums and inserts the dispatch/combine
    collectives — the in-model EP path; parallel.moe.moe_ffn_ep is the
    explicit shard_map+all_to_all equivalent (oracle-tested identical).
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, y, serving: bool = False):
        from ..parallel.moe import moe_ffn_dense, moe_param_specs

        cfg = self.cfg
        specs = moe_param_specs(cfg.d_model, cfg.d_ff, cfg.moe_experts)
        params = {
            name: self.param(
                name, nn.initializers.normal(std), shape, jnp.float32
            ).astype(cfg.dtype)
            for name, (shape, std) in specs.items()
        }
        B, T, d = y.shape
        n = B * T
        flat = y.reshape(-1, d).astype(cfg.dtype)
        # the ONE router projection: used for dispatch below and sown for
        # the Switch aux loss. Consumed by
        # rl_tpu.models.token_log_probs_with_aux, which the LM losses
        # (GRPO/CISPO/SFT, aux_coeff=0.01 default) accept as a
        # (log_probs, aux)-returning log_prob_fn — use it for any MoE
        # training run or routing WILL collapse onto few experts
        router_logits = flat @ params["router"]
        self.sow("intermediates", "router_logits", router_logits)
        # serving (cache live: prefill OR decode) routes with FULL
        # capacity: any capacity drop would make one request's logits/KV
        # depend on which other requests share the batch, and pad tokens
        # could displace real ones (per-request determinism)
        capacity = n if serving else None
        out = moe_ffn_dense(
            params, flat, cfg.moe_top_k, cfg.moe_capacity_factor,
            capacity=capacity, logits=router_logits,
        )
        return out.reshape(B, T, d).astype(cfg.dtype)


class _Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, mask, cache=None, positions=None):
        cfg = self.cfg
        sandwich = cfg.norm_placement == "sandwich"
        h, new_cache = _Attention(cfg, name="attn")(
            _norm(cfg, "ln1")(x), mask, cache, positions
        )
        if sandwich:
            h = _norm(cfg, "ln1_post")(h)
        x = x + h
        y = _norm(cfg, "ln2")(x)
        if cfg.moe_experts:
            y = _MoEFFN(cfg, name="moe")(y, serving=cache is not None)
        elif cfg.ffn == "swiglu":
            gate = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype, name="gate")(y)
            up = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype, name="up")(y)
            y = nn.Dense(
                cfg.d_model, use_bias=False, dtype=cfg.dtype, name="down"
            )(nn.silu(gate) * up)
        else:
            y = nn.Dense(cfg.d_ff, dtype=cfg.dtype, name="up")(y)
            y = nn.gelu(y)
            y = nn.Dense(cfg.d_model, dtype=cfg.dtype, name="down")(y)
        if sandwich:
            y = _norm(cfg, "ln2_post")(y)
        return x + y, new_cache


def _remat_policy(name: str):
    if name in (None, "none"):
        return None  # save nothing: full recompute in the backward
    policies = {
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    }
    try:
        return policies[name]
    except KeyError:
        raise ValueError(
            f"remat_policy must be one of none|dots|dots_no_batch, got {name!r}"
        ) from None


# the fields of a cache entry that hold K/V: what a scanned stack carries
# from layer to layer (the table, lengths and masks are the same for all)
_KV_FIELDS = ("pool_k", "pool_v", "k", "v")


class TransformerLM(nn.Module):
    """Decoder-only LM: tokens [B, T] -> logits [B, T, V]. The block's
    variants (norm, positions, FFN, head, head width, loops) are data on
    :class:`TransformerConfig`; the defaults are GPT-2's."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, attention_mask=None, cache=None, positions=None,
                 return_loops: bool = False):
        """With ``cache``: ``(logits, new_cache)``. Without: the logits of
        the loop each token exits at (the last, at the threshold 1.0), and
        with ``return_loops`` also ``{"logits": [loops, B, T, V],
        "exit_p": [loops, B, T]}``: every loop's logits and the exit
        distribution over the loops."""
        cfg = self.cfg
        L, U = cfg.n_layers, cfg.loop_steps
        if cache is not None and cfg.early_exit_threshold < 1.0:
            raise ValueError(
                "the cache paths run every loop for every sequence: "
                f"early_exit_threshold={cfg.early_exit_threshold} < 1.0 would "
                "need a step whose depth differs by slot"
            )
        emb = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype, name="wte")
        if positions is None:
            if cache is not None:
                lens = cache[0]["len"]
                if lens.ndim:  # paged cache: per-slot lengths [S]
                    positions = lens[:, None] + jnp.arange(tokens.shape[1])[None, :]
                else:
                    positions = lens + jnp.arange(tokens.shape[1])
            else:
                positions = jnp.arange(tokens.shape[1])
        x = emb(tokens)
        if cfg.position == "learned":
            x = x + nn.Embed(
                cfg.max_seq_len, cfg.d_model, dtype=cfg.dtype, name="wpe"
            )(positions)

        block_cls = _Block
        if cfg.remat and cache is None:
            # per-block remat on the training forward only: the KV-cache
            # serving path never runs a backward, so checkpointing it would
            # just disable CSE for nothing
            block_cls = nn.remat(_Block, policy=_remat_policy(cfg.remat_policy))
        ln_f = _norm(cfg, "ln_f")
        if cfg.scan_layers:
            block = block_cls(cfg, name="layers")
            kv = common = None
            if cache is not None:
                (entry0,) = cache
                kv = {f: entry0[f] for f in _KV_FIELDS if f in entry0}
                common = {f: a for f, a in entry0.items() if f not in kv}

            def layer(block, carry, entry):
                x, kv = carry
                if kv is None:
                    return (block(x, attention_mask, None, positions)[0], None), None
                x, new = block(
                    x, attention_mask, {**common, **kv, "entry": entry}, positions
                )
                return (x, {f: new[f] for f in kv}), new["len"]

            stack = nn.scan(
                layer,
                variable_axes={"params": 0, "intermediates": 0},
                split_rngs={"params": True},
                length=L,
            )
        else:
            blocks = [block_cls(cfg, name=f"h{i}") for i in range(L)]
            new_caches = [] if cache is not None else None
        loops = []  # the normed state each loop leaves, the next loop's input
        for u in range(U):
            if cfg.scan_layers:
                (x, kv), lens = stack(block, (x, kv), u * L + jnp.arange(L))
            else:
                for i, blk in enumerate(blocks):
                    x, nc = blk(
                        x, attention_mask,
                        cache[u * L + i] if cache is not None else None, positions,
                    )
                    if cache is not None:
                        new_caches.append(nc)
            x = ln_f(x)
            loops.append(x)
        if cfg.scan_layers and cache is not None:
            # every layer computed the same advanced length; it is set once
            new_caches = [{**common, **kv, "len": lens[-1]}]

        if not cfg.tie_embeddings:
            w_head = self.param(
                "head", nn.initializers.normal(0.02),
                (cfg.d_model, cfg.vocab_size), jnp.float32,
            )

        def head(h):
            if cfg.tie_embeddings:
                return emb.attend(h.astype(jnp.float32))  # fp32 head
            # operands as the weights are held, accumulation in float32
            return jnp.dot(
                h.astype(w_head.dtype), w_head, preferred_element_type=jnp.float32
            )

        exit_p = None
        if U > 1 and (return_loops or cfg.early_exit_threshold < 1.0
                      or self.is_initializing()):
            gate = nn.Dense(1, dtype=jnp.float32, name="exit_gate")
            lam = jax.nn.sigmoid(
                jnp.stack([gate(h.astype(jnp.float32))[..., 0] for h in loops])
            )  # [U, B, T]
            # what no earlier loop took; the last loop takes the rest
            before = jnp.concatenate(
                [jnp.ones_like(lam[:1]), jnp.cumprod(1.0 - lam, axis=0)[:-1]]
            )
            exit_p = jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)
        h = loops[-1]
        if cache is None and U > 1 and cfg.early_exit_threshold < 1.0:
            hit = jnp.cumsum(exit_p, axis=0) >= cfg.early_exit_threshold
            exit_at = jnp.argmax(hit.at[-1].set(True), axis=0)  # first True
            h = jnp.take_along_axis(
                jnp.stack(loops), exit_at[None, ..., None], axis=0
            )[0]
        logits = head(h)
        if cache is not None:
            return logits, new_caches
        if return_loops:
            if exit_p is None:
                exit_p = jnp.ones((1, *tokens.shape), jnp.float32)
            return logits, {
                "logits": jnp.stack([head(h) for h in loops]), "exit_p": exit_p,
            }
        return logits

    # -- cache ----------------------------------------------------------------

    def init_cache(self, batch_size: int, max_len: int) -> list[dict]:
        """One entry a (loop, layer) pair; with ``scan_layers`` ONE entry
        whose ``k``/``v`` are stacked [entries, B, max_len, Hk, D]."""
        cfg = self.cfg
        shape = (batch_size, max_len, cfg.kv_heads, cfg.head_dim)
        lead, n = ((cfg.cache_entries,), 1) if cfg.scan_layers else ((), cfg.cache_entries)
        return [
            {
                "k": jnp.zeros(lead + shape, cfg.dtype),
                "v": jnp.zeros(lead + shape, cfg.dtype),
                "len": jnp.asarray(0, jnp.int32),
            }
            for _ in range(n)
        ]

    def init_paged_cache(
        self, n_slots: int, n_blocks: int, block_size: int, max_blocks: int
    ) -> list[dict]:
        """Paged KV cache (vLLM layout): a pool of ``n_blocks`` KV blocks
        of ``block_size`` tokens shared by ``n_slots`` sequences, each
        owning up to ``max_blocks`` table entries. Block 0 is reserved as
        the scratch write target for inactive slots; -1 marks unassigned
        table entries. One entry a (loop, layer) pair; with
        ``scan_layers`` ONE entry whose pools hold every pair's blocks,
        pair e's block b at row ``e * n_blocks + b``. Managed by
        :class:`rl_tpu.models.serving.ContinuousBatchingEngine`.

        A pool is ``[N, Hk // r, block, r * D]``: ``r`` kv heads side by
        side in one lane row, with ``r = 128 // D`` where that makes whole
        128-lane rows of a bf16/f32 pool, else 1
        (:func:`rl_tpu.ops.attention.paged_heads_per_row`, which says why:
        the device's default layout of a pool with a minor dimension under
        128 wide is not the row-major one the decode kernel reads). 16
        heads x 64 give ``[N, 8, block, 128]``; 16 x 128, 3 x 64 and every
        int8 pool keep ``[N, Hk, block, D]``. Block rows stay axis 0, so
        whoever moves blocks (copy-on-write, hand-off) need not know."""
        from ..ops.attention import paged_heads_per_row

        cfg = self.cfg
        pool_dtype = jnp.int8 if cfg.kv_int8 else cfg.dtype
        stacked, n = (cfg.cache_entries, 1) if cfg.scan_layers else (1, cfg.cache_entries)
        r = paged_heads_per_row(cfg.kv_heads, cfg.head_dim, pool_dtype)
        # HEAD-MAJOR: the Pallas paged-decode kernel views the pool as
        # [N * Hk/r, block, r*D] without a copy
        shape = (stacked * n_blocks, cfg.kv_heads // r, block_size, r * cfg.head_dim)

        def layer():
            c = {
                "pool_k": jnp.zeros(shape, pool_dtype),
                "pool_v": jnp.zeros(shape, pool_dtype),
                "block_table": jnp.full((n_slots, max_blocks), -1, jnp.int32),
                "len": jnp.zeros((n_slots,), jnp.int32),
                "active": jnp.zeros((n_slots,), bool),
            }
            if cfg.kv_int8:
                from ..kernels.kvcache import init_scales

                # per-(block, kv-head) symmetric scales, block-major like
                # the pools so CoW/eviction carry them with the same indexing
                c["scale_k"] = init_scales(n_blocks, cfg.kv_heads)
                c["scale_v"] = init_scales(n_blocks, cfg.kv_heads)
            return c

        return [layer() for _ in range(n)]


def param_sharding_rules(params, model_axis: str = "model", expert_axis: str = "expert"):
    """Megatron-style PartitionSpecs for TransformerLM params.

    Column-parallel (split output features over ``model_axis``): attention
    qkv, MLP up. Row-parallel (split input features): attention proj, MLP
    down. Embeddings split over the feature axis; norms replicated. XLA
    inserts the TP all-reduces these placements imply.
    """

    def rule(path: tuple, x) -> P:
        names = [getattr(p, "key", str(p)) for p in path]
        if names[0] == "layers":  # scanned stack: a leading [n_layers] axis
            return P(None, *rule(path[1:], x[0]))
        joined = "/".join(names)
        if "/moe/" in f"/{joined}/":
            if "w1" in names:  # [E, d_model, d_ff]: EP x TP
                return P(expert_axis, None, model_axis)
            if "w2" in names:  # [E, d_ff, d_model]
                return P(expert_axis, model_axis, None)
            return P()  # router [d, E]: tiny, replicated
        if x.ndim < 2:
            return P()  # biases, norms
        if (
            "qkv" in joined
            or "wq" in joined
            or "wkv" in joined
            or "/up/" in joined
            or joined.endswith("up/kernel")
            or names[-2:] == ["gate", "kernel"]
            or joined == "head"
        ):
            return P(None, model_axis)
        if "proj" in joined or "down" in joined:
            return P(model_axis, None)
        if "wte" in joined or "wpe" in joined:
            return P(None, model_axis)
        return P()

    return jax.tree_util.tree_map_with_path(rule, params)

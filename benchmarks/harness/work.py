"""Operations and bytes that the cells' work requires, from shapes alone.

Counted: what the algorithm needs. Not counted: recomputation, padding,
work on inactive slots. A multiply-add is 2 FLOPs. The arithmetic follows
``rl_tpu/models/generate.py`` (``train_step_flops``, ``generate_flops``)
and ``rl_tpu/kernels/registry.py`` (``price_call``), copied so that the
yardstick does not move with the program.
"""

from __future__ import annotations

# -- decoder-only transformer (GPT-2 config keys) ------------------------------


def lm_body_params(cfg: dict) -> int:
    """Matrix parameters of the blocks: qkv 3d^2, proj d^2, up and down d*ff each."""
    d, ff = cfg["n_embd"], cfg["n_inner"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * ff)


def lm_param_count(cfg: dict) -> int:
    d, ff, L = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    per_layer = 4 * d * d + 2 * d * ff + ff + d + 4 * d  # matrices, two biases, two LayerNorms
    return cfg["vocab_size"] * d + cfg["n_positions"] * d + L * per_layer + 2 * d


def lm_forward_flops(cfg: dict, tokens: float, head_tokens: float, attn_pairs: float) -> float:
    """Forward pass: ``tokens`` through the blocks, ``head_tokens`` through
    the (tied) vocabulary head, ``attn_pairs`` (query, key) pairs attended
    (QK^T and AV: 4 FLOPs per pair per channel per layer)."""
    d = cfg["n_embd"]
    return (
        2.0 * lm_body_params(cfg) * tokens
        + 2.0 * cfg["vocab_size"] * d * head_tokens
        + 4.0 * cfg["n_layer"] * d * attn_pairs
    )


def causal_pairs(length: float) -> float:
    """Pairs a causal forward over ``length`` tokens attends: l(l+1)/2."""
    return length * (length + 1) / 2.0


def lm_sequence_flops(cfg: dict, prompt: float, new: float) -> float:
    """One forward over a sequence, logits for its ``new`` response tokens:
    what generating it (prefill, then decode through the cache) or scoring
    it needs."""
    s = prompt + new
    return lm_forward_flops(cfg, s, new, causal_pairs(s))


def grpo_step_flops(cfg: dict, prompts, news) -> float:
    """One GRPO step over sequences with ``prompts[i]`` real prompt tokens
    and ``news[i]`` response tokens: generation (1 forward), reference
    scoring (1 forward), update forward + backward (3 forwards)."""
    return 5.0 * sum(lm_sequence_flops(cfg, p, n) for p, n in zip(prompts, news))


def kv_bytes_per_token(cfg: dict, bytes_per_el: int = 2) -> int:
    """K and V of one token over all layers (bfloat16 pools)."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * bytes_per_el


def decode_step_bytes(cfg: dict, live_tokens: float, param_bytes_per_el: int = 4) -> float:
    """Bytes one decode step must read: every weight once (float32 as the
    program keeps them), the live K/V of the active slots."""
    return param_bytes_per_el * lm_param_count(cfg) + kv_bytes_per_token(cfg) * live_tokens


def decode_attention_cost(cfg: dict, live_tokens: float, rows: float) -> tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's decode attention call: each of ``rows``
    queries against its slot's live keys and values."""
    d = cfg["n_embd"]
    flops = 4.0 * d * live_tokens
    byts = 2.0 * d * 2 * live_tokens + 2.0 * rows * d * 2  # K, V read; q read, o written (bf16)
    return flops, byts


def sampling_cost(cfg: dict, rows: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one sampling call over [rows, V] float32 logits:
    scale, max, exp-sum, noise-add, arg-max: ~6 FLOPs per logit, one read."""
    n = rows * cfg["vocab_size"]
    return 6.0 * n, 4.0 * n


def flash_attention_cost(cfg: dict, batch: float, length: float, backward: bool) -> tuple[float, float]:
    """(FLOPs, bytes) of ONE layer's causal flash attention over
    [batch, length]: forward QK^T and PV, 4*d*pairs; backward dV, dP, dQ, dK, 8*d*pairs
    (recomputing P is not counted)."""
    d = cfg["n_embd"]
    pairs = batch * causal_pairs(length)
    el = batch * length * d * 2  # one bf16 [B, T, d] tensor
    if backward:
        return 8.0 * d * pairs, 8.0 * el  # q k v o do in, dq dk dv out
    return 4.0 * d * pairs, 4.0 * el  # q k v in, o out


def roofline_seconds(flops: float, byts: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    tc, tm = flops / peaks["flops"], byts / peaks["bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")

"""Operations and bytes of a looped decoder's work, from the published
(Hugging Face) keys alone: ``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``intermediate_size``, ``vocab_size``, ``total_ut_steps``.

Counted: what the algorithm needs. Not counted: recomputation, padding,
work on inactive slots, the embedding gather. A multiply-add is 2 FLOPs.
The layer stack runs ``total_ut_steps`` times over the SAME weights, so a
token costs that many passes of layer FLOPs and leaves that many sets of
keys and values; the weights cannot stay on the chip between passes (one
pass is gigabytes), so a decode step reads them once a pass. bfloat16
everywhere (2 bytes), as the configuration serves.
"""

from __future__ import annotations

BF16 = 2


def loops(cfg: dict) -> int:
    return int(cfg.get("total_ut_steps", 1))


def layer_matrix_params(cfg: dict) -> int:
    """One layer's matrices: q, k, v, o, and the gated FFN's three."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * (H + 2 * Hk) * D + H * D * d + 3 * d * cfg["intermediate_size"]


def param_count(cfg: dict) -> int:
    """Every parameter: layers (matrices and four norm gains), embedding,
    untied head, final norm, the exit gate with its bias."""
    d = cfg["hidden_size"]
    per_layer = layer_matrix_params(cfg) + 4 * d
    return cfg["num_hidden_layers"] * per_layer + 2 * cfg["vocab_size"] * d + d + (d + 1)


def cache_entries(cfg: dict) -> int:
    """K/V sets a token leaves: one a (loop, layer) pair."""
    return loops(cfg) * cfg["num_hidden_layers"]


def kv_bytes_per_token(cfg: dict) -> int:
    return cache_entries(cfg) * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def forward_flops(cfg: dict, tokens: float, head_tokens: float, attn_pairs: float) -> float:
    """``tokens`` through every loop of the layers, ``head_tokens`` through
    the head, ``attn_pairs`` (query, key) pairs attended in ONE cache entry
    (QK^T and AV: 4 FLOPs a pair a channel, in every entry)."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return (
        2.0 * loops(cfg) * cfg["num_hidden_layers"] * layer_matrix_params(cfg) * tokens
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"] * head_tokens
        + 4.0 * cache_entries(cfg) * width * attn_pairs
    )


def decode_weight_bytes(cfg: dict) -> int:
    """Weights one decode step must read: the layers once a loop, the head once."""
    layers = cfg["num_hidden_layers"] * layer_matrix_params(cfg) * BF16
    return loops(cfg) * layers + cfg["vocab_size"] * cfg["hidden_size"] * BF16


def decode_step_bytes(cfg: dict, live_tokens: float) -> float:
    return decode_weight_bytes(cfg) + kv_bytes_per_token(cfg) * live_tokens


def decode_attention_cost(cfg: dict, live_tokens: float, rows: float) -> tuple[float, float]:
    """(FLOPs, bytes) of ONE cache entry's decode attention call: each of
    ``rows`` queries against its slot's live keys and values."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
    flops = 4.0 * width * live_tokens
    byts = 2.0 * kv_width * BF16 * live_tokens + 2.0 * rows * width * BF16  # K, V read; q read, o written
    return flops, byts

"""Plain reference of the ``ouro-2.6b`` configuration.

A looped decoder (ByteDance Ouro, ``model_type`` "ouro") in straightforward
``jax.numpy``, float32, every matrix product at ``highest`` precision, no
kernels, no cache. It imports nothing of ``rl_tpu``. The weights come from
:func:`make_weights` (from the seed, rounded to bfloat16: the served model
holds them so, and both sides get the same rounded numbers, so that the
comparison is not one of weight rounding); the tokens it scores are the
ones the timed path served.

The equations (keys of the published ``config.json``; block structure as
the published modelling code has it, listed under ``assumed`` in the
configuration file)::

    h = E[tokens]
    for u in range(total_ut_steps):              # the SAME layers every loop
        for l in range(num_hidden_layers):
            a = rms1_l(h); q, k, v = a Wq_l, a Wk_l, a Wv_l       (no bias)
            q, k = rotary(q), rotary(k)          # rotate-half, whole head, absolute position
            h = h + rms2_l(softmax(q k^T / sqrt(head_dim), causal) v  Wo_l)
            h = h + rms4_l((silu(rms3_l(h) Wg_l) * (rms3_l(h) Wu_l)) Wd_l)
        h = rms_f(h);  h_u = h                   # the loop's output AND the next loop's input
    lambda_u = sigmoid(h_u w_gate + b_gate)
    p_u = lambda_u * prod_{j<u}(1 - lambda_j)  (u < last),  p_last = prod_{j<last}(1 - lambda_j)
    exit at the first u whose cumulative p reaches early_exit_threshold, else at the last
    logits = h_exit W_head                       (untied)

Each loop's attention reads the keys and values THAT loop wrote: a served
token leaves ``total_ut_steps * num_hidden_layers`` K/V sets in a cache.

``quant="fp8"`` is the CONTROL: the same mathematics with both operands of
every matrix product rounded to float8 e4m3 (scaled per tensor), the
nearest precision below the configuration's bfloat16. ``loops`` runs
fewer loops than the configuration says: the fault a loop can have.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def sizes(cfg: dict) -> dict:
    return {
        "V": cfg["vocab_size"], "d": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
        "H": cfg["num_attention_heads"], "Hk": cfg["num_key_value_heads"], "D": cfg["head_dim"],
        "ff": cfg["intermediate_size"], "U": cfg["total_ut_steps"],
    }


@functools.partial(jax.jit, static_argnames=("n", "shape"))
def _stacked_normal(key, n: int, shape: tuple):
    """[n, *shape] bfloat16, normal(STD): drawn in float32 a layer at a
    time, so that the float32 draw of the whole stack never exists."""
    return jax.lax.map(
        lambda k: (STD * jax.random.normal(k, shape, jnp.float32)).astype(jnp.bfloat16),
        jax.random.split(key, n),
    )


def make_weights(cfg: dict, seed: int):
    """bfloat16 weights on the device, in the layout the program's scanned
    stack takes them (one array a kind of matrix, layers on the first
    axis): matrices, embedding, head and exit gate normal(0.02), norm
    gains 1, the gate's bias 0. Grouped KV heads (fewer than query heads)
    are not this configuration's and are refused."""
    z = sizes(cfg)
    if z["Hk"] != z["H"]:
        raise NotImplementedError("this reference has as many KV heads as query heads")
    d, L, ff, HD = z["d"], z["L"], z["ff"], z["H"] * z["D"]
    ks = iter(jax.random.split(seed_key(seed), 8))
    one = lambda shape: _stacked_normal(next(ks), 1, shape)[0]  # noqa: E731
    gain = lambda *lead: {"scale": jnp.ones((*lead, d), jnp.bfloat16)}  # noqa: E731
    return {
        "wte": {"embedding": one((z["V"], d))},
        "head": one((d, z["V"])),
        "ln_f": gain(),
        "exit_gate": {"kernel": one((d, 1)), "bias": jnp.zeros((1,), jnp.bfloat16)},
        "layers": {
            "ln1": gain(L), "ln1_post": gain(L), "ln2": gain(L), "ln2_post": gain(L),
            "attn": {
                "qkv": {"kernel": _stacked_normal(next(ks), L, (d, 3 * HD))},
                "proj": {"kernel": _stacked_normal(next(ks), L, (HD, d))},
            },
            "gate": {"kernel": _stacked_normal(next(ks), L, (d, ff))},
            "up": {"kernel": _stacked_normal(next(ks), L, (d, ff))},
            "down": {"kernel": _stacked_normal(next(ks), L, (ff, d))},
        },
    }


def _q8(x):
    """Round to float8 (e4m3: 3 bits of mantissa), scaled per tensor so
    that the largest magnitude sits at the format's largest, 448."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    rounded = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(rounded - x)  # straight through


def _mm(a, b, quant):
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision="highest")


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(jnp.float32)


def _rotate(x, cos, sin):
    """x [B, H, T, D]; cos, sin [B, 1, T, D]: rotate-half over the whole head."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@functools.partial(jax.jit, static_argnames=("H", "D", "eps", "quant"))
def _layer(x, layers, l, cos, sin, allow, *, H, D, eps, quant):
    """Layer ``l`` of the stack on x [B, T, d]: one compile for every layer
    of every loop."""
    p = jax.tree.map(lambda a: a[l].astype(jnp.float32), layers)
    B, T, _ = x.shape
    a = _rms(x, p["ln1"]["scale"], eps)
    q, k, v = jnp.split(_mm(a, p["attn"]["qkv"]["kernel"], quant), 3, axis=-1)
    q, k, v = (t.reshape(B, T, H, D).transpose(0, 2, 1, 3) for t in (q, k, v))
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    s = _mm(q, k.transpose(0, 1, 3, 2), quant) * D**-0.5
    w = jax.nn.softmax(jnp.where(allow, s, -1e9), axis=-1)
    o = _mm(w, v, quant).transpose(0, 2, 1, 3).reshape(B, T, H * D)
    x = x + _rms(_mm(o, p["attn"]["proj"]["kernel"], quant), p["ln1_post"]["scale"], eps)
    a = _rms(x, p["ln2"]["scale"], eps)
    f = jax.nn.silu(_mm(a, p["gate"]["kernel"], quant)) * _mm(a, p["up"]["kernel"], quant)
    return x + _rms(_mm(f, p["down"]["kernel"], quant), p["ln2_post"]["scale"], eps)


def loop_states(cfg: dict, params, tokens, mask=None, quant=None, loops=None):
    """tokens [B, T] (+ key mask [B, T], True on real tokens; positions
    count real tokens) -> the normed state h_u every loop leaves, a list of
    ``loops`` (default ``total_ut_steps``) arrays [B, T, d]."""
    z = sizes(cfg)
    B, T = tokens.shape
    eps = float(cfg["rms_norm_eps"])
    mask = jnp.ones((B, T), bool) if mask is None else mask.astype(bool)
    pos = jnp.clip(jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1, 0).astype(jnp.float32)
    inv_freq = float(cfg["rope_theta"]) ** (-jnp.arange(0, z["D"], 2, dtype=jnp.float32) / z["D"])
    ang = pos[:, None, :, None] * inv_freq  # [B, 1, T, D/2]
    ang = jnp.concatenate([ang, ang], axis=-1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    allow = jnp.tril(jnp.ones((T, T), bool))[None, None] & mask[:, None, None, :]
    x = params["wte"]["embedding"][tokens].astype(jnp.float32)
    out = []
    for _u in range(z["U"] if loops is None else loops):
        for l in range(z["L"]):
            x = _layer(x, params["layers"], l, cos, sin, allow, H=z["H"], D=z["D"], eps=eps, quant=quant)
        x = _rms(x, params["ln_f"]["scale"], eps)
        out.append(x)
    return out


def exit_distribution(cfg: dict, params, states):
    """p_u [loops, B, T] over the loops whose states are given: the gate's
    lambda_u weighs loop u by what no earlier loop took; the last takes
    the rest."""
    g = params["exit_gate"]
    lam = jnp.stack([
        jax.nn.sigmoid(_mm(h, g["kernel"].astype(jnp.float32), None)[..., 0] + g["bias"].astype(jnp.float32)[0])
        for h in states
    ])
    before = jnp.concatenate([jnp.ones_like(lam[:1]), jnp.cumprod(1.0 - lam, axis=0)[:-1]])
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


def exit_loop(p, threshold: float):
    """The loop each token leaves at: the first whose cumulative p reaches
    ``threshold``, else the last. [B, T] int."""
    hit = jnp.cumsum(p, axis=0) >= threshold
    return jnp.argmax(hit.at[-1].set(True), axis=0)


def head(params, h, quant=None):
    return _mm(h, params["head"].astype(jnp.float32), quant)


def logits_fn(cfg: dict, params, tokens, mask=None, quant=None, loops=None):
    """Logits [B, T, V] of the loop each token exits at: at the published
    threshold 1.0 the last loop run."""
    states = loop_states(cfg, params, tokens, mask, quant, loops)
    thr = float(cfg["early_exit_threshold"])
    if thr >= 1.0 or len(states) == 1:
        return head(params, states[-1], quant)
    at = exit_loop(exit_distribution(cfg, params, states), thr)
    h = jnp.take_along_axis(jnp.stack(states), at[None, ..., None], axis=0)[0]
    return head(params, h, quant)


def token_log_probs(cfg, params, tokens, mask=None, temperature=1.0, quant=None, loops=None):
    """log p(token_t | tokens_<t) [B, T]; column 0 has no prediction: 0."""
    lg = logits_fn(cfg, params, tokens, mask, quant, loops)[:, :-1] / temperature
    lp = jax.nn.log_softmax(lg, axis=-1)
    out = jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.concatenate([jnp.zeros_like(out[:, :1]), out], axis=1)


def score_rows(cfg, params, tokens, mask, temperature=1.0, quant=None, block=8, fault=None):
    """:func:`token_log_probs` of many rows in blocks of ``block``, so that
    the [rows, T, V] logits fit beside whatever else is on the chip.
    ``fault="loops_minus_one"`` runs one loop fewer than the configuration
    says."""
    if fault not in (None, "loops_minus_one"):
        raise ValueError(f"unknown fault {fault!r}")
    loops = cfg["total_ut_steps"] - 1 if fault else None
    out = [
        token_log_probs(cfg, params, tokens[i:i + block], mask[i:i + block], temperature, quant, loops)
        for i in range(0, tokens.shape[0], block)
    ]
    return jnp.concatenate(out, axis=0)

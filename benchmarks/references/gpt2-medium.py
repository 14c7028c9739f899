"""Plain reference of the ``gpt2-medium`` configuration.

A GPT-2 decoder in straightforward ``jax.numpy``, float32, every matrix
product at ``highest`` precision, no kernels, no cache, no batching tricks.
It imports nothing of ``rl_tpu`` and takes nothing the program made: the
weights come from :func:`make_weights` (the benchmark's own, from the
seed), the tokens it scores are the ones the timed path served.

Block (Radford et al. 2019, as ``openai-community/gpt2-medium``): pre-LN,
fused qkv, tanh GELU, learned positions, tied head. Departures, all the
program's and listed under ``assumed`` in the configuration file: no bias
on qkv/proj, LayerNorm epsilon 1e-6.

``quant="fp8"`` is the CONTROL: the same mathematics with both operands
of every matrix product rounded to float8 e4m3 (scaled per tensor) — the
nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(cfg: dict, seed: int):
    """float32 weights on the device in one jitted call (GPT-2's init:
    normal(0.02), residual projections scaled by 1/sqrt(2 n_layer))."""
    V, d, L, ff, ctx = (cfg[k] for k in ("vocab_size", "n_embd", "n_layer", "n_inner", "n_positions"))

    @jax.jit
    def build(key):
        ks = iter(jax.random.split(key, 2 + 4 * L))
        n = lambda shape, std=0.02: std * jax.random.normal(next(ks), shape, jnp.float32)  # noqa: E731
        ln = lambda: {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}  # noqa: E731
        res = 0.02 / math.sqrt(2 * L)
        p = {"wte": {"embedding": n((V, d))}, "wpe": {"embedding": n((ctx, d), 0.01)}, "ln_f": ln()}
        for i in range(L):
            p[f"h{i}"] = {
                "ln1": ln(), "ln2": ln(),
                "attn": {"qkv": {"kernel": n((d, 3 * d))}, "proj": {"kernel": n((d, d), res)}},
                "up": {"kernel": n((d, ff)), "bias": jnp.zeros((ff,), jnp.float32)},
                "down": {"kernel": n((ff, d), res), "bias": jnp.zeros((d,), jnp.float32)},
            }
        return p

    return build(seed_key(seed))


def _q8(x):
    """Round to float8 (e4m3: 3 bits of mantissa), scaled per tensor so
    that the largest magnitude sits at the format's largest, 448."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    rounded = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    # straight through: the backward pass sees the rounded operands but is
    # not itself cast (a cast would flush every small cotangent to zero)
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(a, b, quant):
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision="highest")


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def logits_fn(cfg: dict, params, tokens, mask=None, quant=None):
    """tokens [B, T] (+ key mask [B, T], True on real tokens; positions
    count real tokens, so left-padded rows start at 0) -> logits [B, T, V]."""
    B, T = tokens.shape
    H = cfg["n_head"]
    d = cfg["n_embd"]
    D = d // H
    if mask is None:
        mask = jnp.ones((B, T), bool)
    mask = mask.astype(bool)
    pos = jnp.clip(jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1, 0)
    x = params["wte"]["embedding"][tokens] + params["wpe"]["embedding"][pos]
    allow = jnp.tril(jnp.ones((T, T), bool))[None, None] & mask[:, None, None, :]
    for i in range(cfg["n_layer"]):
        p = params[f"h{i}"]
        h = _ln(x, p["ln1"])
        q, k, v = jnp.split(_mm(h, p["attn"]["qkv"]["kernel"], quant), 3, axis=-1)
        q, k, v = (a.reshape(B, T, H, D).transpose(0, 2, 1, 3) for a in (q, k, v))
        s = _mm(q, k.transpose(0, 1, 3, 2), quant) * D**-0.5
        a = jax.nn.softmax(jnp.where(allow, s, -1e9), axis=-1)
        o = _mm(a, v, quant).transpose(0, 2, 1, 3).reshape(B, T, d)
        x = x + _mm(o, p["attn"]["proj"]["kernel"], quant)
        h = _ln(x, p["ln2"])
        h = _gelu(_mm(h, p["up"]["kernel"], quant) + p["up"]["bias"])
        x = x + _mm(h, p["down"]["kernel"], quant) + p["down"]["bias"]
    x = _ln(x, params["ln_f"])
    return _mm(x, params["wte"]["embedding"].T, quant)


def token_log_probs(cfg, params, tokens, mask=None, temperature=1.0, quant=None):
    """log p(token_t | tokens_<t) [B, T]; column 0 has no prediction: 0."""
    lg = logits_fn(cfg, params, tokens, mask, quant)[:, :-1] / temperature
    lp = jax.nn.log_softmax(lg, axis=-1)
    out = jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.concatenate([jnp.zeros_like(out[:, :1]), out], axis=1)


@functools.lru_cache(maxsize=None)
def _scorer(shape: tuple, temperature: float, quant):
    """One jitted scorer per (sizes, temperature, precision): a new closure
    a call would be traced again every time."""
    cfg = dict(zip(_SHAPE_KEYS, shape))
    return jax.jit(functools.partial(token_log_probs, cfg, temperature=temperature, quant=quant))


_SHAPE_KEYS = ("vocab_size", "n_embd", "n_layer", "n_head", "n_inner", "n_positions")


def score_rows(cfg, params, tokens, mask, temperature=1.0, quant=None, block=8):
    """:func:`token_log_probs` of many rows in blocks of ``block``, so that
    the [rows, T, V] logits fit beside whatever else is on the chip."""
    f = _scorer(tuple(cfg[k] for k in _SHAPE_KEYS), float(temperature), quant)
    out = [f(params, tokens[i:i + block], mask[i:i + block]) for i in range(0, tokens.shape[0], block)]
    return jnp.concatenate(out, axis=0)


# -- the GRPO job (cell gpt2-medium.grpo) -------------------------------------


def group_advantage(reward, group_id, n_groups, eps=1e-4):
    """A_i = (r_i - mean_group) / (std_group + eps)."""
    onehot = (group_id[:, None] == jnp.arange(n_groups)[None, :]).astype(jnp.float32)
    cnt = jnp.clip(onehot.sum(0), 1.0)
    mean = (onehot * reward[:, None]).sum(0) / cnt
    adv = reward - mean[group_id]
    std = jnp.sqrt((onehot * adv[:, None] ** 2).sum(0) / cnt)
    return adv / (std[group_id] + eps)


def grpo_step(cfg, job, params, opt, params0, batch, task_reward, quant=None, block=8,
              fault=None):
    """One GRPO step on the tokens the program sampled: behaviour and
    reference log-probs, KL-shaped reward, group advantage, clipped
    surrogate over response tokens, Adam. Returns
    ``(params, opt, out)`` with ``out``: loss, per-token behaviour
    log-probs, the gradient's per-leaf norms.

    ``fault="half_batch"`` plants step 3's fault for reading its size: the
    second half of the rows is left out, the mean taken over the rest."""
    tokens = jnp.asarray(batch["tokens"])
    amask = jnp.asarray(batch["attention_mask"]).astype(bool)
    rmask = jnp.asarray(batch["assistant_mask"]).astype(bool)
    gid = jnp.asarray(batch["group_id"])
    behav = score_rows(cfg, params, tokens, amask, job["temperature"], quant, block)
    ref = score_rows(cfg, params0, tokens, amask, job["temperature"], quant, block)
    delta = jnp.clip(jnp.where(rmask, behav - ref, 0.0), -20.0, 20.0)
    reward = jnp.asarray(task_reward, jnp.float32) - job["kl_coeff"] * delta.sum(1)
    adv = group_advantage(reward, gid, job["num_prompts"])
    eps = job["clip_epsilon"]
    rows = tokens.shape[0] // 2 if fault == "half_batch" else tokens.shape[0]
    denom = jnp.clip(rmask[:rows].sum(), 1).astype(jnp.float32)

    def block_gain(p, tk, am, rm, bh, ad):
        lp = token_log_probs(cfg, p, tk, am, job["temperature"], quant)
        ratio = jnp.exp(jnp.where(rm, lp - bh, 0.0))
        gain = jnp.minimum(ratio * ad[:, None], jnp.clip(ratio, 1 - eps, 1 + eps) * ad[:, None])
        return -(gain * rm).sum() / denom

    vg = jax.jit(jax.value_and_grad(block_gain))
    loss, grads = 0.0, None
    for i in range(0, rows, block):
        s = slice(i, min(i + block, rows))
        v, g = vg(params, tokens[s], amask[s], rmask[s], behav[s], adv[s])
        loss = loss + v
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    params, opt = adam(params, opt, grads, job["learning_rate"])
    return params, opt, {"loss": float(loss), "behav": behav, "grad_norms": leaf_norms(grads)}


def adam_init(params):
    return {"mu": jax.tree.map(jnp.zeros_like, params), "nu": jax.tree.map(jnp.zeros_like, params), "t": 0}


@functools.partial(jax.jit, static_argnames=("lr", "t"), donate_argnums=(0, 1, 2))
def _adam(params, mu, nu, grads, lr, t):
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    c1, c2 = 1 - ADAM_B1**t, 1 - ADAM_B2**t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS), params, mu, nu
    )
    return params, mu, nu


def adam(params, opt, grads, lr):
    t = opt["t"] + 1
    params, mu, nu = _adam(params, opt["mu"], opt["nu"], grads, lr=lr, t=t)
    return params, {"mu": mu, "nu": nu, "t": t}


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def _named(tree) -> dict:
    """{path: value} of every leaf, on the host."""
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v) for path, v in flat}


def leaf_norms(tree) -> dict:
    return _named(_leaf_norms(tree))


@jax.jit
def _diff_norms(a, b):
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)


def diff_norms(a, b) -> dict:
    return _named(_diff_norms(a, b))

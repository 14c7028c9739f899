"""Fused top-k/temperature sampling for the shared ``sample_tokens``.

The stock path (``rl_tpu.models.speculative.sample_tokens``) lowers to a
full-vocab log-softmax, a separate gumbel materialization, an argmax, and
a gather — four [S, V] traversals stitched by XLA. The fused kernel does
scale → (optional) top-k filter → log-softmax → gumbel-argmax → chosen
log-prob in ONE pass over a block of whole vocab rows resident in VMEM.

What Mosaic compiles (v5e, ``tests/test_chip_compile.py``): the grid runs
over blocks of 8 rows, never over the vocab, so every row's softmax
reduction is whole inside one grid step; the chosen log-prob is a
one-hot masked max instead of a gather; the top-k threshold is computed
outside the kernel (``lax.top_k`` has no in-kernel lowering) and enters
as a ``[S, 1]`` operand.

Bit-exactness contract (the PR 16 guarantee rides on this):

- The **stock path** (``mode is None``) with ``top_k=0`` is literally the
  legacy ``sample_tokens`` body — same ops, same order — so it is
  bitwise-identical to every artifact PR 16 committed.
- The **kernel** consumes the same f32 logits plus gumbel noise computed
  OUTSIDE with the exact key math ``jax.random.categorical`` uses
  (categorical(key, lps) ≡ argmax(gumbel(key, lps.shape, lps.dtype) +
  lps)), and its body is jnp ops over whole rows — so interpret mode
  reproduces the stock path bit for bit. f32 add is commutative bitwise,
  argmax ties resolve to the first index in both, and a max over one
  unmasked element returns that element's bits.
- Greedy argmaxes the UNSCALED f32 logits: bf16→f32 is monotone and
  injective, so ties (and their first-index resolution) match the legacy
  ``argmax(logits)`` exactly; dividing by temperature first could round
  two distinct logits onto the same value and flip a tie.

Top-k keeps every logit at or above the k-th highest (ties at the
threshold all survive) and sends the rest to -inf before the softmax;
the comparison is made on the unscaled logits — the temperature is
positive, so the order is the same, and no backend's division rounding
can move a logit across the threshold. ``top_k=0`` disables filtering.
"""

from __future__ import annotations

import functools

from . import registry

_ROWS = 8  # f32 sublane tile: rows per grid step


def _fused_sample_kernel(t_ref, x_ref, *refs, greedy, top_k):
    """t [1] in SMEM; x [rows, V]; then g [rows, V] unless greedy, thr
    [rows, 1] if top_k; outputs tok [rows, 1] int32, lp [rows, 1] f32."""
    import jax
    import jax.numpy as jnp

    refs = list(refs)
    g_ref = None if greedy else refs.pop(0)
    thr_ref = refs.pop(0) if top_k else None
    tok_ref, lp_ref = refs

    x = x_ref[...]
    xs = x / t_ref[0]
    if thr_ref is not None:
        xs = jnp.where(x >= thr_ref[...], xs, -jnp.inf)
    lps = jax.nn.log_softmax(xs, axis=-1)
    score = x if greedy else g_ref[...] + lps
    tok = jnp.argmax(score, axis=-1).astype(jnp.int32)[:, None]
    col = jax.lax.broadcasted_iota(jnp.int32, lps.shape, 1)
    tok_ref[...] = tok
    lp_ref[...] = jnp.max(
        jnp.where(col == tok, lps, -jnp.inf), axis=-1, keepdims=True
    )


def _gumbel_like(key, x):
    """The exact noise ``jax.random.categorical`` would draw for logits
    of x's shape/dtype — scalar key or per-row key vector (vmapped keys
    match ``jax.vmap(jax.random.categorical)``)."""
    import jax

    if getattr(key, "ndim", 0):
        return jax.vmap(
            lambda k: jax.random.gumbel(k, (x.shape[-1],), x.dtype)
        )(key)
    return jax.random.gumbel(key, x.shape, x.dtype)


def fused_sample(logits, key, *, temperature=1.0, greedy=False, top_k=0):
    """Sample one token per row of ``logits`` [S, V]; returns
    ``(tok [S] int32, lp [S] f32)``. Drop-in for the legacy
    ``sample_tokens`` body (bitwise, when ``top_k=0``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mode = registry.selection("sampling")
    x = logits.astype(jnp.float32)
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    # top_k is a static Python int (it shapes lax.top_k) — no coercion,
    # an int() here would read as a host sync on the hot path (R001)
    top_k = top_k or 0
    if top_k >= x.shape[-1]:
        top_k = 0  # keeping the whole vocab = no filter
    thr = jax.lax.top_k(x, top_k)[0][:, -1:] if top_k else None

    if mode is None:
        # Legacy sample_tokens body, verbatim (top_k=0): PR 16 bit-exact.
        xs = x / t
        if top_k:
            xs = jnp.where(x >= thr, xs, -jnp.inf)
        lps = jax.nn.log_softmax(xs, axis=-1)
        if greedy:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        elif getattr(key, "ndim", 0):
            tok = jax.vmap(jax.random.categorical)(key, lps).astype(jnp.int32)
        else:
            tok = jax.random.categorical(key, lps).astype(jnp.int32)
        lp = jnp.take_along_axis(lps, tok[:, None], axis=-1)[:, 0]
        return tok, lp

    S, V = x.shape
    rows = _ROWS if S % _ROWS == 0 else S

    def row_block(width):
        return pl.BlockSpec((rows, width), lambda i: (i, 0))

    operands = [t.reshape(1), x]
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), row_block(V)]
    if not greedy:
        operands.append(_gumbel_like(key, x))
        in_specs.append(row_block(V))
    if top_k:
        operands.append(thr)
        in_specs.append(row_block(1))
    tok, lp = pl.pallas_call(
        functools.partial(_fused_sample_kernel, greedy=greedy, top_k=top_k),
        name="_fused_sample_kernel",
        grid=(S // rows,),
        in_specs=in_specs,
        out_specs=[row_block(1), row_block(1)],
        out_shape=[
            jax.ShapeDtypeStruct((S, 1), jnp.int32),
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # two double-buffered row blocks + the body's block-sized
            # temporaries (xs, lps, score, iota, mask, ...)
            vmem_limit_bytes=12 * rows * V * 4 + (4 << 20),
        ),
        interpret=(mode == "interpret"),
    )(*operands)
    return tok[:, 0], lp[:, 0]

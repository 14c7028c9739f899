"""Fused PER sum-tree update: leaf write + block-sum propagation.

The device PER sampler (``rl_tpu.data.replay.samplers``) keeps a flat
two-level tree: ``priorities`` [padded] leaves and ``esum`` [n_blocks]
per-block sums (fanout leaves each). The stock update lowers to TWO
scatter-adds — two full passes over the level arrays with separate index
materializations. The fused kernel holds both levels in VMEM, streams
the update batch once from SMEM, and applies the leaf delta and its
block-sum propagation together.

Layout: each level is viewed lane-dense as ``[n / 128, 128]`` (a 1-D f32
array and its ``[n / 128, 128]`` view share one tiled layout, so the
reshape is free when ``n`` is a multiple of 128; other sizes are padded
per call). One update reads the ``(1, 128)`` row holding its element,
adds the delta on the element's lane and writes the row back. Both
levels live in VMEM whole — 8.5 MiB in + out at capacity ``2**20`` — and
the update batch lives in SMEM whole, so the kernel's reach is bounded by
the chip's fast memories. :func:`fits` is that bound, a rule on static
shapes: a tree over :data:`VMEM_BUDGET_BYTES` or a batch over
:data:`MAX_UPDATES` (a bulk re-prioritization, not the per-step write-
back) takes the two scatter-adds, and the HLO shows which ran.

Exactness: bit-exact vs the stock scatter-adds. The kernel applies
updates sequentially in batch order; XLA's scatter-add also combines
duplicate indices in operand order. The caller (``_delta_update``) has
already deduplicated (non-last writers carry delta 0.0), and
``x + 0.0 == x`` bitwise for the non-negative priorities PER stores —
which is also why adding 0.0 on a row's other 127 lanes changes nothing.
"""

from __future__ import annotations

import functools

from . import registry

_LANES = 128
# v5e has 128 MiB of VMEM per core; half of it leaves the compiler room
# for its own scratch. Reached at capacity ~2**22.8 with fanout 16.
VMEM_BUDGET_BYTES = 64 << 20
# idx + delta take 8 bytes an update of the 1 MiB of SMEM
MAX_UPDATES = 32768


def _vmem_bytes(n_leaves: int, n_blocks: int) -> int:
    """Both levels, in and out, in whole lane rows of f32, plus room for
    the compiler's own scratch."""
    rows = -(-n_leaves // _LANES) + -(-n_blocks // _LANES)
    return 2 * 4 * _LANES * rows + (4 << 20)


def fits(n_leaves: int, n_blocks: int, n_updates: int) -> bool:
    """Can the fused kernel hold this tree and this update batch?"""
    return (
        n_updates <= MAX_UPDATES
        and _vmem_bytes(n_leaves, n_blocks) <= VMEM_BUDGET_BYTES
    )


def _sumtree_update_kernel(
    idx_ref, delta_ref, p_ref, e_ref, po_ref, eo_ref, *, fanout, n_updates
):
    """idx, delta [B] in SMEM; p [P/128, 128], e [NB/128, 128] in VMEM.
    Copy-through then a sequential read-modify-write per update — one
    kernel for both tree levels."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    po_ref[...] = p_ref[...]
    eo_ref[...] = e_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def add_at(ref, j, d):
        row = pl.ds(j // _LANES, 1)
        ref[row, :] = ref[row, :] + jnp.where(lane == j % _LANES, d, 0.0)

    def body(i, carry):
        j = idx_ref[i]
        d = delta_ref[i]
        add_at(po_ref, j, d)
        add_at(eo_ref, j // fanout, d)
        return carry

    jax.lax.fori_loop(0, n_updates, body, 0)


def _lane_rows(x):
    """[n] -> ([ceil(n/128), 128], n): zero-padded to whole lane rows."""
    import jax.numpy as jnp

    n = x.shape[0]
    pad = -n % _LANES
    if pad:
        x = jnp.pad(x, (0, pad))
    return x.reshape(-1, _LANES), n


def sumtree_update(priorities, esum, idx, delta, *, fanout):
    """Apply ``priorities[idx] += delta`` and ``esum[idx // fanout] +=
    delta`` in one fused pass; returns ``(priorities, esum)`` updated.
    The two stock scatter-adds where the registry selects no kernel or
    the shapes do not :func:`fits`."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mode = registry.selection("sumtree")
    if mode is None or not fits(priorities.shape[0], esum.shape[0], idx.shape[0]):
        return (
            priorities.at[idx].add(delta),
            esum.at[idx // fanout].add(delta),
        )

    p2, P = _lane_rows(priorities)
    e2, NB = _lane_rows(esum)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    po, eo = pl.pallas_call(
        functools.partial(
            _sumtree_update_kernel, fanout=int(fanout), n_updates=idx.shape[0]
        ),
        name="_sumtree_update_kernel",
        in_specs=[smem, smem, vmem, vmem],
        out_specs=[vmem, vmem],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, priorities.dtype),
            jax.ShapeDtypeStruct(e2.shape, esum.dtype),
        ],
        # the tree is updated in place where the caller donated it
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_bytes(P, NB)),
        interpret=(mode == "interpret"),
    )(
        jnp.asarray(idx, jnp.int32),
        jnp.asarray(delta, priorities.dtype),
        p2,
        e2,
    )
    return po.reshape(-1)[:P], eo.reshape(-1)[:NB]

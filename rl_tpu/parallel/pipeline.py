"""Pipeline parallelism: GPipe-style microbatch flow over a "pipe" mesh axis.

Fills the reference's pipeline slot TPU-natively (reference: torch
pipelining is delegated to torch.distributed.pipelining in the trainer
recipes; SURVEY §2.13 lists pp among the parallelism modes). Design follows
the scaling-book recipe rather than the torch one: stages are a LEADING
AXIS of the stacked per-stage params, sharded over ``pipe`` with
``shard_map``; microbatches march through the stages with
``lax.ppermute`` rotations inside a ``lax.scan`` over M + S - 1 ticks
(the classic GPipe schedule: fill, steady state, drain).

The backward pass needs no hand scheduling: differentiating through the
scan + ppermute yields the reversed pipeline automatically (ppermute's
transpose is the reverse rotation), i.e. autodiff derives the 1F1B-less
GPipe backward for free.

Stage granularity: ``stage_fn(stage_params, x) -> x`` is the whole
per-stage computation (e.g. ``n_layers // S`` transformer blocks applied
via ``lax.scan`` inside); activations must keep one shape through the
pipe (the transformer's [mb, T, d_model] stream does).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["pipeline_apply", "stack_stage_params", "AXIS_PIPE"]

AXIS_PIPE = "pipe"


def stack_stage_params(stage_params_list):
    """[S pytrees with equal structure] -> one pytree with leading S axis
    (shard this axis over "pipe")."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params_list)


def pipeline_apply(
    stage_fn,
    stacked_params,
    x,
    mesh: Mesh,
    axis_name: str = AXIS_PIPE,
    microbatches: int | None = None,
):
    """Run ``S`` chained stages over ``x`` with pipelined microbatches.

    Args:
        stage_fn: ``(stage_params, x_mb) -> y_mb`` — same activation shape
            in and out.
        stacked_params: pytree with leading stage axis S (see
            :func:`stack_stage_params`).
        x: global input [B, ...]; split into ``microbatches`` along axis 0.
        mesh: mesh containing ``axis_name`` of size S.
        microbatches: number of microbatches M (default S — the minimum
            for full pipe utilization is M >= S).

    Returns [B, ...] outputs (replicated over the pipe axis).
    """
    S = mesh.shape[axis_name]
    M = microbatches if microbatches is not None else S
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    xs = x.reshape(M, B // M, *x.shape[1:])

    fwd = [(i, i + 1) for i in range(S - 1)]  # stage i -> i+1

    def per_device(params, xs_local):
        # params leaves: [1, ...] (this device's stage); xs_local: the full
        # microbatch stream (replicated input)
        s = lax.axis_index(axis_name)
        total = M + S - 1

        def tick(carry, t):
            buf = carry  # activation handed over from the previous tick
            # stage 0 injects microbatch t (clamped during drain ticks)
            inp = jnp.where(
                s == 0, xs_local[jnp.clip(t, 0, M - 1)], buf
            )
            out = stage_fn(jax.tree.map(lambda p: p[0], params), inp)
            if S > 1:
                nxt = lax.ppermute(out, axis_name, fwd)
            else:
                nxt = out
            # last stage emits finished microbatch (valid when t >= S-1)
            y = jnp.where(s == S - 1, out, jnp.zeros_like(out))
            return nxt, y

        zero = jnp.zeros_like(xs_local[0])
        _, ys = lax.scan(tick, zero, jnp.arange(total))
        ys = ys[S - 1 :]  # [M, mb, ...] — nonzero only on the last stage
        # share the last stage's outputs with every pipe rank (psum: all
        # other ranks contribute zeros)
        return lax.psum(ys, axis_name)

    spec_params = jax.tree.map(lambda _: PartitionSpec(axis_name), stacked_params)
    out = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec_params, PartitionSpec()),
        out_specs=PartitionSpec(),
        check_vma=False,
    )(stacked_params, xs)
    return out.reshape(B, *x.shape[1:])


def pipe_mesh(n_stages: int, devices=None) -> Mesh:
    """A 1-axis ("pipe",) mesh over the first ``n_stages`` devices."""
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    if len(devices) < n_stages:
        raise ValueError(f"need {n_stages} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n_stages]), (AXIS_PIPE,))

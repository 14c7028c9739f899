"""Anakin: env + policy + learner fused into ONE multi-device XLA program.

The Podracer "Anakin" architecture (arXiv 2104.06272, PAPERS.md): the env
fleet is pure-array state living on device, so the whole RL loop — vmapped
env ``step_and_reset``, policy forward, GAE, epochs×minibatch SGD — stages
as a single jitted, donated program. The host's only job is to re-dispatch
it and drain metrics with the established lagged-one-dispatch pattern
(obs/device.py); there is **zero** host↔device traffic inside a dispatch,
which is what buys tens of thousands of parallel envs per chip and the
≥1M env-steps/s north star (ROADMAP item 4).

Composition, not reimplementation: :class:`AnakinProgram` builds a
:class:`~rl_tpu.collectors.single.Collector` over a :func:`make_fleet` env
and reuses :meth:`OnPolicyProgram.update_from_batch` for the learner half,
so every existing loss/advantage (PPO, A2C, V-trace) plugs in unchanged
and ``train_step`` is bit-identical to ``OnPolicyProgram.train_step`` —
the fused program is the *same math*, only the dispatch granularity and
placement change.

Sharding (the PR-7 ``(batch, fsdp)`` mesh): env state and rollout batches
shard their env dim over the data axes (including the per-env PRNG key
array — one independent stream per env is data), params/opt FSDP-shard
above the size cutoff, scalar keys replicate. The dispatch pins
``in_shardings == out_shardings`` from ``train_state_shardings`` so
donation reuses buffers in place instead of resharding.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..analysis import hot_path
from ..collectors.single import Collector
from ..data import ArrayDict
from ..envs.base import EnvBase
from ..objectives.common import LossModule
from ..obs.device import DeviceMetrics
from .on_policy import OnPolicyConfig, OnPolicyProgram

__all__ = ["AnakinConfig", "AnakinProgram", "default_anakin_metrics"]


def default_anakin_metrics() -> DeviceMetrics:
    """On-device schema for the fused program: monotone env-step/episode
    counters plus return/loss telemetry, all accumulated inside the
    dispatch and drained at most once per dispatch."""
    return DeviceMetrics(
        counters=("env_steps", "episodes", "episode_return_sum", "updates"),
        gauges=("loss", "reward_mean"),
    )


def _break_donation_aliases(tree):
    """Copy leaves that share a device buffer with an earlier leaf.

    Eager init paths legitimately alias (``EnvBase.reset`` hands the same
    zeros array to done/terminated/truncated); a donated dispatch then
    fails with "attempt to donate the same buffer twice". One init-time
    copy per duplicate breaks the aliasing for good — the program's
    outputs are always distinct buffers."""
    seen: set[int] = set()

    def fix(x):
        if not hasattr(x, "dtype"):
            return x
        try:
            ptr = x.unsafe_buffer_pointer()
        except Exception:
            ptr = id(x)
        if ptr in seen:
            return jnp.copy(x)
        seen.add(ptr)
        return x

    return jax.tree.map(fix, tree)


def _resolve_dm(device_metrics) -> DeviceMetrics | None:
    if device_metrics is True:
        return default_anakin_metrics()
    if device_metrics is False:
        return None
    return device_metrics


@dataclasses.dataclass
class AnakinConfig:
    """Fused-program shape. ``num_envs × unroll_length`` frames per train
    step; ``steps_per_dispatch`` train steps are scanned inside one
    dispatch (amortizing the host round-trip further)."""

    num_envs: int = 64
    unroll_length: int = 16
    steps_per_dispatch: int = 1
    # learner half (forwarded to the inner OnPolicyProgram)
    num_epochs: int = 4
    minibatch_size: int = 256
    max_grad_norm: float = 0.5
    learning_rate: float = 3e-4
    anneal_lr_to: float | None = None
    total_steps: int | None = None
    fsdp_min_size_mb: float = 4.0


class AnakinProgram:
    """The fused Anakin train program over an on-device env fleet.

    Args:
        env: fleet env name (see :func:`rl_tpu.envs.fleet_env_names`), a
            scalar ``EnvBase`` (wrapped via :func:`make_fleet`), or an
            already-batched env whose batch size equals ``config.num_envs``.
        policy: ``(params, td, key) -> td`` writing "action" (+extras).
        loss: any :class:`LossModule` (PPO/A2C/...); its value estimator
            provides the advantage exactly as in ``OnPolicyProgram``.
        mesh: optional ``(batch, fsdp)`` mesh; the dispatch then runs with
            pinned shardings from ``train_state_shardings``.
        device_metrics: True (default schema), False, or a custom
            :class:`DeviceMetrics`.

    Usage::

        program = AnakinProgram("cartpole", policy, loss, config, mesh=mesh)
        ts = program.init(jax.random.key(0))
        ts, snapshot = program.run(ts, num_dispatches=100)
    """

    def __init__(
        self,
        env: str | EnvBase,
        policy: Callable | None,
        loss: LossModule,
        config: AnakinConfig = AnakinConfig(),
        advantage: Callable[[dict, ArrayDict], ArrayDict] | None = None,
        recompute_advantage: bool = False,
        mesh=None,
        device_metrics=True,
        **env_kwargs,
    ):
        from ..envs.fleet import make_fleet

        self.config = config
        if isinstance(env, str):
            env = make_fleet(env, config.num_envs, **env_kwargs)
        elif env_kwargs:
            raise TypeError("env_kwargs only apply when env is a registry name")
        elif env.batch_shape == ():
            env = make_fleet(env, config.num_envs)
        num_envs = math.prod(env.batch_shape)
        if num_envs != config.num_envs:
            raise ValueError(
                f"env batch {env.batch_shape} != config.num_envs={config.num_envs}"
            )
        self.env = env
        self.num_envs = num_envs
        self.frames_per_step = config.num_envs * config.unroll_length
        # static python int, pre-cast so the traced accumulator never calls
        # float() on the hot path (rlint R001 treats that as a sync pattern)
        self._frames_per_step_f = float(self.frames_per_step)
        self.env_steps_per_dispatch = self.frames_per_step * config.steps_per_dispatch
        collector = Collector(
            env, policy, frames_per_batch=self.frames_per_step
        )
        self.inner = OnPolicyProgram(
            collector,
            loss,
            OnPolicyConfig(
                num_epochs=config.num_epochs,
                minibatch_size=config.minibatch_size,
                max_grad_norm=config.max_grad_norm,
                learning_rate=config.learning_rate,
                anneal_lr_to=config.anneal_lr_to,
                total_steps=config.total_steps,
            ),
            advantage,
            recompute_advantage,
        )
        self.mesh = mesh
        self.device_metrics = _resolve_dm(device_metrics)
        self._jit_dispatch = None

    # -- state ----------------------------------------------------------------

    def init(self, key: jax.Array, example_td: ArrayDict | None = None) -> dict:
        """Build (and, with a mesh, place) the train state."""
        ts = _break_donation_aliases(self.inner.init(key, example_td))
        if self.mesh is not None:
            from ..parallel.mesh import shard_train_state

            ts = shard_train_state(
                ts,
                self.mesh,
                self.num_envs,
                min_size_mbytes=self.config.fsdp_min_size_mb,
            )
        return ts

    def init_metrics(self) -> dict | None:
        if self.device_metrics is None:
            return None
        dm = self.device_metrics.init()
        if self.mesh is not None:
            from ..parallel.mesh import replicated

            dm = jax.device_put(dm, replicated(self.mesh))
        return dm

    # -- the fused step (device side) -----------------------------------------

    def train_step(self, ts: dict) -> tuple[dict, ArrayDict]:
        """One fused collect→advantage→SGD step, no metrics accumulation —
        bit-identical to ``OnPolicyProgram.train_step`` (same key usage,
        same op order), kept for parity testing and single-step use."""
        ts, _, metrics = self._fused_step(ts, None)
        return ts, metrics

    def _fused_step(self, ts: dict, dm: dict | None):
        params = ts["params"]
        batch, cstate = self.inner.collector.collect(params, ts["collector"])
        params, opt_state, rng, metrics = self.inner.update_from_batch(
            params, ts["opt"], ts["rng"], batch
        )
        new_ts = {"params": params, "opt": opt_state, "collector": cstate, "rng": rng}
        if dm is not None:
            dm = self._accumulate(dm, batch, metrics)
        return new_ts, dm, metrics

    def _accumulate(self, dm: dict, batch: ArrayDict, metrics: ArrayDict) -> dict:
        m = self.device_metrics
        done = batch["next", "done"]
        dm = m.inc(dm, "env_steps", self._frames_per_step_f)
        dm = m.inc(dm, "episodes", jnp.sum(done.astype(jnp.float32)))
        if ("next", "episode_reward") in batch:
            # RewardSum: terminal episode returns at done edges
            ret = jnp.sum(jnp.where(done, batch["next", "episode_reward"], 0.0))
        else:
            ret = jnp.sum(batch["next", "reward"])
        dm = m.inc(dm, "episode_return_sum", ret)
        dm = m.inc(dm, "updates", 1.0)
        dm = m.set_gauge(dm, "loss", metrics["loss"])
        dm = m.set_gauge(dm, "reward_mean", metrics["reward_mean"])
        return dm

    def _dispatch_impl(self, ts: dict, dm: dict | None):
        n = self.config.steps_per_dispatch
        if n == 1:
            return self._fused_step(ts, dm)

        def body(carry, _):
            ts, dm = carry
            ts, dm, metrics = self._fused_step(ts, dm)
            return (ts, dm), metrics

        (ts, dm), metrics = jax.lax.scan(body, (ts, dm), None, length=n)
        return ts, dm, jax.tree.map(lambda x: x.mean(), metrics)

    def _build_dispatch(self, ts: dict, dm: dict | None):
        from ..compile import get_program_registry

        registry = get_program_registry()
        fingerprint = repr((
            type(self.env).__name__, self.config,
            type(self.inner.loss).__name__,
            None if self.mesh is None else sorted(self.mesh.shape.items()),
        ))
        if self.mesh is None:
            return registry.register(
                "anakin.dispatch",
                self._dispatch_impl,
                fingerprint=fingerprint,
                donate_argnums=(0,),  # the train state, updated in place
            )
        from ..parallel.mesh import replicated, train_state_shardings

        ts_sh = train_state_shardings(
            ts,
            self.mesh,
            self.num_envs,
            min_size_mbytes=self.config.fsdp_min_size_mb,
        )
        repl = replicated(self.mesh)
        dm_sh = jax.tree.map(lambda _: repl, dm)
        # out ts/dm pinned to the in layout: donation reuses buffers in
        # place, no silent reshard copy; metrics placement left to XLA
        return registry.register(
            "anakin.dispatch",
            self._dispatch_impl,
            fingerprint=fingerprint,
            donate_argnums=(0,),  # the train state, updated in place
            in_shardings=(ts_sh, dm_sh),
            out_shardings=(ts_sh, dm_sh, None),
        )

    def dispatch(self, ts: dict, dm: dict | None = None):
        """One compiled dispatch: ``steps_per_dispatch`` fused steps.
        Returns ``(ts, dm, metrics)``; ``ts`` is donated."""
        if self._jit_dispatch is None:
            self._jit_dispatch = self._build_dispatch(ts, dm)
        return self._jit_dispatch(ts, dm)

    def aot_warmup(self, ts: dict, dm: dict | None = None, *, background: bool = False):
        """Pre-compile (or reload from the executable store) the fused
        dispatch program for ``ts``/``dm``'s exact layout before the first
        :meth:`run` loop. ``ts`` is :meth:`init`'s result and ``dm``
        :meth:`init_metrics`'s (only shapes/dtypes/shardings are read, so
        a restored checkpoint works too). Returns the registry report, or
        a :class:`~rl_tpu.compile.WarmupHandle` when backgrounded."""
        from ..compile import abstract_like, get_program_registry

        if self._jit_dispatch is None:
            self._jit_dispatch = self._build_dispatch(ts, dm)
        self._jit_dispatch.add_signature(abstract_like(ts), abstract_like(dm))
        return get_program_registry().aot_warmup(
            programs=[self._jit_dispatch], background=background
        )

    # -- host loop -------------------------------------------------------------

    @hot_path(reason="anakin fused env+policy+learner dispatch loop")
    def run(
        self,
        ts: dict,
        num_dispatches: int,
        registry=None,
        dm: dict | None = None,
    ) -> tuple[dict, dict | None]:
        """Drive ``num_dispatches`` dispatches back to back.

        Metrics drain with the lagged-one-dispatch pattern (PR 3): start
        this dispatch's device→host copy immediately, materialize/publish
        the PREVIOUS one (already landed) — the loop never blocks on the
        in-flight program. ``dm`` is deliberately NOT donated by
        :meth:`dispatch`, so the lagged snapshot's buffers stay valid.
        Returns ``(ts, final_snapshot)`` (snapshot None when metrics are
        disabled).
        """
        m = self.device_metrics
        if m is not None and dm is None:
            dm = self.init_metrics()
        pending = None
        for _ in range(num_dispatches):
            ts, dm, _ = self.dispatch(ts, dm)
            if m is not None:
                DeviceMetrics.drain_async(dm)
                if pending is not None and registry is not None:
                    m.publish(DeviceMetrics.drain(pending), registry)
                pending = dm
        if m is None:
            return ts, None
        snapshot = DeviceMetrics.drain(dm)
        if registry is not None:
            m.publish(snapshot, registry)
        return ts, snapshot

"""End-to-end GRPO/RLHF recipe: tokenizer → chat env → generate → GRPO.

Redesign of the reference's sota GRPO recipe (reference:
sota-implementations/grpo/grpo-sync.py — HF model + vLLM engine + ray weight
sync + KLRewardTransform; torchrl/envs/llm/transforms/kl.py:159) as one
TPU-native component: the SAME TransformerLM params serve jitted KV-cache
generation (local attention) and the training forward (optionally ring
attention over a "context" mesh axis for long sequences), weights move
through a :class:`~rl_tpu.weight_update.DevicePutScheme`, and the KL penalty
is shaped into the reward before group advantages.

Two trainers share the machinery:

- :class:`GRPOTrainer` — the sequential cycle (collect → update → push),
  with the update running as a donated gradient-accumulation microbatch
  ``lax.scan`` and step metrics accumulated on device
  (:class:`~rl_tpu.obs.DeviceMetrics`, drained lagged-one-dispatch — no
  per-step blocking host sync).
- :class:`PipelinedGRPOTrainer` — the grpo-async shape (reference
  sota-implementations/grpo/grpo-async.py; Podracer arXiv:2104.06272):
  generation for step k+1 runs in a background thread against the
  previous weight version while the learner updates on batch k.
  :class:`RolloutPipeline` bounds staleness at its queue depth — with the
  default ``max_pending=1`` every consumed batch is at most ONE version
  behind the trainer (off-by-one), which the trainer asserts.

>>> ds = arithmetic_dataset(64, max_operand=4)
>>> t = GRPOTrainer(ds)            # builds tokenizer/model/env/collector
>>> hist = t.train(50)             # hist["reward"] rises
>>> t.evaluate()                   # exact-match accuracy, greedy decode
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from ..analysis import hot_path
from ..collectors.llm import LLMCollector
from ..compile import abstract_like, get_program_registry
from ..data import ArrayDict
from ..data.llm.tokenizer import SimpleTokenizer
from ..envs.llm.chat import DatasetChatEnv
from ..envs.llm.datasets import QADataset
from ..envs.llm.reward import ExactMatchScorer, SumScorer, combine_scorers
from ..envs.llm.transforms import KLRewardTransform, PolicyVersion
from ..models import (
    TransformerConfig,
    TransformerLM,
    generate,
    token_log_probs,
    token_log_probs_with_aux,
)
from ..obs import DeviceMetrics
from ..obs.trace import carry_context, get_tracer
from ..objectives.llm.grpo import GRPOLoss
from ..parallel.mesh import AXIS_CONTEXT, AXIS_FSDP, DATA_AXES, data_sharding, fsdp_sharding
from ..resilience.faults import fault_point, get_injector
from ..resilience.guard import tree_where
from ..weight_update.schemes import DevicePutScheme, ShardedSyncScheme

__all__ = ["GRPOTrainer", "PipelinedGRPOTrainer", "RolloutPipeline"]


class GRPOTrainer:
    """Self-assembling GRPO trainer over a :class:`QADataset`.

    Args:
        dataset: (question, answer) pairs; tokenizer trains on its corpus.
        mesh: optional ``jax.sharding.Mesh``. With a "context" axis the
            training forward runs ring attention with the sequence sharded
            over it (the axis size must divide prompt+response length).
            With the ``(batch, fsdp)`` mesh
            (:func:`rl_tpu.parallel.make_fsdp_mesh`) the trainer instead
            FSDP-shards params and optimizer state per leaf
            (:func:`rl_tpu.parallel.fsdp_sharding`), shards rollout
            batches over every data axis, pins the donated update dispatch
            with explicit ``in_shardings``/``out_shardings``, and syncs
            weights through a :class:`ShardedSyncScheme` — only each
            device's shard ever moves.
        fsdp_min_size_mb: min-size cutoff (MB) below which a param leaf
            replicates instead of FSDP-sharding (only used on an ``fsdp``
            mesh). Tests pass 0.0 so tiny models actually shard.
        kl_coeff: KL(π‖π_ref) reward-shaping coefficient (π_ref = init).
        scorer: reward override; default exact-match + dense arithmetic
            credit against ``dataset.answers``.
        microbatch_size: gradient-accumulation microbatch rows (must
            divide ``num_prompts * group_repeats``). The update stays ONE
            donated dispatch — a ``lax.scan`` over microbatches with
            token-count-weighted accumulation, numerically equivalent to
            the full-batch update — so activation memory scales with the
            microbatch while the effective batch stays whole. ``None``
            (default) = single microbatch (the full batch).
        remat / remat_policy: per-block activation rematerialization on
            the TRAINING forward (``TransformerConfig.remat``) — pairs
            with small microbatches to fit long sequences.
        warmup: ``True`` AOT-compiles (or store-loads) the update program
            before construction returns; ``"background"`` does it on a
            thread overlapped with the caller's remaining setup
            (:meth:`aot_warmup` run for you; handle at
            ``self._warmup_handle``).
    """

    def __init__(
        self,
        dataset: QADataset,
        model_config: TransformerConfig | None = None,
        tokenizer: Any = None,
        scorer: Callable | None = None,
        mesh: Any = None,
        num_prompts: int = 4,
        group_repeats: int = 8,
        max_prompt_len: int = 16,
        max_new_tokens: int = 16,
        learning_rate: float = 1e-3,
        kl_coeff: float = 0.02,
        clip_epsilon: float = 0.2,
        temperature: float = 1.0,
        seed: int = 0,
        logger: Any = None,
        continuous_batching: bool = False,
        microbatch_size: int | None = None,
        remat: bool = False,
        remat_policy: str = "none",
        fsdp_min_size_mb: float = 4.0,
        warmup: bool | str = False,
    ):
        self.tokenizer = tokenizer or SimpleTokenizer(dataset.corpus())
        self.dataset = dataset
        self.logger = logger
        total_len = max_prompt_len + max_new_tokens
        if model_config is None:
            model_config = TransformerConfig(
                vocab_size=max(self.tokenizer.vocab_size, 64),
                d_model=128,
                n_layers=4,
                n_heads=8,
                d_ff=256,
                max_seq_len=total_len,
                dtype=jnp.float32,
            )
        B = num_prompts * group_repeats
        self.microbatch_size = microbatch_size
        if microbatch_size is not None and B % microbatch_size:
            raise ValueError(
                f"microbatch_size ({microbatch_size}) must divide the batch "
                f"(num_prompts * group_repeats = {B})"
            )
        # one param tree, two attention routes: KV-cache generation cannot
        # ring (decode steps are T=1); the teacher-forced training forward can
        self.gen_model = TransformerLM(model_config)
        train_cfg = model_config
        if remat:
            train_cfg = dataclasses.replace(
                train_cfg, remat=True, remat_policy=remat_policy
            )
        self._fsdp = mesh is not None and AXIS_FSDP in mesh.axis_names
        if mesh is not None and AXIS_CONTEXT in mesh.axis_names:
            ctx = mesh.shape[AXIS_CONTEXT]
            if total_len % ctx:
                raise ValueError(
                    f"context axis size ({ctx}) must divide prompt+response "
                    f"length {total_len} for ring attention"
                )
            train_cfg = dataclasses.replace(
                train_cfg, attention_impl="ring", mesh=mesh
            )
        self.train_model = TransformerLM(train_cfg)
        self.mesh = mesh

        key = jax.random.key(seed)
        self.params = self.gen_model.init(
            key, jnp.zeros((1, 4), jnp.int32)
        )["params"]
        self._mesh_replicated = None
        self._param_shardings = None
        self._batch_placement = None
        if self._fsdp:
            # (batch, fsdp) mesh: per-leaf FSDP placement (min-size cutoff,
            # replicated fallback) instead of the old blanket replicated
            # device_put; rollout batches split their leading dim over every
            # data axis. XLA derives the forward all-gathers and gradient
            # reduce-scatters from these placements alone.
            n_dp = int(np.prod([mesh.shape[a] for a in DATA_AXES if a in mesh.axis_names]))
            if B % n_dp:
                raise ValueError(
                    f"batch (num_prompts * group_repeats = {B}) must be "
                    f"divisible by the mesh's data-parallel extent ({n_dp})"
                )
            self._param_shardings = fsdp_sharding(
                self.params, mesh, min_size_mbytes=fsdp_min_size_mb
            )
            self.params = jax.tree.map(jax.device_put, self.params, self._param_shardings)
            self._batch_placement = data_sharding(mesh)
        elif mesh is not None:
            # the ring forward is a shard_map over the whole mesh: params and
            # batch must live on the mesh's device set (replicated; the
            # sequence axis is split inside ring_attention)
            self._mesh_replicated = NamedSharding(mesh, PartitionSpec())
            self.params = jax.device_put(self.params, self._mesh_replicated)
            self._batch_placement = self._mesh_replicated
        self.ref_params = jax.tree.map(jnp.copy, self.params)

        scorer = scorer or combine_scorers(
            ExactMatchScorer(dataset.answers), SumScorer(dataset.answers),
            weights=[1.0, 0.5],
        )
        self.env = DatasetChatEnv(
            dataset.prompts,
            self.tokenizer,
            reward_fn=scorer,
            group_repeats=group_repeats,
            max_prompt_len=max_prompt_len,
            seed=seed,
        )
        if self._fsdp:
            # shard-local publication: push re-places onto the SAME
            # per-leaf shardings the update emits, so it aliases buffers —
            # no full-replica gather anywhere on the sync path
            self.scheme = ShardedSyncScheme(self._param_shardings)
        else:
            self.scheme = DevicePutScheme(jax.devices()[0])
        self.scheme.push(self.params)
        self.policy_version = PolicyVersion()
        kl = KLRewardTransform(coeff=kl_coeff)

        def reward_transform(rewards, arrays):
            return self.policy_version(kl(rewards, arrays), arrays)

        self.collector = LLMCollector(
            self.env,
            self.gen_model,
            num_prompts=num_prompts,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            eos_id=self.tokenizer.eos_token_id,
            ref_params=self.ref_params,
            weight_scheme=self.scheme,
            reward_transform=reward_transform,
            continuous_batching=continuous_batching,
            engine_params_sharding=self._param_shardings,
        )
        # MoE configs score through the aux-returning path so the Switch
        # load-balancing term trains by default (routing collapses without it)
        _score = (
            token_log_probs_with_aux
            if getattr(self.train_model.cfg, "moe_experts", 0)
            else token_log_probs
        )
        self.loss = GRPOLoss(
            lambda p, b: _score(
                self.train_model, p, b["tokens"], b["attention_mask"]
            ),
            clip_epsilon=clip_epsilon,
            kl_coeff=0.0,  # KL lives in the shaped reward, not the loss
        )
        self.opt = optax.adam(learning_rate)
        self.opt_state = self.opt.init(self.params)
        self._opt_shardings = None
        if self._fsdp:
            # adam moments mirror the param shapes, so the same per-leaf
            # rule lands them on the param specs; step counters replicate
            self._opt_shardings = fsdp_sharding(
                self.opt_state, mesh, min_size_mbytes=fsdp_min_size_mb
            )
            self.opt_state = jax.tree.map(
                jax.device_put, self.opt_state, self._opt_shardings
            )
        self._key = jax.random.key(seed + 1)

        # step metrics accumulate ON DEVICE inside the update program and
        # are drained lagged-one-dispatch (AsyncOffPolicyTrainer pattern):
        # step() never blocks on the update it just dispatched
        self._dm_spec = DeviceMetrics(
            counters=("updates", "tokens", "bad_steps"),
            gauges=("loss", "reward", "kl_approx"),
        )
        self._dm = self._dm_spec.init()
        self._pending_dm: dict | None = None
        # cached device zero for the chaos poison argument: keeps the
        # injector-armed-but-idle path on ONE jit trace with no per-step
        # host->device transfer
        self._poison_zero: jax.Array | None = None

        # donate the rotating optimizer state, NOT the params: the weight
        # scheme (and a pipelined generator thread pulling from it) may
        # alias the same device buffers a same-device device_put returns
        # both update programs go through the ProgramRegistry (rlint R006):
        # named executable tables + aot_warmup() + the persistent store,
        # so a restarted worker reloads instead of re-lowering
        self._registry = get_program_registry()
        self._fingerprint = repr((
            type(self).__name__, train_cfg, self.microbatch_size,
            learning_rate, clip_epsilon, self._fsdp,
            None if mesh is None else sorted(mesh.shape.items()),
        ))
        if self._fsdp:
            # explicit in/out shardings pin the donated dispatch to the FSDP
            # layout: XLA overlaps the param all-gathers / grad
            # reduce-scatters with compute instead of inserting resharding
            # copies at the jit boundary. The fixed arity means every call
            # passes the poison scalar (the cached device zero when the
            # chaos injector is idle or absent).
            _repl = NamedSharding(mesh, PartitionSpec())
            self._update = self._registry.register(
                "grpo.update",
                self._update_impl,
                fingerprint=self._fingerprint,
                donate_argnums=(1,),
                in_shardings=(
                    self._param_shardings,
                    self._opt_shardings,
                    self._batch_placement,
                    _repl,
                    _repl,
                ),
                out_shardings=(self._param_shardings, self._opt_shardings, _repl),
            )
            self._poison_zero = jax.device_put(jnp.zeros((), jnp.float32), _repl)
        else:
            self._update = self._registry.register(
                "grpo.update",
                self._update_impl,
                fingerprint=self._fingerprint,
                donate_argnums=(1,),
            )
        self._eval_gen = self._registry.register(
            "grpo.eval_gen",
            lambda p, t, m, k: generate(
                self.gen_model, p, t, m, k,
                max_new_tokens=max_new_tokens,
                eos_id=self.tokenizer.eos_token_id,
                greedy=True,
            ),
            fingerprint=repr((model_config, max_new_tokens,
                              self.tokenizer.eos_token_id)),
        )
        self._B, self._T = B, total_len
        self.history: dict[str, list[float]] = {"reward": [], "loss": []}
        # warmup=True compiles the update before __init__ returns;
        # "background" overlaps it with collector/env setup the caller
        # still has to do — join via the returned handle's .result() or
        # just let the first step() hit the warmed table
        self._warmup_handle = None
        if warmup == "background":
            self._warmup_handle = self.aot_warmup(background=True)
        elif warmup:
            self.aot_warmup()

    def aot_warmup(self, *, background: bool = False):
        """Pre-compile (or reload from the executable store) the update
        program for the exact batch the collector produces, so the first
        ``step()`` dispatches instead of lowering. Returns the registry's
        per-program ``[(source, seconds)]`` report, or a
        :class:`~rl_tpu.compile.WarmupHandle` when backgrounded."""
        B, T = self._B, self._T
        f32, i32 = jnp.float32, jnp.int32
        bt = lambda dt: jax.ShapeDtypeStruct((B, T), dt)  # noqa: E731
        batch = ArrayDict(
            advantage=jax.ShapeDtypeStruct((B,), f32),
            reward=jax.ShapeDtypeStruct((B,), f32),
            tokens=bt(i32),
            attention_mask=bt(f32),
            assistant_mask=bt(jnp.bool_),
            sample_log_prob=bt(f32),
            group_id=jax.ShapeDtypeStruct((B,), i32),
            policy_version=jax.ShapeDtypeStruct((B,), i32),
            ref_log_prob=bt(f32),
        )
        if self._batch_placement is not None:
            batch = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=self._batch_placement
                ),
                batch,
            )
        params_abs = abstract_like(self.params)
        opt_abs = abstract_like(self.opt_state)
        dm_abs = abstract_like(self._dm)
        if get_injector() is None and not self._fsdp:
            self._update.add_signature(params_abs, opt_abs, batch, dm_abs)
        else:
            pz = abstract_like(
                self._poison_zero
                if self._poison_zero is not None
                else jnp.zeros((), jnp.float32)
            )
            self._update.add_signature(params_abs, opt_abs, batch, dm_abs, pz)
        return self._registry.aot_warmup(
            programs=[self._update], background=background
        )

    # -- the donated, microbatched update program ------------------------

    def _update_impl(self, params, opt_state, batch, dm, poison=None):
        """One dispatch: gradient-accumulation ``lax.scan`` over
        microbatches, optimizer update, on-device metrics. Microbatch
        gradients are weighted by ``GRPOLoss.microbatch_weight`` (the
        assistant-token count) so the accumulated gradient equals the
        full-batch gradient exactly — the loss normalizes per token, and
        the per-microbatch denominators cancel against the weights.

        A finite guard gates the writes: a non-finite loss or gradient
        norm turns the step into an in-program no-op (old params/opt_state
        selected, ``bad_steps`` counter bumped) with no extra host sync.
        ``poison`` is the chaos injector's f32 scalar (NaN on a poisoned
        step, a cached device zero otherwise) added to loss and grads."""
        B = batch["tokens"].shape[0]
        mbs = self.microbatch_size or B
        n_mb = B // mbs

        def loss_and_grad(mb):
            return jax.value_and_grad(
                lambda p: self.loss(p, mb), has_aux=True
            )(params)

        if n_mb == 1:
            (v, m), g = loss_and_grad(batch)
            kl = m["kl_approx"] if "kl_approx" in m else jnp.zeros(())
        else:
            xs = jax.tree.map(
                lambda x: x.reshape((n_mb, mbs) + x.shape[1:]), batch
            )

            def body(carry, mb):
                gsum, vsum, klsum, wsum = carry
                w = self.loss.microbatch_weight(mb)
                (v, m), g = loss_and_grad(mb)
                kl = m["kl_approx"] if "kl_approx" in m else jnp.zeros(())
                gsum = jax.tree.map(lambda a, b: a + w * b, gsum, g)
                return (gsum, vsum + w * v, klsum + w * kl, wsum + w), None

            zero_g = jax.tree.map(jnp.zeros_like, params)
            zero = jnp.zeros((), jnp.float32)
            (gsum, vsum, klsum, wsum), _ = jax.lax.scan(
                body, (zero_g, zero, zero, zero), xs
            )
            wsum = jnp.maximum(wsum, 1e-8)
            g = jax.tree.map(lambda a: a / wsum, gsum)
            v = vsum / wsum
            kl = klsum / wsum

        if poison is not None:
            v = v + poison
            g = jax.tree.map(lambda a: a + poison, g)

        ok = jnp.isfinite(v) & jnp.isfinite(optax.global_norm(g))
        upd, new_opt_state = self.opt.update(g, opt_state)
        new_params = optax.apply_updates(params, upd)
        # jnp.where SELECTS, so a NaN in the rejected branch cannot leak
        params = tree_where(ok, new_params, params)
        opt_state = tree_where(ok, new_opt_state, opt_state)
        okf = ok.astype(jnp.float32)

        spec = self._dm_spec
        dm = spec.inc(dm, "updates", okf)
        dm = spec.inc(dm, "bad_steps", 1.0 - okf)
        dm = spec.inc(
            dm, "tokens", jnp.sum(batch["assistant_mask"].astype(jnp.float32))
        )
        dm = spec.set_gauge(dm, "loss", jnp.where(ok, v, 0.0))
        dm = spec.set_gauge(dm, "reward", jnp.mean(batch["reward"]))
        dm = spec.set_gauge(dm, "kl_approx", jnp.where(ok, kl, 0.0))
        return params, opt_state, dm

    # -- step / train ----------------------------------------------------

    def _consume(self, batch: ArrayDict) -> dict[str, float]:
        """Update on a collected batch, publish weights, drain metrics."""
        tracer = get_tracer()
        inj = get_injector()
        with tracer.span("grpo.update"):
            if inj is None and not self._fsdp:
                self.params, self.opt_state, self._dm = self._update(
                    self.params, self.opt_state, batch, self._dm
                )
            else:
                p = inj.poison("grpo.update") if inj is not None else 0.0
                if self._poison_zero is None:
                    self._poison_zero = jnp.zeros((), jnp.float32)
                pv = self._poison_zero if p == 0.0 else jnp.asarray(p, jnp.float32)
                self.params, self.opt_state, self._dm = self._update(
                    self.params, self.opt_state, batch, self._dm, pv
                )
        with tracer.span("grpo.push"):
            self.scheme.push(self.params)  # non-blocking dispatch
            self.policy_version.bump()
        with tracer.span("grpo.drain_metrics.wait"):
            out = self._drain_metrics()
        self.history["reward"].append(out["reward"])
        self.history["loss"].append(out["loss"])
        return out

    def _drain_metrics(self) -> dict[str, float]:
        """Lagged-one-dispatch drain: start the async device→host copy for
        THIS update's metrics, materialize the PREVIOUS update's (whose
        copy landed while we collected the batch in between). The first
        step drains its own dispatch — it blocks on compile anyway. Step
        metrics therefore lag one step from the second step on."""
        DeviceMetrics.drain_async(self._dm)
        landed = self._pending_dm if self._pending_dm is not None else self._dm
        self._pending_dm = self._dm
        flat = self._dm_spec.to_flat(DeviceMetrics.drain(landed))
        return {
            "reward": flat["reward"],
            "loss": flat["loss"],
            "kl_approx": flat["kl_approx"],
            "bad_steps": flat["bad_steps"],
        }

    def metrics_snapshot(self) -> dict:
        """Host view of the on-device step metrics (and the serving
        engine's, when rollouts run through it). Reads the already-landed
        lagged state — never blocks an in-flight update."""
        landed = self._pending_dm if self._pending_dm is not None else self._dm
        out = dict(self._dm_spec.to_flat(DeviceMetrics.drain(landed)))
        eng = getattr(self.collector, "_engine", None)
        if eng is not None:
            out["engine"] = eng.metrics_snapshot()
        return out

    @hot_path(reason="per-iteration GRPO train step")
    def step(self) -> dict[str, float]:
        """collect → update → push weights. Returns step metrics."""
        tracer = get_tracer()
        with tracer.span("grpo.step", {"version": self.scheme.version}):
            self._key, k = jax.random.split(self._key)
            with tracer.span("grpo.collect"):
                batch = self.collector.collect(None, k)  # scheme snapshot
            if self._batch_placement is not None:
                with tracer.span("grpo.place"):
                    batch = jax.device_put(batch, self._batch_placement)
            return self._consume(batch)

    def train(
        self,
        steps: int,
        log_interval: int = 10,
        preemption: Any = None,
        emergency: Any = None,
        guard: Any = None,
        start_step: int = 0,
    ) -> dict[str, list[float]]:
        """Run ``steps`` training steps.

        Resilience hooks (all optional): ``preemption`` is a
        :class:`~rl_tpu.trainers.resilience.PreemptionHandler` — when its
        flag raises, the loop drains in-flight work and writes an
        ``emergency`` checkpoint (:class:`rl_tpu.resilience.EmergencyCheckpointer`)
        before returning, so :meth:`emergency_restore` + ``train(...,
        start_step=resumed)`` reproduces the uninterrupted run exactly.
        ``guard`` is a :class:`rl_tpu.resilience.LastGoodState` fed the
        lagged ``bad_steps`` total each step; a rollback replaces
        params/opt_state with the last good snapshot and re-pushes weights.
        """
        for i in range(start_step, start_step + steps):
            fault_point("trainer.preempt")  # chaos site (synthetic preemption)
            if preemption is not None and preemption.preempted:
                if emergency is not None:
                    self.emergency_save(emergency, i)
                break
            out = self.step()
            if guard is not None:
                restored = guard.observe(
                    i, out.get("bad_steps", 0.0), self.params, self.opt_state
                )
                if restored is not None:
                    self.params, self.opt_state, _version = restored
                    self.scheme.push(self.params)
            if self.logger is not None and i % log_interval == 0:
                self.logger.log_scalars(
                    {f"grpo/{k}": v for k, v in out.items()}, step=i
                )
        return self.history

    # -- emergency checkpoints -------------------------------------------

    def _drain_for_checkpoint(self) -> None:
        """Quiesce background work so the saved state is consistent; the
        sequential trainer has none (the pipelined override closes its
        rollout pipeline)."""

    def emergency_save(self, emergency: Any, step: int) -> str:
        """Drain pipelines, block on the in-flight dispatch, write a full
        emergency checkpoint (arrays + meta) for exact resume."""
        self._drain_for_checkpoint()
        jax.block_until_ready(self.params)
        arrays = {
            "params": self.params,
            "opt_state": self.opt_state,
            "key": self._key,
            "dm": self._dm,
        }
        meta = {
            "step": int(step),
            "history": {
                k: [float(x) for x in v] for k, v in self.history.items()
            },
            # the chat env draws prompts from its own numpy Generator —
            # without this state, resumed rollouts sample different prompts
            "env_rng": self.env._rng.bit_generator.state,
        }
        return emergency.save(step, arrays, meta)

    def emergency_restore(self, emergency: Any, step: int | None = None) -> int:
        """Load the latest (or given) emergency checkpoint into this
        trainer; returns the step to resume from (pass as ``start_step``)."""
        template = {
            "params": self.params,
            "opt_state": self.opt_state,
            "key": self._key,
            "dm": self._dm,
        }
        arrays, meta, step = emergency.restore(template, step)
        self.params = arrays["params"]
        self.opt_state = arrays["opt_state"]
        self._key = arrays["key"]
        self._dm = arrays["dm"]
        self._pending_dm = None
        if self._fsdp:
            self.params = jax.tree.map(
                jax.device_put, self.params, self._param_shardings
            )
            self.opt_state = jax.tree.map(
                jax.device_put, self.opt_state, self._opt_shardings
            )
        elif self._mesh_replicated is not None:
            self.params = jax.device_put(self.params, self._mesh_replicated)
        self.history = {k: list(v) for k, v in meta.get("history", {}).items()}
        if "env_rng" in meta:
            self.env._rng.bit_generator.state = meta["env_rng"]
        self.scheme.push(self.params)
        # warm restart: start materializing the update executable now (a
        # restarted process loads it from the persistent store in
        # milliseconds), overlapped with whatever host setup remains
        # before the first post-restore step
        self.aot_warmup(background=True)
        return int(meta.get("step", step))

    def evaluate(self, num_prompts: int = 32, key: jax.Array | None = None) -> float:
        """Greedy-decode exact-match accuracy over dataset prompts."""
        state = self.env.reset(self.dataset.prompts[:num_prompts])
        out = self._eval_gen(
            self.scheme.pull(),  # generation-placed copy (dev 0), not the
            # mesh-replicated training params
            jnp.asarray(state["tokens"]),
            jnp.asarray(state["attention_mask"], jnp.float32),
            key if key is not None else jax.random.key(0),
        )
        em = ExactMatchScorer(self.dataset.answers, partial=0.0)
        hits = 0.0
        for i, h in enumerate(state["histories"]):
            toks = np.asarray(out.response_tokens[i])[np.asarray(out.response_mask[i], bool)]
            text = self.tokenizer.decode(toks.tolist())
            hits += em(h.append("assistant", text), toks)
        return hits / len(state["histories"])


class RolloutPipeline:
    """Background rollout producer with a BOUNDED staleness guarantee.

    A daemon thread loops: atomically snapshot ``(params, version)`` from
    the weight scheme (``pull_versioned``), run ``collect_fn(params,
    key)``, and put ``(batch, version)`` on a bounded queue. The consumer
    (the learner) pops batches, updates, and pushes new weights.

    Staleness bound: a ticket semaphore (initially ``max_pending``)
    gates every snapshot; the consumer releases one ticket when it POPS
    a batch. A bounded queue alone is NOT enough — the blocked ``put``
    unblocks the instant the consumer pops, letting the producer
    snapshot again before the learner's update lands, and that batch
    would trail by two versions by the time it is consumed. With
    tickets, generation k+1 starts only after batch k is popped, which
    itself happens only after update k−1 pushed version k — so the
    snapshot is ≥ version k and the batch is consumed at version k+1:
    staleness ≤ 1 (generalizing, ≤ ``max_pending``). Popping releases
    the ticket BEFORE the update runs, so generation k+1 still overlaps
    update k — that is the pipeline. The key stream splits identically
    to the sequential trainer's, so the FIRST pipelined batch is
    bit-identical to the first sequential batch from the same seed.
    """

    def __init__(
        self,
        scheme,
        collect_fn: Callable[[Any, jax.Array], Any],
        key: jax.Array,
        max_pending: int = 1,
        supervisor: Any = None,
    ):
        self.scheme = scheme
        self.collect_fn = collect_fn
        self.max_pending = max_pending
        self._key = key
        self._q: queue.Queue = queue.Queue(maxsize=max_pending)
        self._tickets = threading.Semaphore(max_pending)
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self._thread: threading.Thread | None = None
        # optional rl_tpu.resilience.Supervisor: producer crashes restart
        # the loop (the key stream and ticket pool survive on the instance)
        self._supervisor = supervisor
        self._child: Any = None

    def start(self) -> "RolloutPipeline":
        if self._thread is not None or self._child is not None:
            return self
        if self._supervisor is not None:
            self._child = self._supervisor.spawn(
                "grpo-rollout", self._produce, on_giveup=self._on_giveup
            )
        else:
            # unsupervised path: carry the starter's TraceContext onto the
            # producer thread (the supervised path gets this from spawn())
            self._thread = threading.Thread(
                target=carry_context(self._run), name="grpo-rollout", daemon=True
            )
            self._thread.start()
        return self

    def _on_giveup(self, exc: BaseException) -> None:
        self._error = exc

    @property
    def running(self) -> bool:
        if self._child is not None:
            return self._child.is_alive()
        return self._thread is not None and self._thread.is_alive()

    def _run(self):
        try:
            self._produce()
        except BaseException as e:  # surfaced on the consumer's next get
            self._error = e

    @hot_path(reason="pipelined rollout producer thread")
    def _produce(self):
        from ..resilience.faults import fault_point

        while not self._stop.is_set():
            fault_point("grpo.rollout")  # chaos site, before the ticket
            if not self._tickets.acquire(timeout=0.05):
                continue
            try:
                self._key, k = jax.random.split(self._key)
                params, version = self.scheme.pull_versioned()
                batch = self.collect_fn(params, k)
                self._put((batch, version))
            except BaseException:
                # a crash after the acquire must return the ticket, or a
                # supervised restart would leak it and starve the pipeline
                self._tickets.release()
                raise

    def _put(self, item):
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return
            except queue.Full:
                continue

    def get(self, timeout: float = 120.0) -> tuple[Any, int]:
        """Pop the next ``(batch, version_generated_at)``. Re-raises any
        producer-thread error."""
        deadline = timeout
        while True:
            if self._error is not None:
                raise RuntimeError("rollout pipeline producer failed") from self._error
            try:
                item = self._q.get(timeout=min(0.1, deadline))
                # ticket back BEFORE the caller's update: generation for
                # the next batch overlaps the update on this one
                self._tickets.release()
                return item
            except queue.Empty:
                deadline -= 0.1
                if deadline <= 0:
                    raise TimeoutError(
                        f"no rollout batch within {timeout}s "
                        f"(producer alive: {self.running})"
                    ) from None

    def stop(self):
        self._stop.set()
        # unblock a producer stuck on a full queue, then join
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        if self._child is not None:
            self._child.stop(timeout=10.0)
            self._child = None
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None


class PipelinedGRPOTrainer(GRPOTrainer):
    """GRPO with generation/training overlap (off-by-one staleness).

    While the learner runs the update for step k, the background
    :class:`RolloutPipeline` already generates batch k+1 against the
    previous pushed weights. Every consumed batch's ``policy_version``
    (the scheme version its weights were pulled at) is asserted to be
    ≥ the trainer's current version − ``max_pending`` — the off-by-one
    invariant for the default depth of 1. Rollouts default to the
    continuous-batching engine (EOS'd rows free their slots; completed
    prompt groups are reward-scored first-come while others decode).

    Call :meth:`close` (or use as a context manager) to stop the
    generator thread; it is a daemon, so leaking it cannot hang exit.
    """

    def __init__(self, dataset, *args, max_pending: int = 1, supervisor: Any = None, **kw):
        kw.setdefault("continuous_batching", True)
        super().__init__(dataset, *args, **kw)
        self.max_pending = max_pending
        self.supervisor = supervisor
        self.staleness_history: list[int] = []
        self._pipeline: RolloutPipeline | None = None

    def _ensure_pipeline(self) -> RolloutPipeline:
        if self._pipeline is None:
            self._pipeline = RolloutPipeline(
                self.scheme,
                lambda params, k: self.collector.collect(params, k),
                self._key,
                max_pending=self.max_pending,
                supervisor=self.supervisor,
            ).start()
        return self._pipeline

    def _drain_for_checkpoint(self) -> None:
        # stop the producer and throw away its in-flight batch: the saved
        # state then needs no queue contents to be consistent — resume
        # regenerates from the checkpointed key/weights. Adopt the
        # producer's key position so resumed rollouts continue the stream
        # instead of replaying consumed keys.
        if self._pipeline is not None:
            self._key = self._pipeline._key
        self.close()

    @hot_path(reason="pipelined GRPO consumer step")
    def step(self) -> dict[str, float]:
        with get_tracer().span("grpo.step", {"version": self.scheme.version}):
            batch, version = self._ensure_pipeline().get()
            staleness = self.scheme.version - version
            self.staleness_history.append(int(staleness))
            if staleness > self.max_pending:
                raise RuntimeError(
                    f"staleness invariant violated: batch generated at version "
                    f"{version}, trainer at {self.scheme.version} "
                    f"(bound {self.max_pending})"
                )
            # restamp with the version the GENERATOR snapshotted — the
            # PolicyVersion transform stamped inside collect, racing the
            # learner's bump; the snapshot is the authoritative value
            B = batch["reward"].shape[0]
            batch = batch.set(
                "policy_version", np.full(B, version, np.int32)
            )
            if self._batch_placement is not None:
                batch = jax.device_put(batch, self._batch_placement)
            out = self._consume(batch)
            out["staleness"] = float(staleness)
            return out

    def close(self):
        if self._pipeline is not None:
            self._pipeline.stop()
            self._pipeline = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

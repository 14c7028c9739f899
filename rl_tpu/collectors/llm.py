"""LLM collector: chat env x jitted generation -> GRPO training batches.

Redesign of the reference's ``LLMCollector`` (reference:
torchrl/collectors/llm/base.py:26 — rollout = wrapper.generate() batch into a
ChatEnv) without the external engine: generation is the jitted KV-cache scan
(rl_tpu/models/generate.py) over the SAME params the trainer optimizes
(SharedProgramScheme — zero-copy weight "sync"), or over a scheme-provided
snapshot for decoupled rollout.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..analysis import hot_path
from ..data import ArrayDict
from ..envs.llm.chat import DatasetChatEnv
from ..models import generate
from ..objectives.llm import mc_advantage
from ..obs.trace import get_tracer

__all__ = ["LLMCollector"]


class LLMCollector:
    """Collect GRPO batches: sample prompt groups, generate G responses per
    prompt, score, compute group-relative advantages."""

    def __init__(
        self,
        env: DatasetChatEnv,
        model: Any,
        num_prompts: int = 8,
        max_new_tokens: int = 64,
        temperature: float = 1.0,
        eos_id: int | None = None,
        ref_params: Any = None,
        weight_scheme: Any = None,
        reward_transform: Callable | None = None,
        continuous_batching: bool = False,
        engine_slots: int | None = None,
        engine_block_size: int = 16,
        engine_decode_chunk: int | str = 1,
        engine_params_sharding: Any = None,
        engine_prefix_cache: bool = False,
        fleet: Any = None,
        fleet_timeout_s: float = 120.0,
        fleet_poll_s: float = 0.01,
    ):
        self.env = env
        self.model = model
        self.num_prompts = num_prompts
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.eos_id = eos_id
        self.ref_params = ref_params
        self.weight_scheme = weight_scheme
        # continuous batching: responses come from the paged-KV engine
        # (slot admission mid-batch) instead of one fixed-batch generate —
        # rows that hit eos early stop paying decode steps (the vLLM-side
        # behavior the reference gets from its AsyncVLLM backend)
        self.continuous_batching = continuous_batching
        self.engine_slots = engine_slots
        self.engine_block_size = engine_block_size
        # 1 (default) keeps sampling key-deterministic vs the fixed-batch
        # path; "auto" lets the engine tune its chunk from measured chunk
        # wall-time vs sync overhead (throughput over reproducibility)
        self.engine_decode_chunk = engine_decode_chunk
        # shardings the engine pins pushed params to (FSDP rollouts: the
        # sharded trainer passes its per-leaf param placements through)
        self.engine_params_sharding = engine_params_sharding
        # prefix-aware KV tier (rl_tpu.kvmem): a GRPO group's G rollouts
        # share ONE prompt, so every response after the group's first
        # prefills only the last prompt position via the radix tree's
        # exact-match fast path. Off by default to keep the engine path
        # bit-identical with prior behavior; flip on for shared-prompt
        # rollout workloads.
        self.engine_prefix_cache = engine_prefix_cache
        # batch-lane tenancy (ISSUE 19): instead of a PRIVATE engine, the
        # collector rides an existing ServingFleet's "batch" lane —
        # interactive traffic holds the SLO lane strictly ahead, rollouts
        # harvest whatever capacity is idle. Admission sheds
        # (ServiceSaturated) and post-admission sheds both back off and
        # resubmit: a slack tenant yields, never competes.
        self.fleet = fleet
        self.fleet_timeout_s = fleet_timeout_s
        self.fleet_poll_s = fleet_poll_s
        self._engine = None
        # (rewards, batch_arrays) -> rewards, applied BEFORE group advantages
        # (KLRewardTransform / PolicyVersion — reference envs/llm/transforms/)
        self.reward_transform = reward_transform

        self._gen = jax.jit(
            lambda params, toks, mask, key: generate(
                model,
                params,
                toks,
                mask,
                key,
                max_new_tokens=max_new_tokens,
                temperature=temperature,
                eos_id=eos_id,
            )
        )
        if ref_params is not None:
            from ..models import token_log_probs

            # the reference weights are an ARGUMENT: closed over, they are
            # baked into the program as constants (a 763 MB executable at
            # 110M, seen on the chip — slow to compile, too big to cache)
            self._ref_lp = jax.jit(
                lambda params, toks, mask: token_log_probs(model, params, toks, mask)
            )
            # reference weights placed on a mesh (ring / FSDP trainers):
            # scoring runs where they live, on inputs replicated over it
            sh = jax.tree.leaves(ref_params)[0].sharding
            self._ref_replicated = (
                NamedSharding(sh.mesh, PartitionSpec())
                if isinstance(sh, NamedSharding)
                else None
            )

    def _score_ref(self, toks, mask):
        """Reference-policy log-probs of a rollout batch, returned where
        the batch lives (device-to-device moves only)."""
        if self._ref_replicated is None:
            return self._ref_lp(self.ref_params, toks, mask)
        lp = self._ref_lp(
            self.ref_params, *jax.device_put((toks, mask), self._ref_replicated)
        )
        return jax.device_put(lp, toks.sharding)

    @hot_path(reason="drives the engine decode loop per rollout batch")
    def _engine_generate(self, params, toks, pmask, key, on_row_done=None):
        """Continuous-batching rollout shaped like ``generate``'s output:
        the G requests stream through engine slots; early-eos rows free
        their slot (and KV blocks) immediately.

        ``on_row_done(row)`` fires as each request's tokens land on the
        host — its row of the shared resp/rlp/rmask buffers is final at
        that point — so callers can consume completions first-come
        (score a prompt group's rewards while other groups still decode;
        the ``AsyncHostCollector`` harvest pattern)."""
        from ..models.generate import GenerateOutput
        from ..models.serving import ContinuousBatchingEngine

        G, P = toks.shape
        if self._engine is None:
            bucket = max(16, 1 << (P - 1).bit_length())
            slots = self.engine_slots or min(G, 8)
            self._engine = ContinuousBatchingEngine(
                self.model,
                params,
                n_slots=slots,
                block_size=self.engine_block_size,
                n_blocks=slots
                * (-(-self.model.cfg.max_seq_len // self.engine_block_size))
                + 1,
                prompt_buckets=(bucket,),
                eos_id=self.eos_id,
                temperature=self.temperature,
                decode_chunk=self.engine_decode_chunk,
                params_sharding=self.engine_params_sharding,
                prefix_cache=self.engine_prefix_cache,
            )
        eng = self._engine
        eng.params = params  # fresh policy weights each collect
        # the per-call key drives sampling (key-deterministic, like the
        # fixed-batch path): fold it into the engine's stream
        eng._key = jax.random.fold_in(key, 0)
        # env batches arrive host-side; np.asarray is a no-op there. A
        # device array here would mean a blocking d2h of data the caller
        # just uploaded — keep prompts on the host until the final concat.
        toks_np = np.asarray(toks)
        mask_np = np.asarray(pmask) > 0
        rids = [
            eng.submit(toks_np[g][mask_np[g]], self.max_new_tokens)
            for g in range(G)
        ]
        rid_row = {rid: g for g, rid in enumerate(rids)}
        N = self.max_new_tokens
        resp = np.zeros((G, N), np.int32)
        rlp = np.zeros((G, N), np.float32)
        rmask = np.zeros((G, N), bool)

        def _absorb(done):
            for rid, f in done.items():
                g = rid_row.pop(rid)
                n = len(f.tokens)
                resp[g, :n] = f.tokens
                rlp[g, :n] = f.log_probs
                # every produced token INCLUDING a terminal eos is real —
                # generate()'s response_mask convention (valid = was_alive;
                # the policy must see gradient on the stop decision)
                rmask[g, :n] = True
                if on_row_done is not None:
                    on_row_done(g, resp, rmask)

        # drive the engine incrementally, consuming completions while the
        # remaining slots keep decoding (run() would block to the end)
        while eng.step():
            _absorb(eng.harvest())
        _absorb(eng.harvest())
        if rid_row:
            raise RuntimeError(f"engine lost requests: {sorted(rid_row)}")
        full = jnp.concatenate([jnp.asarray(toks_np), jnp.asarray(resp)], axis=1)
        full_mask = jnp.concatenate(
            [jnp.asarray(mask_np), jnp.asarray(rmask)], axis=1
        )
        return GenerateOutput(
            tokens=full,
            response_tokens=jnp.asarray(resp),
            response_mask=jnp.asarray(rmask),
            response_log_probs=jnp.asarray(rlp),
            full_mask=full_mask,
        )

    @hot_path(reason="drives the fleet batch lane per rollout batch")
    def _fleet_generate(self, params, toks, pmask, key, on_row_done=None):
        """Batch-lane tenant rollout: the G requests ride an existing
        :class:`~rl_tpu.models.ServingFleet`'s ``batch`` lane, filling
        whatever capacity the interactive SLO lane leaves idle. Weight
        push is the fleet's rolling per-member swap (serving never
        globally stalls); sheds — admission-time saturation AND
        post-admission ``ShedRequest`` — back off and resubmit until the
        deadline. Results come through :meth:`ServingFleet.poll`, which
        never drains another tenant's rows. The per-call ``key`` is
        unused here: sampling streams belong to the member engines."""
        import time as _time

        from ..models.fleet import ShedRequest
        from ..models.generate import GenerateOutput
        from ..models.serving import ServiceSaturated

        fleet = self.fleet
        if params is not None:
            fleet.push_params(params)
        G, P = toks.shape
        toks_np = np.asarray(toks)
        mask_np = np.asarray(pmask) > 0
        N = self.max_new_tokens
        resp = np.zeros((G, N), np.int32)
        rlp = np.zeros((G, N), np.float32)
        rmask = np.zeros((G, N), bool)
        pending_rows = list(range(G))  # not yet admitted (or re-shed)
        outstanding: dict[int, int] = {}  # frid -> row
        deadline = _time.monotonic() + self.fleet_timeout_s
        while pending_rows or outstanding:
            still: list[int] = []
            for g in pending_rows:
                try:
                    frid = fleet.submit(
                        toks_np[g][mask_np[g]], N, lane="batch")
                    outstanding[frid] = g
                except ServiceSaturated:
                    still.append(g)  # the SLO lane owns the pool right now
            pending_rows = still
            for frid, res in fleet.poll(list(outstanding)).items():
                g = outstanding.pop(frid)
                if isinstance(res, ShedRequest):
                    pending_rows.append(g)  # bounded by the deadline below
                    continue
                n = len(res.tokens)
                resp[g, :n] = res.tokens
                rlp[g, :n] = res.log_probs
                rmask[g, :n] = True
                if on_row_done is not None:
                    on_row_done(g, resp, rmask)
            if pending_rows or outstanding:
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        f"fleet batch lane: {len(pending_rows)} unadmitted + "
                        f"{len(outstanding)} outstanding rollout rows after "
                        f"{self.fleet_timeout_s}s"
                    )
                _time.sleep(self.fleet_poll_s)
        full = jnp.concatenate(
            [jnp.asarray(toks_np), jnp.asarray(resp)], axis=1)
        full_mask = jnp.concatenate(
            [jnp.asarray(mask_np), jnp.asarray(rmask)], axis=1)
        return GenerateOutput(
            tokens=full,
            response_tokens=jnp.asarray(resp),
            response_mask=jnp.asarray(rmask),
            response_log_probs=jnp.asarray(rlp),
            full_mask=full_mask,
        )

    def _engine_collect(self, params, toks, pmask, key, state, group_ids):
        """Engine rollout with FIRST-COME group scoring: the moment a
        prompt group's last response lands, its rewards are computed on
        the host while the other groups' slots keep decoding — reward
        work overlaps device decode instead of serializing after it.
        Falls back to end-of-rollout scoring when the env has no
        ``score_rows``."""
        can_score = hasattr(self.env, "score_rows")
        G = toks.shape[0]
        rewards = np.zeros(G, np.float32)
        group_rows: dict[int, list[int]] = {}
        for row, g in enumerate(np.asarray(group_ids)):
            group_rows.setdefault(int(g), []).append(row)
        remaining = {g: len(rows) for g, rows in group_rows.items()}

        def on_row_done(row, resp, rmask):
            if not can_score:
                return
            g = int(group_ids[row])
            remaining[g] -= 1
            if remaining[g] == 0:
                rows = group_rows[g]
                with get_tracer().span("collector.reward", {"rows": len(rows)}):
                    rewards[rows] = self.env.score_rows(state, resp, rmask, rows)

        gen = (
            self._fleet_generate
            if self.fleet is not None
            else self._engine_generate
        )
        out = gen(params, toks, pmask, key, on_row_done)
        if not can_score:
            return out, None
        return out, rewards

    def collect(self, params: Any, key: jax.Array) -> ArrayDict:
        """One GRPO batch: ArrayDict with tokens/attention_mask/
        assistant_mask/sample_log_prob/advantage/reward (+ref_log_prob).

        ``params=None`` pulls the weight scheme's latest snapshot;
        explicitly-passed params win (a pipelined caller snapshots
        ``(params, version)`` atomically and must generate with exactly
        that snapshot, not whatever the scheme holds by generation time).
        """
        if params is None:
            if self.weight_scheme is None:
                raise ValueError("params=None requires a weight_scheme to pull from")
            params = self.weight_scheme.pull()
        tracer = get_tracer()
        with tracer.span("collector.prompts", {"n": self.num_prompts}):
            state, group_ids = self.env.sample_batch(self.num_prompts)
        toks = np.asarray(state["tokens"])
        pmask = np.asarray(state["attention_mask"], np.float32)
        G, P_len = toks.shape
        with tracer.span("collector.rollout") as rollout:
            if self.fleet is not None or self.continuous_batching:
                # the engine consumes prompts on the host (slot-packing and
                # submit copies) — handing it a device array would round-trip
                # the freshly-uploaded batch straight back through a blocking
                # transfer, so the upload happens once, inside _engine_generate
                out, rewards = self._engine_collect(params, toks, pmask, key, state, group_ids)
            else:
                out = self._gen(params, jnp.asarray(toks), jnp.asarray(pmask), key)
                rewards = None
            # the fixed-batch path blocks here, on the tokens it generated
            resp = np.asarray(out.response_tokens)
            rmask = np.asarray(out.response_mask)
            if tracer.enabled:
                rollout.args = {"requests": G, "tokens": np.count_nonzero(rmask)}
        if rewards is None:
            with tracer.span("collector.reward", {"rows": G}):
                _, rewards, _ = self.env.step(state, resp, rmask)

        T = P_len + self.max_new_tokens
        attention_mask = out.full_mask[:, :T].astype(jnp.float32)
        ref_lp = None
        if self.ref_params is not None:
            with tracer.span("collector.ref_score"):
                ref_lp = self._score_ref(out.tokens, attention_mask)
        with tracer.span("collector.assemble"):
            gid = jnp.asarray(group_ids)
            arrays: dict = {
                "tokens": out.tokens,
                "attention_mask": attention_mask,
                "assistant_mask": jnp.concatenate(
                    [jnp.zeros((G, P_len), bool), out.response_mask], axis=1
                ),
                "sample_log_prob": jnp.concatenate(
                    [jnp.zeros((G, P_len)), out.response_log_probs], axis=1
                ),
                "group_id": gid,
            }
            if ref_lp is not None:
                arrays["ref_log_prob"] = ref_lp
            if self.reward_transform is not None:
                # the host needs the shaped rewards, and the stock KL
                # transform reads the reference scores to the host itself:
                # this is the first wait on the scoring dispatched above
                with tracer.span("collector.assemble.wait"):
                    rewards = np.asarray(self.reward_transform(rewards, arrays))
            # advantages AFTER reward shaping, same ordering as the reference's
            # in-env KLRewardTransform (the estimator sees the shaped reward)
            adv = mc_advantage(jnp.asarray(rewards), gid, self.num_prompts)
            return ArrayDict(advantage=adv, reward=jnp.asarray(rewards), **arrays)

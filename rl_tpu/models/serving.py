"""Continuous batching over the paged KV cache (round-4 VERDICT
next-step #6; decode loop de-synced in round 6).

The reference delegates LLM serving to vLLM — continuous batching, paged
KV, multi-replica load balancing (reference
torchrl/modules/llm/backends/vllm/vllm_async.py:515 ``AsyncVLLM``,
:1559 ``LoadBalancer``). There is no serving engine to delegate to on
TPU-in-this-image, so this is the native equivalent, built the XLA way:

- **Static shapes.** The engine owns ``n_slots`` sequence slots and a
  block pool (``TransformerLM.init_paged_cache``). Every jitted program —
  one prefill per prompt-length bucket, one K-step decode chunk — has a
  fixed shape; dynamism lives in block tables, per-slot lengths, and
  active masks (data, not shapes).
- **Slot admission (the continuous part).** When a sequence finishes, its
  blocks return to the pool and the slot is re-filled from the queue
  while the other slots keep decoding — a batch never waits for its
  slowest member, which is where the mixed-length throughput win comes
  from (the fixed-batch ``generate`` runs every row to the batch max).
- **Paged KV.** Slots own block tables into a shared pool, so HBM holds
  ~sum(actual lengths), not n_slots x max_len; the attention gathers the
  table's blocks in one shot (``transformer._paged_attention``).
- **On-device stop accounting (the de-sync).** The decode program carries
  ``active``/``lens``/``budget``/``last`` ON DEVICE: each scan step
  samples a token, decrements the active slots' budgets, and deactivates
  slots that emit eos or exhaust their budget — the host never needs the
  token VALUES to decide continuation, only to drain finished outputs.
  That makes chunk K+1 safe to launch before chunk K's tokens have been
  transferred (double-buffered dispatch): the per-chunk ``np.asarray``
  sync becomes an overlapped async copy of the PREVIOUS chunk while the
  next one runs.
- **Host-side allocator.** Block bookkeeping (free list, table mirror,
  per-slot lengths) is plain numpy on the host. The device holds a
  pinned mirror of the block table that one registered program brings
  up to the host's table per round (the changed entries only, never a
  full host->device table upload per step), and the
  host accepts each drained chunk with one vectorized pass over all S
  slots (no per-token Python loop). The host mirrors are exact by
  construction: the device's stop rule (accept tokens up to
  min(first-eos+1, budget, K)) is re-derived on the host from the same
  inputs, so the two ledgers never need a reconciliation sync.
"""

from __future__ import annotations

import collections
import dataclasses
import operator
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import hot_path
from ..compile import ShapeBuckets, get_program_registry
from ..kvmem import DEFER_ROUND, PrefixKVAllocator
from ..obs.device import DeviceMetrics
from ..obs.trace import ctx_args, current_context, get_tracer
from .speculative import (
    DraftSource,
    NGramDraft,
    PrefixTreeDraft,
    sample_tokens,
    slot_keys,
    spec_keys,
)

__all__ = [
    "ContinuousBatchingEngine",
    "KVHandoff",
    "LoadBalancer",
    "Request",
    "FinishedRequest",
    "ServiceSaturated",
    "ServingService",
    "RemoteEngine",
]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [P] int32
    max_new_tokens: int
    # causal link to the submitter (fleet dispatch span, TCP handler, ...);
    # None outside any traced request
    ctx: Any = None
    t_submit: float = 0.0  # seconds on the recorder's clock (now_us() / 1e6)
    blocks: int = 0  # KV blocks it needs to run to its budget (set at submit)


@dataclasses.dataclass
class FinishedRequest:
    rid: int
    prompt: np.ndarray
    tokens: np.ndarray  # [N] generated ids (eos included if hit)
    log_probs: np.ndarray  # [N] behavior log-probs of the sampled tokens
    finished_reason: str  # "eos" | "length"
    # seconds on the recorder's clock (``get_tracer().now_us() / 1e6``):
    # submitted, just before its prefill's program call, first token on the
    # host, slot freed
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_finish: float = 0.0
    slot: int = -1


@dataclasses.dataclass
class KVHandoff:
    """A detached prefill's transferable result (the ``kv_handoff``
    disaggregation path): everything a decode-role engine needs to adopt
    the sequence — the prompt, the first sampled token, the remaining
    budget, and host copies of the paged KV block contents for positions
    ``[0, lens)``. Self-contained: the prefill engine frees its blocks
    before returning, so dropping a handoff leaks nothing anywhere."""

    prompt: np.ndarray  # [P] int32
    first_token: int
    first_lp: float
    budget: int  # tokens still to emit (max_new_tokens - 1)
    lens: int  # KV-valid positions (== len(prompt))
    block_size: int
    # per layer: the engine's pool-field tuple (2 f32 / 4 int8+scales) of
    # host arrays, each [n_blocks_used, ...] block-major
    kv: tuple = ()
    # set when the prefill already finished the request (eos on the first
    # token, or a one-token budget): nothing to adopt, deliver directly
    finished: FinishedRequest | None = None


@dataclasses.dataclass
class _InFlight:
    """A dispatched decode chunk whose tokens have not been accepted yet."""

    toks: Any  # device [S, K] int32
    lps: Any  # device [S, K] float32
    rid0: np.ndarray  # slot -> rid at launch (accept only if unchanged)
    run_mask: np.ndarray  # slots this chunk was allowed to advance
    chunk: int
    fresh_compile: bool  # first launch at this K: exclude from tuning
    # the ``engine.launch.dispatch`` span's duration: host wall from the
    # program call to the async copies' start (tuner input)
    dispatch_s: float
    # speculative verify dispatches carry the drafts they proposed so the
    # host drain can re-derive the device's chain-acceptance rule exactly
    kind: str = "decode"  # "decode" | "verify"
    draft: np.ndarray | None = None  # [S, K-1] proposed tokens (verify only)


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds the largest bucket {buckets[-1]}")


def _pow2ceil(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


# every per-layer pool-shaped buffer a program threads, in order: int8 KV
# (TransformerConfig.kv_int8) adds per-block scale arrays that must ride
# through programs, CoW copies, and warmup signatures exactly like the
# pools (all are block-major on axis 0). f32 KV yields the legacy
# 2-tuples — same pytree structure, so executable-store keys and program
# signatures are unchanged when int8 is off.
_POOL_FIELDS = ("pool_k", "pool_v", "scale_k", "scale_v")


def _pools_from(cache):
    """Layer cache dicts -> the flat per-layer pool tuples the engine
    threads through its programs (2-tuples f32, 4-tuples int8+scales)."""
    return tuple(tuple(c[f] for f in _POOL_FIELDS if f in c) for c in cache)


def _pool_caches(pools, **common):
    """Per-layer cache dicts back from the threaded pool tuples, plus the
    shared table/len/active fields."""
    return [dict(zip(_POOL_FIELDS, lp), **common) for lp in pools]


class _ChunkTuner:
    """Pick ``decode_chunk`` from measured sync overhead vs chunk compute.

    Per drained chunk the engine reports the host-side cost of the round
    (dispatch + vectorized accept, ``host_s``) and the blocking remainder
    of the device wait (``wait_s``). With per-step device time
    ``s = wait_s / K``, the chunk size that keeps sync overhead at or
    below ``target_frac`` of the compute is ``K >= host_s / (frac * s)``;
    the tuner tracks EMAs of both and selects the smallest power-of-two
    ladder entry that satisfies it. When the device wait vanishes (host
    is the bottleneck), it saturates at the ladder top — exactly the
    regime where amortizing host work hardest matters. Overlapped rounds
    under-measure ``s`` which only biases K upward (fewer syncs), never
    below the safe floor.
    """

    LADDER = (1, 2, 4, 8, 16, 32)

    def __init__(self, target_frac: float = 0.25, ema: float = 0.35, init: int = 2):
        self.k = init
        self.target_frac = target_frac
        self._ema = ema
        self._h: float | None = None
        self._s: float | None = None

    def observe(self, host_s: float, wait_s: float, chunk: int):
        per_step = wait_s / max(chunk, 1)
        a = self._ema
        self._h = host_s if self._h is None else (1 - a) * self._h + a * host_s
        self._s = per_step if self._s is None else (1 - a) * self._s + a * per_step
        if self._s <= 1e-9:
            self.k = self.LADDER[-1]
            return
        want = self._h / (self.target_frac * self._s)
        for c in self.LADDER:
            if c >= want:
                self.k = c
                return
        self.k = self.LADDER[-1]


class ContinuousBatchingEngine:
    """Slot-based continuous batching for :class:`TransformerLM`.

    Args:
        model / params: the language model (any TransformerConfig).
        n_slots: concurrent sequences on device (the decode batch).
        block_size: tokens per KV block.
        n_blocks: pool size (block 0 is reserved scratch; usable pool is
            ``n_blocks - 1`` blocks ~= ``(n_blocks-1)*block_size`` tokens).
        max_seq_len: per-sequence cap (defines the block-table width).
        prompt_buckets: prefill compile buckets (one program per bucket).
        eos_id: stop token (None = run every request to max_new_tokens).
        temperature / greedy: sampling controls.
        decode_chunk: K decode steps per host round-trip (one jitted
            ``lax.scan``), or ``"auto"`` to tune K from measured chunk
            wall-time vs sync overhead. Token output is identical for
            every K (the stop rule is applied on device per step); for
            non-greedy sampling the RNG stream depends on K, so
            reproducibility-sensitive callers should pin an int.
        params_sharding: optional pytree of shardings (params' structure,
            e.g. from :func:`rl_tpu.parallel.fsdp_sharding`) every params
            assignment is pinned to — weight pushes that already match
            alias buffers instead of copying.
        buckets: a :class:`rl_tpu.compile.ShapeBuckets` shared shape
            config (supersedes ``prompt_buckets``; a fleet passes ONE
            instance to every member). Besides the prompt ladder it
            rounds the compact prefill's admitted-count dim up a
            power-of-two ladder, so admission shapes come from a fixed,
            warmable set instead of one program per admitted count.
        registry: the :class:`rl_tpu.compile.ProgramRegistry` the
            engine's programs register with (default: the process one).
            ``aot_warmup()`` pre-compiles — or reloads from the
            persistent executable store — the whole ladder.
        warmup: ``True`` runs :meth:`aot_warmup` before construction
            returns; ``"background"`` runs it on a thread (handle at
            ``self._warmup_handle``) overlapped with remaining setup.
        prefix_cache: enable the prefix-aware KV memory tier
            (:mod:`rl_tpu.kvmem`): admissions match the prompt against a
            radix tree of resident blocks, reference the shared prefix's
            blocks instead of recomputing them, fork at most one block
            copy-on-write, and prefill ONLY the uncached suffix through
            partial-prefill programs (``serving.pprefill.*``). Finished
            sequences donate their blocks back to the tree (multi-turn
            reuse) and unreferenced blocks are evicted LRU under
            pressure. Token output is bit-identical for greedy decoding;
            for sampled decoding the RNG stream differs from the
            non-cached engine (different program shapes), not the
            distribution. See ``docs/kv_prefix.md``.
        speculative: enable speculative decoding — draft up to
            ``spec_lookahead`` tokens per slot from ``draft_source`` and
            verify them all in ONE dispatch (``serving.verify.k{K}``,
            same K-ladder as decode, AOT-warmed: steady-state
            CompileDelta stays 0). Acceptance is exact equality against
            what sequential decode would have sampled, so output is
            BIT-IDENTICAL to ``slot_rng=True`` vanilla decode from the
            same seed (greedy and temperature alike). Implies
            ``slot_rng=True``. See ``docs/speculative.md``.
        slot_rng: sample with per-request streams — response token n of
            request rid keys ``fold_in(fold_in(key(seed), rid), n)`` —
            instead of the legacy split-per-dispatch engine stream.
            Schedule-invariant: the sampled sequence depends only on
            (seed, rid), not batch composition or chunk sizes. Off by
            default; the legacy stream is byte-for-byte unchanged.
        spec_lookahead: max drafted tokens verified per dispatch.
        draft_source: ``"prefix_tree"`` (the kvmem radix tree; requires
            ``prefix_cache=True``), ``"ngram"`` (host prompt-lookup), a
            :class:`~rl_tpu.models.speculative.DraftSource` instance, or
            None to pick the best available.
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        *,
        n_slots: int = 8,
        block_size: int = 16,
        n_blocks: int = 257,
        max_seq_len: int | None = None,
        prompt_buckets: tuple = (32, 128, 512),
        eos_id: int | None = None,
        temperature: float = 1.0,
        greedy: bool = False,
        seed: int = 0,
        decode_chunk: int | str = 1,
        params_sharding: Any = None,
        buckets: ShapeBuckets | None = None,
        registry: Any = None,
        warmup: bool | str = False,
        prefix_cache: bool = False,
        speculative: bool = False,
        slot_rng: bool = False,
        spec_lookahead: int = 7,
        draft_source: Any = None,
        kv_handoff: bool = False,
    ):
        # placement is applied by the params setter, so it must exist
        # before the first assignment below
        self.params_sharding = params_sharding
        self.model, self.params = model, params
        self.n_slots, self.block = n_slots, block_size
        self.max_seq_len = max_seq_len or model.cfg.max_seq_len
        self.max_blocks = -(-self.max_seq_len // block_size)
        if buckets is None:
            buckets = ShapeBuckets(prompt=tuple(sorted(prompt_buckets)))
        self.shape_buckets = buckets
        self.buckets = buckets.prompt
        self.eos_id = eos_id
        self.temperature, self.greedy = temperature, greedy
        self.decode_chunk = decode_chunk
        if decode_chunk == "auto":
            self._fixed_chunk = None
            self._tuner = _ChunkTuner()
        else:
            self._fixed_chunk = max(1, int(decode_chunk))
            self._tuner = None
        self._key = jax.random.key(seed)
        # per-request RNG streams (speculation requires them; opt-in
        # without speculation via slot_rng=True): token n of request rid
        # samples with fold_in(fold_in(base, rid), n), a stream invariant
        # to batch composition, chunk size, and accept/reject history —
        # the property that makes speculative output bit-identical to
        # vanilla slot-stream decode. The legacy split-per-dispatch
        # stream (self._key) stays byte-for-byte untouched when off.
        self.speculative = bool(speculative)
        # prefill/decode disaggregation: detached prefills hand their KV
        # block contents to a decode-role engine (fleet ``disaggregate``).
        # Plain engines only — a kvmem lease cannot cross engines, and the
        # speculative verify path assumes it owns the sequence end to end.
        self.kv_handoff = bool(kv_handoff)
        if self.kv_handoff and speculative:
            raise ValueError(
                "kv_handoff does not compose with speculative decoding")
        if self.kv_handoff and prefix_cache:
            raise ValueError(
                "kv_handoff needs prefix_cache=False (a prefix lease "
                "cannot follow the sequence to another engine)")
        self.slot_rng = bool(slot_rng or speculative)
        self.spec_lookahead = int(spec_lookahead)
        self._base_key = jax.random.key(seed)

        if model.cfg.early_exit_threshold < 1.0:
            raise ValueError(
                "the engine runs every loop for every slot: "
                f"early_exit_threshold={model.cfg.early_exit_threshold} < 1.0 "
                "would need a decode step whose depth differs by slot"
            )
        self.cache = model.init_paged_cache(
            n_slots, n_blocks, block_size, self.max_blocks
        )
        self.n_blocks = n_blocks
        # K/V sets a token leaves in the pools, counted from the cache: one
        # an entry, or several stacked in one entry's pools (scan_layers)
        self.cache_entries = sum(c["pool_k"].shape[0] // n_blocks for c in self.cache)
        self.kv_bytes_per_token = sum(
            c[f].nbytes for c in self.cache for f in ("pool_k", "pool_v")
        ) // (n_blocks * block_size)
        # kv heads a pool stores side by side in one lane row (1: unpacked)
        self.kv_heads_per_row = (
            self.cache[0]["pool_k"].shape[3] // model.cfg.head_dim
        )
        self.loop_steps = model.cfg.loop_steps
        # host mirrors (the allocator's source of truth)
        self.free_blocks = list(range(1, n_blocks))  # 0 = reserved scratch
        self._kvmem: PrefixKVAllocator | None = None
        self._slot_lease: list = [None] * n_slots
        self.prefill_tokens_computed = 0  # suffix token-slots actually run
        self.prefill_tokens_cached = 0  # prompt tokens served from the tree
        if prefix_cache:
            self._kvmem = PrefixKVAllocator(n_blocks, block_size)
            # ONE list object: the allocator owns it, the engine (and the
            # fleet's O(1) accounting) alias it — no mirror to reconcile
            self.free_blocks = self._kvmem.free_blocks
        self.table = np.full((n_slots, self.max_blocks), -1, np.int32)
        self.lens = np.zeros(n_slots, np.int64)  # prompt + ACCEPTED tokens
        self.slot_rid = np.full(n_slots, -1, np.int64)  # -1 = free slot
        self.slot_budget = np.zeros(n_slots, np.int64)  # tokens left to emit
        # admission by the pool: blocks the slot's request needs to run to
        # its budget. What of it the slot's table does not hold yet is
        # RESERVED: no admission may count on it, so a running slot never
        # waits for a block (see _admit)
        self.slot_need = np.zeros(n_slots, np.int64)
        # scheduled upper bounds: cover launches whose tokens are still in
        # flight (== lens/slot_budget whenever nothing is undrained)
        self.sched_lens = np.zeros(n_slots, np.int64)
        self.sched_budget = np.zeros(n_slots, np.int64)
        self.slot_tokens: list[list[np.ndarray]] = [[] for _ in range(n_slots)]
        self.slot_lps: list[list[np.ndarray]] = [[] for _ in range(n_slots)]
        self.slot_prompt: dict[int, np.ndarray] = {}

        # device-resident decode state (threaded through every program; the
        # table is pinned and brought up to the host mirror by the
        # ``serving.table_write`` program, never re-uploaded wholesale)
        self.dev_table = jnp.full((n_slots, self.max_blocks), -1, jnp.int32)
        # what ``dev_table`` holds, on the host: a flush writes where the
        # mirror differs from it (so no mutation of ``table`` is missed)
        self._table_on_device = self.table.copy()
        self.table_write_calls = 0  # flushes that wrote
        self.table_writes = 0  # entries those flushes wrote
        self.dev_lens = jnp.zeros(n_slots, jnp.int32)
        self.dev_active = jnp.zeros(n_slots, bool)
        self.dev_budget = jnp.zeros(n_slots, jnp.int32)
        self.dev_last = jnp.zeros(n_slots, jnp.int32)
        # slot-stream RNG state (slot_rng mode): the request id occupying
        # each slot and how many response tokens it has sampled so far —
        # together they derive every sampling key ON DEVICE
        self.dev_rid = jnp.full(n_slots, -1, jnp.int32)
        self.dev_ntok = jnp.zeros(n_slots, jnp.int32)
        self._dev_all_slots = jnp.ones(n_slots, bool)
        self._inflight: collections.deque[_InFlight] = collections.deque()

        self.queue: list[Request] = []
        self.finished: list[FinishedRequest] = []
        self._next_rid = 0
        # fleet hook: called with each admitted rid right after its prefill
        # sampled the first token (TTFT instrumentation without polling)
        self.on_admit: Any = None
        # instrumentation for throughput + host-sync accounting
        self.decode_steps = 0
        self.prefill_token_slots = 0
        self.decode_launches = 0
        self.decode_drains = 0
        self.host_transfers = 0  # blocking device->host materializations
        self.decode_chunk_last = 1
        self.admissions = 0
        # admission rounds that left a free slot empty for want of blocks,
        # and the blocks the slots' tables held, summed over decode steps
        self.admissions_deferred_kv = 0
        self.kv_block_steps = 0
        # program calls that took the pools, and those that consumed them
        # (the donation engaged): equal wherever the backend donates
        self.kv_pool_calls = 0
        self.kv_pool_calls_aliased = 0
        self.completions: dict[str, int] = {"eos": 0, "length": 0}
        # speculative accounting: dispatches that carried drafts, tokens
        # proposed/accepted, and the accept-rate EMA the fleet's lane
        # router reads (accepted tokens PER verify dispatch, >= 1.0 when
        # speculation is winning)
        self.spec_dispatches = 0
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_accept_ema = 1.0
        self._spec_accept_counts: dict[int, int] = {}  # n_emit -> dispatches
        self._slot_ctx: dict[int, Any] = {}  # rid -> the submitter's trace ctx
        # slot -> (t_submit, t_admit, t_first) of its occupant
        self._slot_times = [(0.0, 0.0, 0.0)] * n_slots
        self._n_pool_blocks = n_blocks - 1
        # on-device token accounting: the decode scan counts every token
        # generated by an effectively-active slot, so throughput telemetry
        # never adds a per-chunk host sync (read only at scrape time)
        self._obs_spec = DeviceMetrics(counters=("tokens", "loop_steps_run"))
        self.dev_obs = self._obs_spec.init()

        # every hot program is a registry-named CachedProgram: compiles are
        # attributed per program on /metrics, executables persist in the
        # store (a restarted replica loads instead of recompiling), and
        # aot_warmup() can pre-build the whole ladder
        self._registry = registry if registry is not None else get_program_registry()
        # same name + same abstract shapes must not collide across engines
        # serving different models/sampling configs
        # kernels_fingerprint() is folded in so an executable baked with a
        # Pallas kernel active can never store-load into a process where
        # that kernel is disabled (and vice versa)
        from ..kernels.registry import kernels_fingerprint

        self._fingerprint = repr((
            type(model).__name__, getattr(model, "cfg", None),
            float(temperature), bool(greedy), eos_id,
            kernels_fingerprint(),
        ))
        self._decode_progs: dict[int, Any] = {}  # chunk K -> CachedProgram
        self._prefills: dict[tuple, Any] = {}  # (A, bucket) -> CachedProgram
        self._pprefills: dict[tuple, Any] = {}  # (A, suffix bucket) -> prog
        self._cow_progs: dict[int, Any] = {}  # padded pair count -> prog
        # slot-stream variants (slot_rng mode): same ladder rungs, keys
        # derived in-program from (base_key, rid, ntok) instead of a host
        # split per dispatch
        self._sdecode_progs: dict[int, Any] = {}  # chunk K -> prog
        self._verify_progs: dict[int, Any] = {}  # verify width K -> prog
        self._sprefills: dict[tuple, Any] = {}
        self._spprefills: dict[tuple, Any] = {}
        # every serving program is replica-local by design (the engine
        # parallelizes by running whole replicas); the IR auditor (R103)
        # holds them to it — a collective appearing in a lowered serving
        # program means a sharding annotation leaked in
        self._ir_contract = {"shard_local": True}
        # programs that end in a sample must lower the fused sampler when
        # the backend supports it; decode/verify additionally carry the
        # paged-attention read. R106 audits both declarations.
        self._ir_contract_sample = {
            **self._ir_contract, "kernel_hot_path": ("sampling",)
        }
        self._ir_contract_decode = {
            **self._ir_contract,
            # an int8 cache satisfies the paged read via the kv_int8
            # kernel, not the f32 one — declaring the wrong name would
            # make R106 fire on every int8 decode lowering
            "kernel_hot_path": (
                "kv_int8" if model.cfg.kv_int8 else "paged_attention",
                "sampling",
            ),
        }
        self._admit_update = self._registry.register(
            "serving.admit_update", _admit_update_fn,
            ir_contract=self._ir_contract,
        )
        self._sadmit_update = (
            self._registry.register(
                "serving.sadmit_update", _sadmit_update_fn,
                ir_contract=self._ir_contract,
            )
            if self.slot_rng
            else None
        )
        # the host loop's own bookkeeping on the device: the table writes,
        # and (legacy stream) the admission's key split. Undonated: the
        # chunk in flight still reads the table that goes in
        self._table_write = self._registry.register(
            "serving.table_write", _table_write_fn, ir_contract=self._ir_contract,
        )
        self._key_split = (
            None
            if self.slot_rng
            else self._registry.register(
                "serving.key_split", _key_split_fn, ir_contract=self._ir_contract,
            )
        )
        # draft source: explicit instance > named source > best available
        # (the prefix tree already holds every served continuation when
        # prefix_cache is on; host n-gram prompt-lookup otherwise)
        self._draft_source: Any = None
        if self.speculative:
            if draft_source is None:
                draft_source = "prefix_tree" if self._kvmem is not None else "ngram"
            if draft_source == "prefix_tree":
                if self._kvmem is None:
                    raise ValueError(
                        "draft_source='prefix_tree' needs prefix_cache=True "
                        "(the radix tree IS the draft index)"
                    )
                self._draft_source = PrefixTreeDraft(self._kvmem)
            elif draft_source == "ngram":
                self._draft_source = NGramDraft()
            elif isinstance(draft_source, DraftSource):
                self._draft_source = draft_source
            else:
                raise ValueError(f"unknown draft_source: {draft_source!r}")
        # warmup=True builds the whole ladder before __init__ returns;
        # "background" overlaps it with the caller's remaining setup
        self._warmup_handle = None
        if warmup == "background":
            self._warmup_handle = self.aot_warmup(background=True)
        elif warmup:
            self.aot_warmup()

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value):
        # pin incoming weights to the engine's mesh layout: when the
        # trainer pushes FSDP-sharded params that already match, device_put
        # aliases the buffers (zero copy); a mismatched layout is reshard-
        # on-device once here rather than at every prefill/decode dispatch
        if self.params_sharding is not None:
            sh = self.params_sharding
            if jax.tree_util.treedef_is_leaf(jax.tree_util.tree_structure(sh)):
                value = jax.device_put(value, sh)  # one sharding, all leaves
            else:
                value = jax.tree.map(jax.device_put, value, sh)
        self._params = value

    # -- jitted programs -------------------------------------------------------

    def _prefill_fn(self, params, pools, table_rows, tokens, token_mask, key):
        """COMPACT bucketed prefill: only the admitted slots' rows ride
        the forward — tokens [A, B] (pads beyond each prompt), token_mask
        [A, B] marks real prompt tokens, table_rows [A, max_blocks] are
        the admitted slots' block tables. The pools are shared with the
        decode cache, so the writes land in place; the compact batch keeps
        per-admission cost at A x bucket instead of n_slots x bucket.
        Samples each admitted slot's FIRST response token."""
        A = tokens.shape[0]
        cache = _pool_caches(
            pools,
            block_table=table_rows,
            len=jnp.zeros((A,), jnp.int32),
            active=token_mask,
        )
        logits, cache = self.model.apply({"params": params}, tokens, cache=cache)
        last = jnp.maximum(token_mask.sum(axis=1) - 1, 0)  # [A]
        last_logits = jnp.take_along_axis(
            logits, last[:, None, None], axis=1
        )[:, 0]
        tok, lp = self._sample(last_logits, key)
        return tok, lp, _pools_from(cache)

    def _count_tokens(self, dm, n):
        """The device counters of ``n`` decoded tokens: the tokens, and the
        loops of the layer stack run for them."""
        n = n.astype(jnp.float32)
        dm = self._obs_spec.inc(dm, "tokens", n)
        return self._obs_spec.inc(dm, "loop_steps_run", n * self.loop_steps)

    def _get_decode_prog(self, chunk: int):
        prog = self._decode_progs.get(chunk)
        if prog is not None:
            return prog

        eos = self.eos_id
        count_tokens = self._count_tokens

        def fn(params, pools, table, lens, active, budget, last, run_mask, key, dm):
            """K decode steps in one program, with the per-slot stop rule
            applied ON DEVICE: an active slot decrements its budget each
            step and deactivates itself when it samples eos or runs out —
            inactive slots write to scratch and freeze their length, so
            the host only needs the token values to DRAIN outputs, never
            to decide continuation. ``key`` is the engine's stream: the
            program splits it as the host's ``jax.random.split`` would
            and samples with the second half. Returns tokens/log-probs
            [S, K] plus the advanced device state, the stream's next key
            and the on-device metrics state, which counts tokens from
            effectively-active slots."""

            def body(carry, k):
                pools, lens, active, budget, last, dm = carry
                eff = active & run_mask
                dm = count_tokens(dm, eff.sum())
                cache = _pool_caches(
                    pools, block_table=table, len=lens, active=eff
                )
                logits, cache = self.model.apply(
                    {"params": params}, last[:, None], cache=cache
                )
                tok, lp = self._sample(logits[:, 0], k)
                new_pools = _pools_from(cache)
                lens = cache[0]["len"]
                budget = budget - eff.astype(budget.dtype)
                stop = budget <= 0
                if eos is not None:
                    stop = stop | (tok == eos)
                active = active & ~(stop & eff)
                last = jnp.where(eff, tok, last)
                return (new_pools, lens, active, budget, last, dm), (tok, lp)

            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, chunk)
            carry = (tuple(pools), lens, active, budget, last, dm)
            (pools, lens, active, budget, last, dm), (toks, lps) = jax.lax.scan(
                body, carry, keys
            )
            return (
                jnp.moveaxis(toks, 0, 1),
                jnp.moveaxis(lps, 0, 1),
                pools,
                lens,
                active,
                budget,
                last,
                key,
                dm,
            )

        prog = self._decode_progs[chunk] = self._registry.register(
            f"serving.decode.k{chunk}", fn, fingerprint=self._fingerprint,
            ir_contract=self._ir_contract_decode,
            donate_argnums=(1,),
        )
        return prog

    def _get_prefill_prog(self, a: int, bucket: int):
        prog = self._prefills.get((a, bucket))
        if prog is None:
            prog = self._prefills[(a, bucket)] = self._registry.register(
                f"serving.prefill.a{a}.b{bucket}",
                self._prefill_fn,
                fingerprint=self._fingerprint,
                ir_contract=self._ir_contract_sample,
                donate_argnums=(1,),
            )
        return prog

    def _pprefill_fn(self, params, pools, table_rows, tokens, token_mask, start, key):
        """PARTIAL bucketed prefill (prefix-cache hits): each admitted
        row's first ``start[i]`` positions already hold valid K/V in
        shared (or CoW-forked) pool blocks, so only the uncached suffix
        rides the forward — tokens [A, B] hold ``prompt[start:]`` and the
        cache ``len`` begins at ``start``, landing the paged writes at
        the right absolute positions while attention reads the cached
        prefix through the row's block table (``kv_pos <= pos`` masking
        makes the suffix attend to prefix + itself causally). Samples
        each admitted slot's FIRST response token, same as the full
        prefill."""
        cache = _pool_caches(
            pools, block_table=table_rows, len=start, active=token_mask
        )
        logits, cache = self.model.apply({"params": params}, tokens, cache=cache)
        last = jnp.maximum(token_mask.sum(axis=1) - 1, 0)  # [A], suffix-local
        last_logits = jnp.take_along_axis(
            logits, last[:, None, None], axis=1
        )[:, 0]
        tok, lp = self._sample(last_logits, key)
        return tok, lp, _pools_from(cache)

    def _get_pprefill_prog(self, a: int, bucket: int):
        prog = self._pprefills.get((a, bucket))
        if prog is None:
            prog = self._pprefills[(a, bucket)] = self._registry.register(
                f"serving.pprefill.a{a}.s{bucket}",
                self._pprefill_fn,
                fingerprint=self._fingerprint,
                ir_contract=self._ir_contract_sample,
                donate_argnums=(1,),
            )
        return prog

    def _cow_copy_fn(self, pools, src, dst):
        """Copy-on-write fork: one gather + one scatter per layer pool
        copies the source blocks' K/V into the writers' fresh private
        blocks (pool axis 0 is the block axis). With int8 KV the per-block
        scale arrays ride the same copy — a forked block keeps the exact
        scale its payload was quantized with. Dispatched BEFORE the
        round's partial prefill, which consumes the returned pools — XLA
        dataflow orders the prefill's writes after these copies without
        any host sync."""
        return tuple(
            tuple(
                a.at[self._block_rows(a, dst)].set(a[self._block_rows(a, src)])
                for a in lp
            )
            for lp in pools
        )

    def _block_rows(self, a, blocks):
        """Rows of pool array ``a`` that hold ``blocks``: themselves, or in
        a stacked pool each entry's copy (entry e's block b is row
        ``e * n_blocks + b``), entry by entry."""
        entries = a.shape[0] // self.n_blocks
        if entries == 1:
            return blocks
        return (self.n_blocks * jnp.arange(entries)[:, None] + blocks[None, :]).reshape(-1)

    def _get_cow_prog(self, n: int):
        prog = self._cow_progs.get(n)
        if prog is None:
            prog = self._cow_progs[n] = self._registry.register(
                f"serving.cowcopy.n{n}", self._cow_copy_fn,
                fingerprint=self._fingerprint,
                ir_contract=self._ir_contract,
                donate_argnums=(0,),
            )
        return prog

    def _dispatch_cow(self, cows):
        """Run the round's COW copies as one fixed-shape program (pair
        count padded up the power-of-two ladder by repeating the last
        pair — re-copying the same src->dst is idempotent). Takes the
        cache's pools and leaves its outputs there for the round's
        partial prefill: cache -> cow -> cache -> prefill -> cache."""
        n = _pow2ceil(len(cows))
        cows = cows + [cows[-1]] * (n - len(cows))
        src, dst = jax.device_put(tuple(np.asarray(cows, np.int32).T))
        pools = _pools_from(self.cache)
        self._rebind_pools(self._get_cow_prog(n)(pools, src, dst))

    def _rebind_pools(self, new_pools):
        """Make a program call's output pools the cache. Every engine
        program that takes the pools is registered with them DONATED, so
        the arrays that went in are consumed by the call (the output
        aliases their memory: no copy, no second set of pools) and
        ``self.cache`` is the only live reference from here on. Nothing
        may keep a pool across a call; what a caller needs of one it
        reads out before (``np.asarray`` of a gather). Also the counter
        that says the donation engages: the input is still the cache's
        first pool here, and ``is_deleted`` is a host flag, no device
        read. A backend that declines the donation leaves it False and
        nothing else differs."""
        self.kv_pool_calls += 1
        self.kv_pool_calls_aliased += self.cache[0]["pool_k"].is_deleted()
        for layer, bufs in zip(self.cache, new_pools):
            layer.update(zip(_POOL_FIELDS, bufs))

    def _sample(self, logits, key):
        """(token, behavior log-prob of that token) per row — ONE source
        of truth for the temperature clamp + greedy branch, shared by
        prefill, decode, and the speculative verify
        (:func:`rl_tpu.models.speculative.sample_tokens`)."""
        return sample_tokens(
            logits, key, temperature=self.temperature, greedy=self.greedy
        )

    # -- slot-stream programs (slot_rng / speculative mode) --------------------
    #
    # Same ladder rungs as the legacy families, but every sampling key is
    # derived IN-PROGRAM from (base_key, rid, ntok) — response token n of
    # request rid always keys fold_in(fold_in(base, rid), n), whatever
    # batch, chunk size, or speculative accept history produced it. That
    # schedule invariance is what lets the verify program reproduce
    # sequential decode bit-for-bit.

    def _sprefill_fn(self, params, pools, table_rows, tokens, token_mask, rids, base_key):
        """Compact bucketed prefill, slot-stream RNG: row i samples its
        FIRST response token (index 0 of rid's stream)."""
        A = tokens.shape[0]
        cache = _pool_caches(
            pools,
            block_table=table_rows,
            len=jnp.zeros((A,), jnp.int32),
            active=token_mask,
        )
        logits, cache = self.model.apply({"params": params}, tokens, cache=cache)
        last = jnp.maximum(token_mask.sum(axis=1) - 1, 0)  # [A]
        last_logits = jnp.take_along_axis(
            logits, last[:, None, None], axis=1
        )[:, 0]
        keys = slot_keys(base_key, rids, jnp.zeros_like(rids))
        tok, lp = self._sample(last_logits, keys)
        return tok, lp, _pools_from(cache)

    def _get_sprefill_prog(self, a: int, bucket: int):
        prog = self._sprefills.get((a, bucket))
        if prog is None:
            prog = self._sprefills[(a, bucket)] = self._registry.register(
                f"serving.sprefill.a{a}.b{bucket}",
                self._sprefill_fn,
                fingerprint=self._fingerprint,
                ir_contract=self._ir_contract_sample,
                donate_argnums=(1,),
            )
        return prog

    def _spprefill_fn(self, params, pools, table_rows, tokens, token_mask, start, rids, base_key):
        """Partial bucketed prefill (prefix-cache hits), slot-stream RNG."""
        cache = _pool_caches(
            pools, block_table=table_rows, len=start, active=token_mask
        )
        logits, cache = self.model.apply({"params": params}, tokens, cache=cache)
        last = jnp.maximum(token_mask.sum(axis=1) - 1, 0)  # [A], suffix-local
        last_logits = jnp.take_along_axis(
            logits, last[:, None, None], axis=1
        )[:, 0]
        keys = slot_keys(base_key, rids, jnp.zeros_like(rids))
        tok, lp = self._sample(last_logits, keys)
        return tok, lp, _pools_from(cache)

    def _get_spprefill_prog(self, a: int, bucket: int):
        prog = self._spprefills.get((a, bucket))
        if prog is None:
            prog = self._spprefills[(a, bucket)] = self._registry.register(
                f"serving.spprefill.a{a}.s{bucket}",
                self._spprefill_fn,
                fingerprint=self._fingerprint,
                ir_contract=self._ir_contract_sample,
                donate_argnums=(1,),
            )
        return prog

    def _get_sdecode_prog(self, chunk: int):
        prog = self._sdecode_progs.get(chunk)
        if prog is not None:
            return prog

        eos = self.eos_id
        count_tokens = self._count_tokens

        def fn(params, pools, table, lens, active, budget, last, run_mask,
               rids, ntok, base_key, dm):
            """The decode scan with slot-stream keys: step j of this chunk
            samples slot s with key (rids[s], ntok[s] + emitted so far).
            Carries ``ntok`` so the stream survives chunk boundaries and
            speculative interleaving."""

            def body(carry, _):
                pools, lens, active, budget, last, ntok, dm = carry
                eff = active & run_mask
                dm = count_tokens(dm, eff.sum())
                cache = _pool_caches(
                    pools, block_table=table, len=lens, active=eff
                )
                logits, cache = self.model.apply(
                    {"params": params}, last[:, None], cache=cache
                )
                keys = slot_keys(base_key, rids, ntok)
                tok, lp = self._sample(logits[:, 0], keys)
                new_pools = _pools_from(cache)
                lens = cache[0]["len"]
                ntok = ntok + eff.astype(ntok.dtype)
                budget = budget - eff.astype(budget.dtype)
                stop = budget <= 0
                if eos is not None:
                    stop = stop | (tok == eos)
                active = active & ~(stop & eff)
                last = jnp.where(eff, tok, last)
                return (new_pools, lens, active, budget, last, ntok, dm), (tok, lp)

            carry = (tuple(pools), lens, active, budget, last, ntok, dm)
            (pools, lens, active, budget, last, ntok, dm), (toks, lps) = jax.lax.scan(
                body, carry, None, length=chunk
            )
            return (
                jnp.moveaxis(toks, 0, 1),
                jnp.moveaxis(lps, 0, 1),
                pools,
                lens,
                active,
                budget,
                last,
                ntok,
                dm,
            )

        prog = self._sdecode_progs[chunk] = self._registry.register(
            f"serving.sdecode.k{chunk}", fn, fingerprint=self._fingerprint,
            ir_contract=self._ir_contract_decode,
            donate_argnums=(1,),
        )
        return prog

    def _get_verify_prog(self, k: int):
        """The speculative verify: score a chunk of K positions — the
        true last token plus K-1 drafted continuations — in ONE parallel
        forward, then accept the longest prefix of drafts that matches
        what sequential decode would have sampled (chain acceptance).
        Position j samples with the key token index ntok+j would use, so
        every accepted token is bit-identical to vanilla slot-stream
        decode; the first rejected position's sample is itself the
        corrected (vanilla) token, so a dispatch always advances >= 1."""
        prog = self._verify_progs.get(k)
        if prog is not None:
            return prog

        eos = self.eos_id
        msl = self.max_seq_len
        K = int(k)

        def fn(params, pools, table, lens, active, budget, last, run_mask,
               drafts, rids, ntok, base_key, dm):
            S = lens.shape[0]
            eff = active & run_mask
            x = jnp.concatenate([last[:, None], drafts], axis=1)  # [S, K]
            # clamp KV writes inside the slot's allocated room: emitted
            # tokens never exceed budget (< n_room), so every accepted
            # position was really written and really attended
            n_room = jnp.minimum(jnp.minimum(budget + 1, msl - lens), K)
            posmask = (jnp.arange(K)[None, :] < n_room[:, None]) & eff[:, None]
            cache = _pool_caches(
                pools, block_table=table, len=lens, active=posmask
            )
            logits, cache = self.model.apply({"params": params}, x, cache=cache)
            keys = spec_keys(base_key, rids, ntok, K)  # [S, K]
            tok, lp = self._sample(
                logits.reshape(S * K, -1), keys.reshape(S * K)
            )
            tok, lp = tok.reshape(S, K), lp.reshape(S, K)
            # chain acceptance: position j's sample is the vanilla token
            # iff drafts 1..j each equalled the sample before them
            good = (drafts == tok[:, : K - 1]).astype(jnp.int32)  # [S, K-1]
            chain = 1 + jnp.cumprod(good, axis=1).sum(axis=1)  # [S]
            if eos is None:
                eos_pos = jnp.full((S,), K, jnp.int32)
            else:
                is_eos = tok == eos
                eos_pos = jnp.where(
                    is_eos.any(axis=1), jnp.argmax(is_eos, axis=1), K
                ).astype(jnp.int32)
            n_emit = jnp.minimum(
                jnp.minimum(chain.astype(jnp.int32), eos_pos + 1),
                budget,
            )
            n_emit = jnp.where(eff, n_emit, 0)
            dm = self._count_tokens(dm, n_emit.sum())
            lens = lens + n_emit
            ntok = ntok + n_emit
            budget = budget - n_emit
            stop = budget <= 0
            if eos is not None:
                stop = stop | (eos_pos < n_emit)
            active = active & ~(stop & eff)
            idx = jnp.maximum(n_emit - 1, 0)
            last = jnp.where(
                eff & (n_emit > 0),
                jnp.take_along_axis(tok, idx[:, None], axis=1)[:, 0],
                last,
            )
            return tok, lp, _pools_from(cache), lens, active, budget, last, ntok, dm

        prog = self._verify_progs[k] = self._registry.register(
            # verify feeds K>1 positions per dispatch, so the T==1 paged
            # decode kernel never lowers here — only the sampler is owed
            f"serving.verify.k{K}", fn, fingerprint=self._fingerprint,
            ir_contract=self._ir_contract_sample,
            donate_argnums=(1,),
        )
        return prog

    # -- allocator -------------------------------------------------------------

    def _blocks_needed(self, length: int) -> int:
        return -(-length // self.block)

    def _ensure_blocks(self, slot: int, new_len: int) -> bool:
        """Grow the slot's table to cover ``new_len`` tokens; False if the
        pool is exhausted (caller defers the work). ``have`` is counted
        from the table itself — recomputing it from ``lens`` undercounts
        when the previous allocation already covered len+1 (prompt length
        an exact block multiple), which would overwrite and LEAK a block."""
        have = int((self.table[slot] >= 0).sum())
        need = self._blocks_needed(new_len)
        if self._kvmem is not None:
            # decode growth through the allocator: may evict LRU
            # unreferenced cached blocks to satisfy the request
            got = self._kvmem.alloc(need - have)
            if got is None:
                return False
            self.table[slot, have:need] = got
            return True
        if need - have > len(self.free_blocks):
            return False
        for j in range(have, need):
            self.table[slot, j] = self.free_blocks.pop()
        return True

    def _kv_reserved(self) -> int:
        """Blocks the running slots will still take to reach their budgets."""
        have = (self.table >= 0).sum(axis=1)
        return operator.index(np.maximum(self.slot_need - have, 0).sum())

    def _kv_available(self) -> int:
        """Blocks a new admission may count on: the free capacity (in
        prefix mode with what eviction would free) less the reservations."""
        return self.kv_free_blocks() - self._kv_reserved()

    def _flush_table_writes(self):
        """Bring the pinned device table up to the host mirror: every entry
        that differs from what the device holds (blocks handed out, and
        the -1s of freed rows) in ONE call of ``serving.table_write``,
        whose positions and values go over in one ``[2, table.size]``
        transfer. One shape for any number of writes, warmed by
        ``aot_warmup``."""
        pos = np.flatnonzero(self.table != self._table_on_device)
        if not len(pos):
            return
        n = len(pos)
        with get_tracer().span("engine.flush_tables", {"writes": n}):
            writes = np.zeros((2, self.table.size), np.int32)
            writes[0] = self.table.size  # past the n real writes: out of range, dropped
            writes[0, :n] = pos
            writes[1, :n] = self.table.flat[pos]
            self.dev_table = self._table_write(self.dev_table, jax.device_put(writes))
            self._table_on_device.flat[pos] = writes[1, :n]
            self.table_write_calls += 1
            self.table_writes += n

    def _free_slot(self, slot: int, reason: str):
        self.completions[reason] = self.completions.get(reason, 0) + 1
        rid = int(self.slot_rid[slot])
        ctx = self._slot_ctx.pop(rid, None)
        chunks = self.slot_tokens[slot]
        tracer = get_tracer()
        t_submit, t_admit, t_first = self._slot_times[slot]
        fin = FinishedRequest(
            rid=rid,
            prompt=self.slot_prompt.pop(rid),
            tokens=(
                np.concatenate(chunks).astype(np.int32)
                if chunks
                else np.zeros(0, np.int32)
            ),
            log_probs=(
                np.concatenate(self.slot_lps[slot]).astype(np.float32)
                if self.slot_lps[slot]
                else np.zeros(0, np.float32)
            ),
            finished_reason=reason,
            t_submit=t_submit,
            t_admit=t_admit,
            t_first=t_first,
            t_finish=tracer.now_us() * 1e-6,
            slot=slot,
        )
        self.finished.append(fin)
        if tracer.enabled:
            # one complete event a request, submit to finish; ``rid`` is the
            # identifier its spans share, the ctx ids hang it in the
            # submitter's causal tree beside ``engine_admit``
            tracer.end_span(
                "request",
                t_submit * 1e6,
                {"rid": rid, "slot": slot, "queue_s": t_admit - t_submit,
                 "prefill_s": t_first - t_admit, "tokens": len(fin.tokens),
                 "reason": reason,
                 **(ctx_args(ctx.child()) if ctx is not None else {})},
                end_us=fin.t_finish * 1e6,
            )
        used = self.table[slot]
        if self._kvmem is not None:
            # the lease ends here, BEFORE the host mirrors reset: lens[slot]
            # still counts exactly the KV-valid positions (prompt + accepted
            # tokens minus the final sample, which was never fed back), so
            # the allocator can extend/donate the generated blocks into the
            # tree for multi-turn reuse and free the rest
            lease, self._slot_lease[slot] = self._slot_lease[slot], None
            self._kvmem.release(
                lease,
                fin.prompt.tolist() + fin.tokens.tolist(),
                operator.index(self.lens[slot]),
                [b for b in used.tolist() if b >= 0],
            )
        else:
            self.free_blocks.extend(int(b) for b in used[used >= 0])
        self.table[slot] = -1
        self.lens[slot] = 0
        self.sched_lens[slot] = 0
        self.slot_budget[slot] = 0
        self.sched_budget[slot] = 0
        self.slot_need[slot] = 0  # a slot that stopped early drops its reservation
        self.slot_rid[slot] = -1
        self.slot_tokens[slot] = []
        self.slot_lps[slot] = []
        # no device-side cleanup is needed: the slot deactivated ITSELF on
        # device (that is what finished it), and the row's -1s reach the
        # device table with the next flush

    # -- public surface --------------------------------------------------------

    def aot_warmup(
        self,
        *,
        decode_chunks=None,
        admit_sizes=None,
        prompt_buckets=None,
        background: bool = False,
    ):
        """Pre-build the engine's whole program ladder ahead of traffic.

        Every ``(admit size x prompt bucket)`` prefill, every decode-chunk
        program, the admit merge, the block-table write and the key split
        get their abstract signatures
        registered and driven through ``lower().compile()`` — or loaded
        from the persistent executable store when a previous process
        already built them. After this, steady-state traffic is
        recompile-free (assert it with
        :class:`rl_tpu.compile.CompileDelta`).

        Defaults cover the full ladder: all admit sizes x all prompt
        buckets, and the fixed decode chunk (or the auto-tuner's whole
        ladder when ``decode_chunk="auto"``). ``background=True`` returns
        a :class:`rl_tpu.compile.WarmupHandle` so compilation overlaps
        host setup (fleet membership, TCP binds, checkpoint IO).
        """

        def absval(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        params_abs = jax.tree.map(absval, self.params)
        pools_abs = tuple(
            tuple(absval(layer[f]) for f in _POOL_FIELDS if f in layer)
            for layer in self.cache
        )
        key_abs = absval(self._key)
        S = self.n_slots
        table_abs = jax.ShapeDtypeStruct((S, self.max_blocks), jnp.int32)
        vec_i32 = jax.ShapeDtypeStruct((S,), jnp.int32)
        vec_bool = jax.ShapeDtypeStruct((S,), jnp.bool_)
        dm_abs = jax.tree.map(absval, self.dev_obs)
        progs = []
        if decode_chunks is None:
            decode_chunks = (
                (self._fixed_chunk,)
                if self._fixed_chunk is not None
                else _ChunkTuner.LADDER
            )
        for chunk in decode_chunks:
            if self.slot_rng:
                prog = self._get_sdecode_prog(int(chunk))
                prog.add_signature(
                    params_abs, pools_abs, table_abs, vec_i32, vec_bool,
                    vec_i32, vec_i32, vec_bool, vec_i32, vec_i32, key_abs,
                    dm_abs,
                )
            else:
                prog = self._get_decode_prog(int(chunk))
                prog.add_signature(
                    params_abs, pools_abs, table_abs, vec_i32, vec_bool,
                    vec_i32, vec_i32, vec_bool, key_abs, dm_abs,
                )
            progs.append(prog)
        if self.speculative:
            # verify rungs ride the SAME K-ladder as decode chunks: every
            # width speculation can ever dispatch is warmed here, so the
            # steady-state CompileDelta is 0 by construction
            k_max = _pow2ceil(
                min(self.spec_lookahead, _ChunkTuner.LADDER[-1] - 1) + 1
            )
            for k in _ChunkTuner.LADDER:
                if k < 2 or k > k_max:
                    continue
                prog = self._get_verify_prog(k)
                prog.add_signature(
                    params_abs, pools_abs, table_abs, vec_i32, vec_bool,
                    vec_i32, vec_i32, vec_bool,
                    jax.ShapeDtypeStruct((S, k - 1), jnp.int32),
                    vec_i32, vec_i32, key_abs, dm_abs,
                )
                progs.append(prog)
        if admit_sizes is None:
            admit_sizes = self.shape_buckets.admit_sizes(S)
        if prompt_buckets is None:
            prompt_buckets = (
                self.buckets
                if self._kvmem is None
                else self.shape_buckets.suffix_ladder()
            )
        if self._kvmem is None:
            for a in admit_sizes:
                for b in prompt_buckets:
                    a, b = int(a), int(b)
                    if self.slot_rng:
                        prog = self._get_sprefill_prog(a, b)
                        prog.add_signature(
                            params_abs,
                            pools_abs,
                            jax.ShapeDtypeStruct((a, self.max_blocks), jnp.int32),
                            jax.ShapeDtypeStruct((a, b), jnp.int32),
                            jax.ShapeDtypeStruct((a, b), jnp.bool_),
                            jax.ShapeDtypeStruct((a,), jnp.int32),
                            key_abs,
                        )
                    else:
                        prog = self._get_prefill_prog(a, b)
                        prog.add_signature(
                            params_abs,
                            pools_abs,
                            jax.ShapeDtypeStruct((a, self.max_blocks), jnp.int32),
                            jax.ShapeDtypeStruct((a, b), jnp.int32),
                            jax.ShapeDtypeStruct((a, b), jnp.bool_),
                            key_abs,
                        )
                    progs.append(prog)
        else:
            # prefix mode dispatches partial prefills bucketed on SUFFIX
            # length (the legacy full-prefill family is never called), plus
            # the COW copy ladder: one program per padded pair count
            for a in admit_sizes:
                for b in prompt_buckets:
                    a, b = int(a), int(b)
                    if self.slot_rng:
                        prog = self._get_spprefill_prog(a, b)
                        prog.add_signature(
                            params_abs,
                            pools_abs,
                            jax.ShapeDtypeStruct((a, self.max_blocks), jnp.int32),
                            jax.ShapeDtypeStruct((a, b), jnp.int32),
                            jax.ShapeDtypeStruct((a, b), jnp.bool_),
                            jax.ShapeDtypeStruct((a,), jnp.int32),
                            jax.ShapeDtypeStruct((a,), jnp.int32),
                            key_abs,
                        )
                    else:
                        prog = self._get_pprefill_prog(a, b)
                        prog.add_signature(
                            params_abs,
                            pools_abs,
                            jax.ShapeDtypeStruct((a, self.max_blocks), jnp.int32),
                            jax.ShapeDtypeStruct((a, b), jnp.int32),
                            jax.ShapeDtypeStruct((a, b), jnp.bool_),
                            jax.ShapeDtypeStruct((a,), jnp.int32),
                            key_abs,
                        )
                    progs.append(prog)
            n = 1
            while n <= _pow2ceil(S):
                prog = self._get_cow_prog(n)
                prog.add_signature(
                    pools_abs,
                    jax.ShapeDtypeStruct((n,), jnp.int32),
                    jax.ShapeDtypeStruct((n,), jnp.int32),
                )
                progs.append(prog)
                n *= 2
        if self.slot_rng:
            self._sadmit_update.add_signature(
                vec_i32, vec_bool, vec_i32, vec_i32, vec_i32, vec_i32,
                vec_bool, vec_i32, vec_i32, vec_i32, vec_i32,
            )
            progs.append(self._sadmit_update)
        else:
            self._admit_update.add_signature(
                vec_i32, vec_bool, vec_i32, vec_i32,
                vec_bool, vec_i32, vec_i32, vec_i32,
            )
            self._key_split.add_signature(key_abs)
            progs += [self._admit_update, self._key_split]
        self._table_write.add_signature(
            table_abs, jax.ShapeDtypeStruct((2, S * self.max_blocks), jnp.int32)
        )
        progs.append(self._table_write)
        return self._registry.aot_warmup(programs=progs, background=background)

    def metrics_snapshot(self) -> dict:
        """Flat host dict of the engine's telemetry. The only device read
        is the on-device counters (one explicit transfer), so calling
        this at scrape cadence costs nothing on the decode path."""
        used = self._n_pool_blocks - len(self.free_blocks)
        counters = jax.device_get(self.dev_obs["counters"])
        snap = {
            "tokens_generated": float(counters["tokens"]),
            "decode_steps": self.decode_steps,
            "decode_launches": self.decode_launches,
            "decode_drains": self.decode_drains,
            "host_transfers": self.host_transfers,
            "prefill_token_slots": self.prefill_token_slots,
            "decode_chunk": self.decode_chunk_last,
            "tuner_k": self._tuner.k if self._tuner is not None else None,
            "admissions": self.admissions,
            "completions_eos": self.completions.get("eos", 0),
            "completions_length": self.completions.get("length", 0),
            "queue_depth": len(self.queue),
            "active_slots": int((self.slot_rid >= 0).sum()),
            "pending": self.pending(),
            "kv_blocks_used": used,
            "kv_blocks_total": self._n_pool_blocks,
            "kv_utilization": used / max(self._n_pool_blocks, 1),
            "kv_reserved_blocks": self._kv_reserved(),
            "kv_block_steps": self.kv_block_steps,
            "admissions_deferred_kv": self.admissions_deferred_kv,
            "loop_steps_run": float(counters["loop_steps_run"]),
            "cache_entries": self.cache_entries,
            "kv_bytes_per_token": self.kv_bytes_per_token,
            "kv_heads_per_row": self.kv_heads_per_row,
            "kv_pool_calls": self.kv_pool_calls,
            "kv_pool_calls_aliased": self.kv_pool_calls_aliased,
            "table_write_calls": self.table_write_calls,
            "table_writes": self.table_writes,
        }
        snap["prefill_tokens_computed"] = self.prefill_tokens_computed
        snap["prefill_tokens_cached"] = self.prefill_tokens_cached
        if self.speculative:
            snap["spec_dispatches"] = self.spec_dispatches
            snap["spec_draft_tokens"] = self.spec_draft_tokens
            snap["spec_accepted_tokens"] = self.spec_accepted_tokens
            snap["spec_accept_ema"] = self.spec_accept_ema
            snap["spec_accepted_per_dispatch"] = (
                self.spec_accepted_tokens / self.spec_dispatches
                if self.spec_dispatches
                else 0.0
            )
            snap["spec_accept_counts"] = dict(self._spec_accept_counts)
            for k, v in self._draft_source.stats().items():
                snap[f"spec_draft_{k}"] = v
        if self._kvmem is not None:
            snap.update(self._kvmem.stats())
            # sharing-adjusted: resident blocks no live sequence references
            # are one eviction from free, so they don't count as used
            free_adj = self._kvmem.free_adjusted()
            snap["kv_free_blocks_adjusted"] = free_adj
            snap["kv_utilization"] = 1.0 - free_adj / max(self._n_pool_blocks, 1)
        return snap

    def kv_free_blocks(self) -> int:
        """Sharing-adjusted free capacity for fleet admission: the free
        list plus (prefix mode) resident blocks no live sequence
        references — a fully-shared prompt must not look like pressure."""
        if self._kvmem is not None:
            return self._kvmem.free_adjusted()
        return len(self.free_blocks)

    def kv_admission_probe(self, prompt, max_new_tokens: int = 1):
        """``(shared_len, new_blocks_needed)`` if ``prompt`` were admitted
        now — read-only (nothing allocated, no refs taken). The fleet's
        watermark bypass uses it to recognize fully-shared prompts."""
        seq = prompt.tolist() if hasattr(prompt, "tolist") else list(prompt)
        want = len(seq) + max(1, max_new_tokens)
        if self._kvmem is None:
            return 0, self._blocks_needed(want)
        return self._kvmem.probe(seq, want)

    # -- prefill/decode disaggregation (kv_handoff) ----------------------------

    def prefill_detached(self, prompt, max_new_tokens: int):
        """Run ONE bucketed prefill and return a :class:`KVHandoff`
        instead of occupying a slot: the written KV block contents are
        read back to host, the borrowed blocks return to the free list,
        and a decode-role engine continues via :meth:`adopt_handoff`.
        Uses the same warmed prefill ladder as admission (the admit-size-1
        rung), so a warmed engine hands off without compiling; the
        pow2-padded KV gather is the only eager program, steady after its
        first few widths. Returns ``None`` when no slot or blocks are
        free this instant (the caller retries)."""
        if not self.kv_handoff:
            raise RuntimeError("engine built without kv_handoff=True")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        P = len(prompt)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if P + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({P}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len ({self.max_seq_len})"
            )
        if P > self.buckets[-1]:
            raise ValueError(
                f"prompt length {P} exceeds the largest prefill "
                f"bucket {self.buckets[-1]}"
            )
        free = [s for s in range(self.n_slots) if self.slot_rid[s] < 0]
        if not free:
            return None
        s = free[0]
        if self._blocks_needed(P + 1) > self._kv_available():
            return None  # what is free is reserved for the running slots
        if not self._ensure_blocks(s, P + 1):
            return None
        blocks = [int(b) for b in self.table[s] if b >= 0]
        bucket = self.shape_buckets.prompt_bucket(P)
        pad_a = self.shape_buckets.admit_bucket(1, self.n_slots)
        tokens = np.zeros((pad_a, bucket), np.int32)
        mask = np.zeros((pad_a, bucket), bool)
        tokens[0, :P] = prompt
        mask[0, :P] = True
        slots = np.zeros(pad_a, np.int64)
        slots[0] = s
        rid = self._next_rid
        self._next_rid += 1
        # the prefill reads its table rows from the host mirror; the
        # borrowed blocks are back in the pool before any program reads
        # the device's table, which never sees them
        rows = self.table[slots]
        pools = _pools_from(self.cache)
        if self.slot_rng:
            rid_v = np.full(pad_a, -1, np.int32)
            rid_v[0] = rid
            fn = self._get_sprefill_prog(pad_a, bucket)
            tok, lp, new_pools = fn(
                self.params, pools, *jax.device_put((rows, tokens, mask, rid_v)),
                self._base_key,
            )
        else:
            self._key, k = self._key_split(self._key)
            fn = self._get_prefill_prog(pad_a, bucket)
            tok, lp, new_pools = fn(
                self.params, pools, *jax.device_put((rows, tokens, mask)), k,
            )
        self._rebind_pools(new_pools)
        self.admissions += 1
        self.prefill_token_slots += pad_a * bucket
        self.prefill_tokens_computed += P
        t0, l0 = int(np.asarray(tok)[0]), float(np.asarray(lp)[0])
        self.host_transfers += 1
        budget = max_new_tokens - 1
        hit_eos = self.eos_id is not None and t0 == self.eos_id
        kv: tuple = ()
        if not hit_eos and budget > 0:
            # gather the written KV back to host, padded to a pow2 block
            # count by repeating the last index (duplicate gathers are
            # harmless; the pad rows are sliced off host-side)
            n = len(blocks)
            pad_n = _pow2ceil(n)
            gidx = jnp.asarray(
                np.asarray(blocks + [blocks[-1]] * (pad_n - n), np.int32))

            def take(a):  # [entries * n, ...]: each entry's blocks in a row
                got = np.asarray(a[self._block_rows(a, gidx)])
                got = got.reshape(-1, pad_n, *got.shape[1:])[:, :n]
                return got.reshape(-1, *got.shape[2:])

            kv = tuple(
                tuple(take(c[f]) for f in _POOL_FIELDS if f in c)
                for c in self.cache
            )
        # the borrowed slot returns immediately: the handoff owns host
        # copies, nothing on this engine references the sequence anymore
        self.free_blocks.extend(blocks)
        self.table[s] = -1
        if hit_eos or budget <= 0:
            reason = "eos" if hit_eos else "length"
            self.completions[reason] = self.completions.get(reason, 0) + 1
            fin = FinishedRequest(
                rid=rid, prompt=prompt,
                tokens=np.asarray([t0], np.int32),
                log_probs=np.asarray([l0], np.float32),
                finished_reason=reason,
            )
            return KVHandoff(
                prompt=prompt, first_token=t0, first_lp=l0, budget=0,
                lens=P, block_size=self.block, finished=fin,
            )
        return KVHandoff(
            prompt=prompt, first_token=t0, first_lp=l0, budget=budget,
            lens=P, block_size=self.block, kv=kv,
        )

    def adopt_handoff(self, ho: KVHandoff):
        """Adopt a :class:`KVHandoff`: allocate a slot and blocks, scatter
        the handed-off KV contents into this engine's pools, and activate
        the slot through the same masked admit-update a local admission
        uses — decode continues from the first token as if the prefill
        had run here. Returns the engine rid, or ``None`` when no slot or
        blocks are free this instant."""
        if not self.kv_handoff:
            raise RuntimeError("engine built without kv_handoff=True")
        if ho.finished is not None:
            raise ValueError("handoff already finished; nothing to adopt")
        if ho.block_size != self.block:
            raise ValueError(
                f"handoff block_size {ho.block_size} != engine block size "
                f"{self.block}")
        n = len(ho.kv[0][0]) * len(self.cache) // self.cache_entries  # rows / entries stacked in one
        free = [s for s in range(self.n_slots) if self.slot_rid[s] < 0]
        need = max(n, self._blocks_needed(int(ho.lens) + ho.budget + 1))
        if not free or need > self._kv_available():
            return None
        s = free[0]
        self.slot_need[s] = need
        blocks = [self.free_blocks.pop() for _ in range(n)]
        self.table[s, :n] = blocks
        # scatter the KV in, padded to a pow2 count with duplicate
        # index+value pairs (idempotent — the table-flush trick), so the
        # eager scatter compiles for O(log) distinct widths
        pad_n = _pow2ceil(n)
        didx = jnp.asarray(
            np.asarray(blocks + [blocks[-1]] * (pad_n - n), np.int32))
        for c, layer_kv in zip(self.cache, ho.kv):
            fields = [f for f in _POOL_FIELDS if f in c]
            for f, host in zip(fields, layer_kv):
                vals = host.reshape(-1, n, *host.shape[1:])  # [entries, n, ...]
                if pad_n > n:
                    vals = np.concatenate(
                        [vals, np.repeat(vals[:, -1:], pad_n - n, axis=1)], axis=1)
                c[f] = c[f].at[self._block_rows(c[f], didx)].set(
                    jnp.asarray(vals.reshape(-1, *vals.shape[2:])))
        rid = self._next_rid
        self._next_rid += 1
        P = int(ho.lens)
        self.slot_rid[s] = rid
        self.slot_prompt[rid] = ho.prompt
        self.slot_tokens[s] = [np.asarray([ho.first_token], np.int32)]
        self.slot_lps[s] = [np.asarray([ho.first_lp], np.float32)]
        self.lens[s] = P
        self.sched_lens[s] = P
        self.slot_budget[s] = ho.budget
        self.sched_budget[s] = ho.budget
        # the prefill ran elsewhere: this engine's clock starts at adoption
        self._slot_times[s] = (get_tracer().now_us() * 1e-6,) * 3
        self.admissions += 1
        self._flush_table_writes()
        surv = np.zeros(self.n_slots, bool)
        surv[s] = True
        new_lens = np.zeros(self.n_slots, np.int32)
        new_budget = np.zeros(self.n_slots, np.int32)
        new_last = np.zeros(self.n_slots, np.int32)
        new_lens[s], new_budget[s], new_last[s] = P, ho.budget, ho.first_token
        if self.slot_rng:
            new_rid = np.zeros(self.n_slots, np.int32)
            new_rid[s] = rid
            (
                self.dev_lens, self.dev_active, self.dev_budget,
                self.dev_last, self.dev_rid, self.dev_ntok,
            ) = self._sadmit_update(
                self.dev_lens, self.dev_active, self.dev_budget,
                self.dev_last, self.dev_rid, self.dev_ntok,
                *jax.device_put((surv, new_lens, new_budget, new_last, new_rid)),
            )
        else:
            (
                self.dev_lens, self.dev_active, self.dev_budget,
                self.dev_last,
            ) = self._admit_update(
                self.dev_lens, self.dev_active, self.dev_budget,
                self.dev_last,
                *jax.device_put((surv, new_lens, new_budget, new_last)),
            )
        # on_admit deliberately NOT fired: it runs on the caller's thread
        # (the fleet dispatcher), and admit_events is stepper-thread-only.
        # The fleet records the handoff TTFT at prefill time instead.
        return rid

    def pending(self) -> int:
        """Outstanding work: queued + in-flight requests."""
        return len(self.queue) + int((self.slot_rid >= 0).sum())

    def submit(self, prompt, max_new_tokens: int) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (prefill always samples one token)")
        if len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_seq_len ({self.max_seq_len})"
            )
        if len(prompt) > self.buckets[-1]:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the largest prefill "
                f"bucket {self.buckets[-1]}; raise prompt_buckets"
            )
        need = self._blocks_needed(len(prompt) + max_new_tokens)
        if need > self._n_pool_blocks:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"needs {need} KV blocks, the pool has {self._n_pool_blocks}: "
                "the request could never be admitted"
            )
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(
            rid, prompt, max_new_tokens, ctx=current_context(),
            t_submit=get_tracer().now_us() * 1e-6, blocks=need,
        ))
        return rid

    def _admit(self):
        """Fill free slots from the queue; one bucketed prefill per
        admission round (requests grouped into the round's max bucket).

        Admission is by the POOL, not by the prompt: a request is taken
        when the blocks it needs to run to its budget (``len(prompt) +
        max_new_tokens``, known at ``submit``) are free and not reserved
        by a running slot; it takes its prompt's blocks now and the rest
        stay reserved for it (``slot_need``), so no running slot ever
        waits for a block. Where the pool holds every slot's whole table
        the rule never binds.

        Prefill is synchronous — the host needs the first token to settle
        eos/budget immediately — but its device-state updates are fused
        into one jitted masked write, sequenced after any in-flight chunk
        (XLA program order on the shared state arrays)."""
        free = [s for s in range(self.n_slots) if self.slot_rid[s] < 0]
        if not free or not self.queue:
            return
        batch: list[tuple[int, Request]] = []
        starts: list[int] = []  # cached-prefix length per admitted row
        cows: list[tuple[int, int]] = []  # (src, dst) block copies this round
        kv_short = False  # the queue's head did not fit what is free and unreserved
        if self._kvmem is not None:
            for s in free:
                if not self.queue:
                    break
                req = self.queue[0]
                seq = req.prompt.tolist()
                total = len(seq) + req.max_new_tokens
                if self._kvmem.charge(seq, total) > self._kv_available():
                    kv_short = True
                    break  # retry after sequences finish
                plan = self._kvmem.admit(seq, len(seq) + 1)
                if plan is None:
                    break  # pool exhausted: retry after sequences finish
                if plan is DEFER_ROUND:
                    # the match touches blocks published by an EARLIER
                    # admission in this same round, whose prefill has not
                    # dispatched yet — stop batching; next round the
                    # dispatch order makes the share safe
                    break
                self.table[s, : len(plan.blocks)] = plan.blocks
                self._slot_lease[s] = plan.lease
                self.slot_need[s] = req.blocks
                starts.append(plan.shared_len)
                if plan.cow is not None:
                    cows.append(plan.cow)
                batch.append((s, self.queue.pop(0)))
        else:
            for s in free:
                if not self.queue:
                    break
                req = self.queue[0]
                if req.blocks > self._kv_available():
                    kv_short = True
                    break  # retry after sequences finish
                self._ensure_blocks(s, len(req.prompt) + 1)
                self.slot_need[s] = req.blocks
                starts.append(0)
                batch.append((s, self.queue.pop(0)))
        # requests a free slot could not take for want of blocks
        kv_deferred = min(len(free) - len(batch), len(self.queue)) if kv_short else 0
        self.admissions_deferred_kv += kv_short
        if not batch:
            return
        cached = sum(starts)
        computed = sum(len(r.prompt) for _, r in batch) - cached
        tracer = get_tracer()
        with tracer.span("engine.admit") as span:
            if tracer.enabled:
                span.args = {
                    "admitted": len(batch),
                    "slots": [s for s, _ in batch],
                    "queue_depth": len(self.queue),
                    "prefill_tokens": computed,
                    "prefill_cached": cached,
                    "kv_free_blocks": self.kv_free_blocks(),
                    "kv_reserved_blocks": self._kv_reserved(),
                    "kv_deferred": kv_deferred,
                }
            if self._kvmem is not None:
                # the compile ladder buckets the SUFFIX, not the prompt: a
                # 500-token prompt with 480 cached prefills through the same
                # small program as a 20-token cold prompt
                bucket = self.shape_buckets.suffix_bucket(
                    max(len(r.prompt) - st for (_, r), st in zip(batch, starts))
                )
            else:
                bucket = self.shape_buckets.prompt_bucket(
                    max(len(r.prompt) for _, r in batch)
                )
            A = len(batch)
            self.admissions += A
            # round the admitted-count dim up its ladder: the pad rows carry an
            # all-False token mask, so the paged cache routes their writes to
            # the reserved scratch block and the host never reads their rows —
            # admission shapes come from a FIXED set instead of one program per
            # count (the serving shape-bucket tentpole)
            pad_a = self.shape_buckets.admit_bucket(A, self.n_slots)
            tokens = np.zeros((pad_a, bucket), np.int32)
            mask = np.zeros((pad_a, bucket), bool)
            for i, (s, req) in enumerate(batch):
                P = len(req.prompt)
                st = starts[i]
                tokens[i, : P - st] = req.prompt[st:]
                mask[i, : P - st] = True
                self.slot_rid[s] = req.rid
                self.slot_prompt[req.rid] = req.prompt
                self.slot_tokens[s] = []
                self.slot_lps[s] = []
            # pad rows take slot 0's (or any) table row — harmless, since an
            # inactive row never writes through its table and reads are masked
            slots = np.zeros(pad_a, np.int64)
            slots[:A] = [s for s, _ in batch]
            # the prefill reads its rows from the host mirror; the device
            # table takes them now for the decode that follows
            rows = self.table[slots]
            self._flush_table_writes()
            if not self.slot_rng:
                # the legacy engine stream splits here; slot-stream mode
                # derives keys in-program from (base_key, rid, 0) instead and
                # must leave this stream byte-for-byte untouched
                self._key, k = self._key_split(self._key)
            rid_v = np.full(pad_a, -1, np.int32)
            rid_v[:A] = [req.rid for _, req in batch]
            if cows:
                self._dispatch_cow(cows)
            pools = _pools_from(self.cache)
            if self._kvmem is not None:
                start_v = np.zeros(pad_a, np.int32)
                start_v[:A] = starts
                if self.slot_rng:
                    fn = self._get_spprefill_prog(pad_a, bucket)
                    host, tail = (start_v, rid_v), (self._base_key,)
                else:
                    fn = self._get_pprefill_prog(pad_a, bucket)
                    host, tail = (start_v,), (k,)
            elif self.slot_rng:
                fn = self._get_sprefill_prog(pad_a, bucket)
                host, tail = (rid_v,), (self._base_key,)
            else:
                fn = self._get_prefill_prog(pad_a, bucket)
                host, tail = (), (k,)
            t_admit = tracer.now_us() * 1e-6
            # the program call is a span of its own: on this runtime a
            # dispatch blocks while its output pools cannot be allocated,
            # and that wait must not read as the admission's host work
            with tracer.span("engine.prefill.dispatch"):
                tok, lp, new_pools = fn(
                    self.params, pools,
                    *jax.device_put((rows, tokens, mask, *host)), *tail,
                )
            self._rebind_pools(new_pools)
            if self._kvmem is not None:
                # the round's published blocks are now behind a dispatched
                # prefill: safe for next round's admissions to share
                self._kvmem.end_round()
                self.prefill_tokens_cached += cached
            self.prefill_tokens_computed += computed
            self.prefill_token_slots += A * bucket
            with tracer.span("engine.prefill.wait"):
                tok_host, lp_host = np.asarray(tok), np.asarray(lp)
            t_first = tracer.now_us() * 1e-6
            self.host_transfers += 1
            surv = np.zeros(self.n_slots, bool)
            new_lens = np.zeros(self.n_slots, np.int32)
            new_budget = np.zeros(self.n_slots, np.int32)
            new_last = np.zeros(self.n_slots, np.int32)
            new_rid = np.zeros(self.n_slots, np.int32)
            for i, (s, req) in enumerate(batch):
                P = len(req.prompt)
                t0, l0 = int(tok_host[i]), float(lp_host[i])
                self.lens[s] = P
                self.sched_lens[s] = P
                self.slot_tokens[s] = [np.asarray([t0], np.int32)]
                self.slot_lps[s] = [np.asarray([l0], np.float32)]
                b = req.max_new_tokens - 1  # prefill emitted the first token
                self.slot_budget[s] = b
                self.sched_budget[s] = b
                self._slot_times[s] = (req.t_submit, t_admit, t_first)
                if req.ctx is not None:
                    self._slot_ctx[req.rid] = req.ctx
                if self.eos_id is not None and t0 == self.eos_id:
                    self._free_slot(s, "eos")
                elif b <= 0:
                    self._free_slot(s, "length")
                else:
                    surv[s] = True
                    new_lens[s], new_budget[s], new_last[s] = P, b, t0
                    new_rid[s] = req.rid
            if self.on_admit is not None:
                for _s, req in batch:
                    self.on_admit(req.rid)
            if tracer.enabled:
                # one causal node per admitted request, hanging under its
                # submitter's context: the kvmem-admit/CoW/partial-prefill leg
                # of the request tree (cached_prefix tells how partial)
                for (_s, req), st in zip(batch, starts):
                    if req.ctx is not None:
                        tracer.instant(
                            "engine_admit",
                            {"rid": req.rid, "cached_prefix": st,
                             **ctx_args(req.ctx.child())},
                        )
            if surv.any():
                if self.slot_rng:
                    (
                        self.dev_lens,
                        self.dev_active,
                        self.dev_budget,
                        self.dev_last,
                        self.dev_rid,
                        self.dev_ntok,
                    ) = self._sadmit_update(
                        self.dev_lens,
                        self.dev_active,
                        self.dev_budget,
                        self.dev_last,
                        self.dev_rid,
                        self.dev_ntok,
                        *jax.device_put((surv, new_lens, new_budget, new_last, new_rid)),
                    )
                else:
                    (
                        self.dev_lens,
                        self.dev_active,
                        self.dev_budget,
                        self.dev_last,
                    ) = self._admit_update(
                        self.dev_lens,
                        self.dev_active,
                        self.dev_budget,
                        self.dev_last,
                        *jax.device_put((surv, new_lens, new_budget, new_last)),
                    )

    # -- the de-synced decode loop ---------------------------------------------

    def _choose_chunk(self, run: np.ndarray) -> int:
        base = self._fixed_chunk if self._fixed_chunk is not None else self._tuner.k
        if self._fixed_chunk is not None:
            return base
        rem = self.sched_budget[run]
        # no point scanning past the longest remaining budget; with queued
        # admissions waiting, stop just past the EARLIEST finisher so its
        # slot refills promptly (bounds the idle-slot ride-along waste)
        cap = int(rem.max())
        if self.queue:
            cap = min(cap, _pow2ceil(int(rem.min())))
        k = 1
        for c in _ChunkTuner.LADDER:
            if c <= min(base, max(cap, 1)):
                k = c
        return k

    def _launch(self) -> bool:
        """Dispatch one decode chunk without waiting for its result.
        Returns False when there is nothing to advance."""
        host_active = self.slot_rid >= 0
        run = host_active & (self.sched_budget > 0)
        if not run.any():
            return False
        chunk = self._choose_chunk(run)
        while True:
            failed = [
                s
                for s in map(int, np.nonzero(run)[0])
                if not self._ensure_blocks(
                    s,
                    int(self.sched_lens[s])
                    + min(chunk, int(self.sched_budget[s])),
                )
            ]
            if not failed:
                break
            if self._inflight:
                # in-flight completions may free blocks: settle them first
                while self._inflight:
                    self._drain_one()
                host_active = self.slot_rid >= 0
                run = host_active & (self.sched_budget > 0)
                if not run.any():
                    return False
                continue
            if chunk > 1:
                chunk = 1  # pool tight: single-step this round
                continue
            for s in failed:
                run[s] = False
            if not run.any():
                # every in-flight sequence needs a block and none can
                # decode: no completion can ever free one — fail loudly
                # instead of spinning (a PARTIAL stall is fine; the
                # running slots' completions will free blocks)
                raise RuntimeError(
                    f"block pool exhausted with all {len(failed)} in-flight "
                    f"sequences stalled ({len(self.free_blocks)} free "
                    f"blocks); the pool cannot hold this working set"
                )
            break
        tracer = get_tracer()
        with tracer.span("engine.launch") as span:
            self._flush_table_writes()
            run_dev = self._dev_all_slots if run.all() else jax.device_put(run)
            pools = _pools_from(self.cache)
            if self.slot_rng:
                fresh = chunk not in self._sdecode_progs
                prog = self._get_sdecode_prog(chunk)
            else:
                fresh = chunk not in self._decode_progs
                prog = self._get_decode_prog(chunk)
            # the program call to the async copies' start: the tuner's
            # dispatch interval, and a span of its own because a dispatch
            # can block on the device (its output pools' allocation)
            with tracer.span("engine.launch.dispatch") as disp:
                if self.slot_rng:
                    (
                        toks,
                        lps,
                        new_pools,
                        self.dev_lens,
                        self.dev_active,
                        self.dev_budget,
                        self.dev_last,
                        self.dev_ntok,
                        self.dev_obs,
                    ) = prog(
                        self.params,
                        pools,
                        self.dev_table,
                        self.dev_lens,
                        self.dev_active,
                        self.dev_budget,
                        self.dev_last,
                        run_dev,
                        self.dev_rid,
                        self.dev_ntok,
                        self._base_key,
                        self.dev_obs,
                    )
                else:
                    (
                        toks,
                        lps,
                        new_pools,
                        self.dev_lens,
                        self.dev_active,
                        self.dev_budget,
                        self.dev_last,
                        self._key,
                        self.dev_obs,
                    ) = prog(
                        self.params,
                        pools,
                        self.dev_table,
                        self.dev_lens,
                        self.dev_active,
                        self.dev_budget,
                        self.dev_last,
                        run_dev,
                        self._key,
                        self.dev_obs,
                    )
                self._rebind_pools(new_pools)
                try:  # start the device->host copy early; the drain just awaits it
                    toks.copy_to_host_async()
                    lps.copy_to_host_async()
                except Exception:
                    pass
            want = np.minimum(chunk, self.sched_budget) * run
            self.sched_lens += want
            self.sched_budget -= want
            self._inflight.append(
                _InFlight(toks, lps, self.slot_rid.copy(), run.copy(), chunk, fresh, disp.dur_s)
            )
            self.decode_steps += chunk
            self.decode_launches += 1
            self.decode_chunk_last = chunk
            self.kv_block_steps += chunk * np.count_nonzero(self.table >= 0)
            if tracer.enabled:
                span.args = {"launch": self.decode_launches, "chunk": chunk,
                             "active": np.count_nonzero(run)}
        return True

    def _launch_spec(self) -> bool:
        """Dispatch one speculative verify round: fetch host drafts for
        every running slot, pad them into ONE [S, K-1] proposal batch at
        the smallest decode-ladder rung covering the longest draft, and
        score all positions in one parallel forward
        (``serving.verify.k{K}``). Slots without a draft ride along with
        zero-padding — any coincidental match is still the true sampled
        token (acceptance is exact equality), so padding can only help.
        Falls back to the plain slot-stream decode scan when no source
        has a proposal or the block pool is too tight for width K."""
        host_active = self.slot_rid >= 0
        run = host_active & (self.sched_budget > 0)
        if not run.any():
            return False
        drafts: dict[int, list] = {}
        max_d = 0
        ladder_cap = _ChunkTuner.LADDER[-1] - 1
        for s in map(int, np.nonzero(run)[0]):
            cap = min(
                self.spec_lookahead,
                int(self.slot_budget[s]) - 1,  # the +1 is the bonus sample
                self.max_seq_len - int(self.lens[s]) - 1,
                ladder_cap,
            )
            if cap <= 0:
                continue
            rid = int(self.slot_rid[s])
            context = self.slot_prompt[rid].tolist()
            for ch in self.slot_tokens[s]:
                context.extend(int(t) for t in ch)
            d = self._draft_source.propose(context, cap)
            if d:
                drafts[s] = list(d)[:cap]
                max_d = max(max_d, len(drafts[s]))
        if max_d == 0:
            return self._launch()  # nothing to verify: plain decode
        K = next(c for c in _ChunkTuner.LADDER if c >= max_d + 1)
        for s in map(int, np.nonzero(run)[0]):
            need = int(self.lens[s]) + min(
                K, int(self.slot_budget[s]) + 1,
                self.max_seq_len - int(self.lens[s]),
            )
            if not self._ensure_blocks(s, need):
                # pool too tight for a K-wide verify; the plain launch
                # has its own degrade ladder (chunk->1, drop slots)
                return self._launch()
        draft_np = np.zeros((self.n_slots, K - 1), np.int32)
        for s, d in drafts.items():
            draft_np[s, : len(d)] = d
        tracer = get_tracer()
        with tracer.span("engine.launch") as span:
            self._flush_table_writes()
            fresh = K not in self._verify_progs
            prog = self._get_verify_prog(K)
            run_dev = self._dev_all_slots if run.all() else jax.device_put(run)
            pools = _pools_from(self.cache)
            with tracer.span("engine.launch.dispatch") as disp:
                (
                    toks,
                    lps,
                    new_pools,
                    self.dev_lens,
                    self.dev_active,
                    self.dev_budget,
                    self.dev_last,
                    self.dev_ntok,
                    self.dev_obs,
                ) = prog(
                    self.params,
                    pools,
                    self.dev_table,
                    self.dev_lens,
                    self.dev_active,
                    self.dev_budget,
                    self.dev_last,
                    run_dev,
                    jax.device_put(draft_np),
                    self.dev_rid,
                    self.dev_ntok,
                    self._base_key,
                    self.dev_obs,
                )
                self._rebind_pools(new_pools)
                try:
                    toks.copy_to_host_async()
                    lps.copy_to_host_async()
                except Exception:
                    pass
            # scheduled UPPER bound (the chain length is on device); the
            # verify drain resyncs sched_* to actuals before the next launch
            want = np.minimum(K, self.sched_budget) * run
            self.sched_lens += want
            self.sched_budget -= want
            self._inflight.append(
                _InFlight(
                    toks, lps, self.slot_rid.copy(), run.copy(), K, fresh,
                    disp.dur_s, kind="verify", draft=draft_np,
                )
            )
            self.spec_dispatches += 1
            self.spec_draft_tokens += sum(len(d) for d in drafts.values())
            self.decode_steps += 1  # one forward, however many positions
            self.kv_block_steps += np.count_nonzero(self.table >= 0)
            self.decode_launches += 1
            self.decode_chunk_last = K
            if tracer.enabled:
                span.args = {"launch": self.decode_launches, "chunk": K,
                             "active": np.count_nonzero(run)}
        return True

    def _drain_one(self):
        """Accept the OLDEST in-flight chunk: one blocking transfer, then
        one vectorized pass over all S slots (the device stop rule
        re-derived in numpy: accept min(first-eos+1, budget, K) tokens)."""
        tracer = get_tracer()
        with tracer.span("engine.drain") as span:
            fl = self._inflight.popleft()
            with tracer.span("engine.drain.wait") as wait:
                tok = np.asarray(fl.toks)
                lp = np.asarray(fl.lps)
            self.host_transfers += 1
            self.decode_drains += 1
            K = fl.chunk
            # a slot's tokens count only while the SAME request still owns it
            # (a slot freed by an earlier drain — and possibly re-admitted —
            # ran this chunk deactivated on device; its rows are garbage)
            valid = fl.run_mask & (self.slot_rid == fl.rid0) & (fl.rid0 >= 0)
            if fl.kind == "verify":
                # re-derive the device's chain-acceptance rule from the SAME
                # inputs: drafts 1..j accepted iff each equalled the sample
                # before it (positions past the first mismatch are resampled
                # next round from the corrected history)
                good = (tok[:, : K - 1] == fl.draft).astype(np.int64)
                chain = 1 + np.cumprod(good, axis=1).sum(axis=1)
            else:
                chain = np.full(self.n_slots, K, np.int64)
            if self.eos_id is None:
                eos_pos = np.full(self.n_slots, K, np.int64)
            else:
                is_eos = tok == self.eos_id
                has = is_eos.any(axis=1)
                eos_pos = np.where(has, is_eos.argmax(axis=1), K)
            n_emit = np.minimum(np.minimum(eos_pos + 1, self.slot_budget), chain)
            n_emit = np.where(valid, n_emit, 0)
            self.lens += n_emit
            self.slot_budget -= n_emit
            for s in map(int, np.nonzero(n_emit)[0]):
                n = int(n_emit[s])
                self.slot_tokens[s].append(tok[s, :n])
                self.slot_lps[s].append(lp[s, :n])
            fin_eos = valid & (eos_pos < n_emit)
            fin_len = valid & ~fin_eos & (self.slot_budget <= 0)
            if fl.kind == "verify":
                emitted = int(n_emit.sum())
                n_valid = int(valid.sum())
                self.spec_accepted_tokens += emitted
                if n_valid:
                    self.spec_accept_ema = (
                        0.8 * self.spec_accept_ema + 0.2 * (emitted / n_valid)
                    )
                    for s in map(int, np.nonzero(valid)[0]):
                        n = int(n_emit[s])
                        self._spec_accept_counts[n] = (
                            self._spec_accept_counts.get(n, 0) + 1
                        )
                if tracer.enabled:
                    for s in map(int, np.nonzero(valid)[0]):
                        ctx = self._slot_ctx.get(int(fl.rid0[s]))
                        if ctx is not None:
                            tracer.instant(
                                "spec_verify",
                                {"rid": int(fl.rid0[s]), "k": K,
                                 "accepted": int(n_emit[s]),
                                 **ctx_args(ctx.child())},
                            )
            for s in map(int, np.nonzero(fin_eos)[0]):
                self._free_slot(s, "eos")
            for s in map(int, np.nonzero(fin_len)[0]):
                self._free_slot(s, "length")
            if fl.kind == "verify":
                # chain breaks emit fewer tokens than were scheduled without
                # finishing the slot — resync the scheduled bounds to actuals
                # (safe: spec mode drains before every launch)
                self.sched_lens[:] = self.lens
                self.sched_budget[:] = self.slot_budget
            if tracer.enabled:
                span.args = {
                    "emitted": operator.index(n_emit.sum()),
                    "finished": np.count_nonzero(fin_eos) + np.count_nonzero(fin_len),
                }
        if self._tuner is not None and fl.kind == "decode" and not fl.fresh_compile:
            # the drain's own host work (its span less the blocking wait)
            # plus the dispatch of the chunk it settles
            self._tuner.observe(
                (span.dur_s - wait.dur_s) + fl.dispatch_s, wait.dur_s, K)

    def _inflight_ready(self) -> bool:
        try:
            return bool(self._inflight[0].toks.is_ready())
        except Exception:
            return True  # no readiness probe: treat as ready (drain early)

    @hot_path(reason="continuous-batching decode dispatch loop")
    def step(self) -> bool:
        """Admit + dispatch one decode chunk, then accept the PREVIOUS
        chunk's tokens while the new one runs (double buffering). Returns
        False when all work is done."""
        with get_tracer().span("engine.step"):
            if self.speculative:
                return self._step_spec()
            # if the previous chunk already finished on device, settle it
            # first — admissions and the next launch then see fresh slots
            # instead of riding a known-finished batch for another chunk
            if self._inflight and self._inflight_ready():
                self._drain_one()
            self._admit()
            launched = self._launch()
            if not launched:
                if self._inflight:
                    while self._inflight:
                        self._drain_one()
                    self._admit()
                    launched = self._launch()
                if not launched:
                    if self.queue and not (self.slot_rid >= 0).any():
                        # nothing in flight, yet admission failed: the pool
                        # cannot hold the front request at all — no progress
                        # is possible
                        raise RuntimeError(
                            f"block pool too small: request rid="
                            f"{self.queue[0].rid} needs "
                            f"{self.queue[0].blocks} "
                            f"blocks, pool has {self._kv_available()} free"
                        )
                    return bool(self.queue) or bool((self.slot_rid >= 0).any())
            while len(self._inflight) > 1:
                self._drain_one()
            return True

    def _step_spec(self) -> bool:
        """The speculative step: drafting reads each slot's FULL context
        on the host, so spec mode drains every in-flight dispatch before
        launching the next — it trades the legacy double-buffering for
        multi-token accepts per dispatch (the net win on transfer-bound
        decode, measured by ``BENCH_MODE=spec``)."""
        while self._inflight:
            self._drain_one()
        self._admit()
        launched = self._launch_spec()
        if not launched:
            if self.queue and not (self.slot_rid >= 0).any():
                raise RuntimeError(
                    f"block pool too small: request rid="
                    f"{self.queue[0].rid} needs "
                    f"{self.queue[0].blocks} "
                    f"blocks, pool has {self._kv_available()} free"
                )
            return bool(self.queue) or bool((self.slot_rid >= 0).any())
        while self._inflight:
            self._drain_one()
        return True

    def harvest(self) -> dict[int, FinishedRequest]:
        """Pop the requests finished SO FAR without blocking on the rest.

        First-come consumption: callers interleave ``step()`` /
        ``harvest()`` to process completions (decode + score rewards on
        the host) while the remaining slots keep decoding — the
        ``AsyncHostCollector`` harvest pattern applied to serving. A
        ``run()`` after harvesting returns only the not-yet-harvested
        completions."""
        if not self.finished:
            return {}
        out = {f.rid: f for f in self.finished}
        self.finished.clear()
        return out

    def run(self) -> dict[int, FinishedRequest]:
        """Drain the queue; returns THIS run's {rid: FinishedRequest}.

        The internal finished list is cleared — a long-lived engine
        (LLMCollector reuses one across collects) must not accumulate
        every request it ever served."""
        while self.step():
            pass
        out = {f.rid: f for f in self.finished}
        self.finished.clear()
        return out

    def reset(self) -> None:
        """Return the engine to an empty state IN PLACE: every slot freed,
        every block back in the pool, queue/finished/in-flight dropped.

        Compiled programs, the KV pools themselves (stale contents are
        unreachable once every table row is cleared and every len is 0),
        the RNG stream, and the monotone counters (``_next_rid``,
        completions, token totals) all survive — this is how the fleet
        recycles a crashed replica without paying recompilation, and why a
        request id never collides across a crash. One exception: the
        programs consume the pools they are handed (donation), so a call
        that raised after taking them left ``self.cache`` holding deleted
        arrays; each such pool is made anew here, zeroed, with the shape,
        dtype and sharding it had — the programs are keyed on those, so
        nothing recompiles."""
        n = self.n_slots
        for layer in self.cache:
            for f in _POOL_FIELDS:
                a = layer.get(f)
                if a is not None and a.is_deleted():
                    layer[f] = jnp.zeros(a.shape, a.dtype, device=a.sharding)
        if self._kvmem is not None:
            # in place: self.free_blocks stays the allocator's list object;
            # the cached tree is dropped (pool contents are unreachable)
            self._kvmem.reset()
            self._slot_lease = [None] * n
        else:
            self.free_blocks = list(range(1, self._n_pool_blocks + 1))
        self.table[:] = -1
        self.lens[:] = 0
        self.slot_rid[:] = -1
        self.slot_budget[:] = 0
        self.slot_need[:] = 0
        self.sched_lens[:] = 0
        self.sched_budget[:] = 0
        self.slot_tokens = [[] for _ in range(n)]
        self.slot_lps = [[] for _ in range(n)]
        self.slot_prompt.clear()
        # the device table keeps its entries; the next flush writes the -1s
        self.dev_lens = jnp.zeros_like(self.dev_lens)
        self.dev_active = jnp.zeros_like(self.dev_active)
        self.dev_budget = jnp.zeros_like(self.dev_budget)
        self.dev_last = jnp.zeros_like(self.dev_last)
        self.dev_rid = jnp.full_like(self.dev_rid, -1)
        self.dev_ntok = jnp.zeros_like(self.dev_ntok)
        self._slot_ctx.clear()
        self._inflight.clear()
        self.queue.clear()
        self.finished.clear()


def _table_write_fn(table, writes):
    """Write ``writes[1]`` at the flat positions ``writes[0]`` of the block
    table. One shape for any number of writes: the rows past the real
    ones carry the position ``table.size``, out of range, and are
    dropped."""
    flat = table.reshape(-1).at[writes[0]].set(writes[1], mode="drop")
    return flat.reshape(table.shape)


def _key_split_fn(key):
    """The engine stream's next key and the key one program samples with:
    ``jax.random.split`` as one registered program."""
    new, sub = jax.random.split(key)
    return new, sub


def _admit_update_fn(lens, active, budget, last, mask, new_lens, new_budget, new_last):
    """Masked full-width merge of freshly-prefilled slots into the device
    decode state (one fused program regardless of how many were admitted)."""
    return (
        jnp.where(mask, new_lens, lens),
        active | mask,
        jnp.where(mask, new_budget, budget),
        jnp.where(mask, new_last, last),
    )


def _sadmit_update_fn(lens, active, budget, last, rid, ntok, mask,
                      new_lens, new_budget, new_last, new_rid):
    """The slot-stream admit merge: same masked write, plus the per-slot
    RNG stream state — the occupying rid, and ntok = 1 because the
    prefill just sampled response token index 0."""
    return (
        jnp.where(mask, new_lens, lens),
        active | mask,
        jnp.where(mask, new_budget, budget),
        jnp.where(mask, new_last, last),
        jnp.where(mask, new_rid, rid),
        jnp.where(mask, jnp.ones_like(ntok), ntok),
    )


class LoadBalancer:
    """Route requests across engine replicas with a strategy hierarchy
    (reference torchrl/modules/llm/backends/vllm/vllm_async.py:1559
    ``LoadBalancer`` — there over Ray-actor AsyncVLLM replicas; here over
    :class:`ContinuousBatchingEngine` instances, e.g. one per host
    process or per model copy).

    Strategies, tried in order until one yields a pick:

    - ``"prefix-aware"``: hash the prompt's first ``prefix_length`` tokens
      to a replica (KV/prefix cache locality) — skipped when the chosen
      replica is overloaded (> ``overload_threshold`` x mean load, with
      the mean FLOORED AT 1.0 so single stray requests at near-idle
      traffic don't defeat stickiness) or no prompt is given;
    - ``"requests"``: fewest pending requests (queue + in-flight);
    - ``"kv-cache"``: lowest KV block-pool utilization;
    - ``"round-robin"``: next index.

    ``submit`` forwards to the chosen replica and returns
    ``(replica_index, rid)``; ``run_all`` drains every replica.

    Membership may change at runtime (the fleet swaps ``engines`` as
    replicas sicken and recover). Losing the LAST engine is a degraded
    service, not a programming error: ``select_engine``/``submit`` on an
    empty replica set raise :class:`ServiceSaturated` with
    ``retry_after_s`` — an explicit shed the routing thread survives —
    instead of the ``ValueError``/``ZeroDivisionError`` the old code hit.
    Constructing with zero engines still raises unless ``allow_empty``
    (an empty fleet at startup is usually a config bug).
    """

    STRATEGIES = ("prefix-aware", "requests", "kv-cache", "round-robin")

    def __init__(
        self,
        engines,
        strategy="prefix-aware",
        prefix_length: int = 8,
        overload_threshold: float = 1.5,
        retry_after_s: float = 0.25,
        allow_empty: bool = False,
    ):
        self.engines = list(engines)
        if not self.engines and not allow_empty:
            raise ValueError("LoadBalancer needs at least one engine")
        self.retry_after_s = retry_after_s
        strategies = [strategy] if isinstance(strategy, str) else list(strategy)
        for st in strategies:
            if st not in self.STRATEGIES:
                raise ValueError(f"unknown strategy {st!r}; want one of {self.STRATEGIES}")
        # round-robin is the unconditional terminal fallback
        if "round-robin" not in strategies:
            strategies.append("round-robin")
        self.strategies = strategies
        self.prefix_length = prefix_length
        self.overload_threshold = overload_threshold
        self._rr = 0

    # -- per-replica load signals ---------------------------------------------

    def _pending(self, eng) -> int:
        return eng.pending()

    def _kv_utilization(self, eng) -> float:
        # O(1) from the engine's free-list accounting — select_engine runs
        # per submit, so an O(blocks) table rescan here was pure overhead.
        # Prefix-cache engines report sharing-ADJUSTED free capacity
        # (cached blocks no live sequence references are one eviction from
        # free), so a pool full of reusable prefixes doesn't read as
        # pressure; plain engines fall back to the raw free list
        probe = getattr(eng, "kv_free_blocks", None)
        free = probe() if probe is not None else len(eng.free_blocks)
        used = eng._n_pool_blocks - free
        return used / max(eng._n_pool_blocks, 1)

    # -- selection -------------------------------------------------------------

    def select_engine(self, prompt=None) -> int:
        if not self.engines:
            raise ServiceSaturated(self.retry_after_s)
        loads = [self._pending(e) for e in self.engines]
        mean_load = sum(loads) / len(loads)
        for st in self.strategies:
            if st == "prefix-aware":
                if prompt is None:
                    continue
                prefix = tuple(np.asarray(prompt).reshape(-1)[: self.prefix_length].tolist())
                idx = hash(prefix) % len(self.engines)
                if loads[idx] <= self.overload_threshold * max(mean_load, 1.0):
                    return idx
                continue  # overloaded: fall through to the next strategy
            if st == "requests":
                return int(np.argmin(loads))
            if st == "kv-cache":
                return int(np.argmin([self._kv_utilization(e) for e in self.engines]))
            if st == "round-robin":
                idx = self._rr % len(self.engines)
                self._rr += 1
                return idx
        raise AssertionError("unreachable: round-robin always selects")

    # -- request surface --------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int) -> tuple[int, int]:
        idx = self.select_engine(prompt)
        return idx, self.engines[idx].submit(prompt, max_new_tokens)

    def run_all(self) -> dict[tuple[int, int], FinishedRequest]:
        """Drain every replica; keys are (replica_index, rid)."""
        out = {}
        for i, eng in enumerate(self.engines):
            for rid, f in eng.run().items():
                out[(i, rid)] = f
        return out


class ServingService:
    """The engine behind a TCP endpoint (the reference's serving shape:
    AsyncVLLM is a long-lived SERVICE actors submit to,
    vllm_async.py:180; here the transport is the framework's own
    line-delimited-JSON control plane, rl_tpu.comm.TCPCommandServer).

    A background thread drives ``engine.step()`` whenever work is
    pending; handlers and the stepper share one lock (the engine is not
    thread-safe). Commands:

    - ``submit`` {"prompt": [ids], "max_new_tokens": n} -> rid
    - ``collect`` -> {rid: {"tokens": [...], "log_probs": [...],
      "finished_reason": ...}} — finished since the last collect
    - ``stats`` -> {"pending": ..., "free_blocks": ..., "decode_steps": ...}

    Alongside the command port, a stdlib HTTP server exposes the engine's
    telemetry as Prometheus text on ``GET /metrics`` (``metrics_port=0``
    binds an ephemeral port, read back from ``metrics_address``; ``None``
    disables it). The service owns its registry by default so replica
    services never cross-publish.

    Resilience: ``max_queue`` caps admission — a submit past the cap gets
    an explicit ``{"saturated": true, "retry_after": s}`` shed reply
    instead of silently deepening the queue (clients back off and retry);
    passing a ``supervisor`` (:class:`rl_tpu.resilience.Supervisor`) puts
    the stepper thread under supervision, so an engine crash restarts the
    stepper within budget instead of wedging the service.
    """

    def __init__(self, engine: ContinuousBatchingEngine, host: str = "127.0.0.1",
                 port: int = 0, metrics_port: int | None = 0, registry=None,
                 max_queue: int | None = None, retry_after_s: float = 0.25,
                 supervisor=None):
        import threading

        from ..comm import TCPCommandServer

        self.engine = engine
        self.max_queue = max_queue
        self.retry_after_s = retry_after_s
        self._supervisor = supervisor
        self._stepper_child = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._done: dict[int, FinishedRequest] = {}
        self._error: str | None = None  # fatal stepper error, surfaced to clients
        self._server = TCPCommandServer(host=host, port=port)
        self._server.register_handler("submit", self._h_submit)
        self._server.register_handler("collect", self._h_collect)
        self._server.register_handler("stats", self._h_stats)
        from ..obs.trace import carry_context

        self._thread = threading.Thread(target=carry_context(self._loop), daemon=True)
        self._metrics_server = None
        self.registry = registry
        if metrics_port is not None:
            from ..obs import MetricsHTTPServer, MetricsRegistry

            if self.registry is None:
                self.registry = MetricsRegistry()
            # the sidecar also serves /healthz, /debug/state (the
            # engine's snapshot, bounded) and POST /profile (fires the
            # armed TriggeredProfiler's manual trigger)
            self._metrics_server = MetricsHTTPServer(
                self.registry, host=host, port=metrics_port,
                state_fn=self._debug_state,
            )
        if self.registry is not None:
            self._init_metrics(self.registry)

    def _debug_state(self) -> dict:
        """``GET /debug/state`` payload: the engine snapshot plus the
        service-side queue view — the first thing to curl on a replica
        that is scraping fine but serving slowly."""
        with self._lock:
            snap = self.engine.metrics_snapshot()
            done = len(self._done)
            error = self._error
        return {"engine": snap, "finished_unclaimed": done, "error": error}

    def _init_metrics(self, reg):
        p = "rl_tpu_serving"
        self._m_tokens = reg.counter(f"{p}_tokens_total", "tokens generated on device")
        self._m_counters = {
            name: reg.counter(f"{p}_{name}_total", help_)
            for name, help_ in (
                ("decode_steps", "decode steps dispatched"),
                ("decode_launches", "decode chunk launches"),
                ("decode_drains", "decode chunk drains"),
                ("host_transfers", "blocking device->host transfers"),
                ("prefill_token_slots", "prefill token-slots computed"),
                ("admissions", "requests admitted to slots"),
            )
        }
        self._m_completions = reg.counter(
            f"{p}_completions_total", "finished requests", labels=("reason",)
        )
        self._m_shed = reg.counter(
            f"{p}_shed_total", "submits shed with retry-after (queue saturated)"
        )
        self._m_kv_cow = reg.counter(
            f"{p}_kv_cow_copies_total", "copy-on-write KV block forks"
        )
        self._m_kv_evictions = reg.counter(
            f"{p}_kv_evictions_total", "prefix-cache blocks evicted",
            labels=("reason",),
        )
        self._m_gauges = {
            name: reg.gauge(f"{p}_{name}", help_)
            for name, help_ in (
                ("kv_utilization", "fraction of KV pool blocks in use"),
                ("kv_prefix_hit_rate", "prompt tokens served from the prefix cache"),
                ("kv_shared_blocks", "resident KV blocks referenced by live sequences"),
                ("queue_depth", "requests waiting for a slot"),
                ("active_slots", "slots decoding"),
                ("pending", "queued + in-flight requests"),
                ("decode_chunk", "last decode chunk size K"),
                ("tuner_k", "chunk auto-tuner's current K"),
                ("tokens_per_second", "decode throughput since last scrape"),
                ("spec_accept_ema", "accepted tokens per verify dispatch (EMA)"),
                ("spec_draft_hit_rate", "draft-source queries that proposed"),
            )
        }
        self._m_spec = {
            name: reg.counter(f"{p}_{name}_total", help_)
            for name, help_ in (
                ("spec_dispatches", "speculative verify dispatches"),
                ("spec_draft_tokens", "tokens proposed by the draft source"),
                ("spec_accepted_tokens", "drafted tokens accepted by verify"),
            )
        }
        self._m_spec_accepted = reg.histogram(
            f"{p}_spec_accepted_per_dispatch",
            "tokens emitted per verify dispatch (chain length incl. bonus)",
            buckets=(1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 32.0),
        )
        self._spec_counts_seen: dict[int, int] = {}
        self._tps_last: tuple[float, float] | None = None
        reg.register_collector(self._update_metrics)

    def _update_metrics(self):
        with self._lock:
            snap = self.engine.metrics_snapshot()
        for name, c in self._m_counters.items():
            c.set_total(snap[name])
        self._m_tokens.set_total(snap["tokens_generated"])
        self._m_completions.set_total(snap["completions_eos"], {"reason": "eos"})
        self._m_completions.set_total(snap["completions_length"], {"reason": "length"})
        for name in ("kv_utilization", "queue_depth", "active_slots", "pending",
                     "decode_chunk"):
            self._m_gauges[name].set(float(snap[name]))
        if "kv_prefix_hit_rate" in snap:  # engine runs the prefix tier
            self._m_gauges["kv_prefix_hit_rate"].set(float(snap["kv_prefix_hit_rate"]))
            self._m_gauges["kv_shared_blocks"].set(float(snap["kv_shared_blocks"]))
            self._m_kv_cow.set_total(snap["kv_cow_copies_total"])
            for reason, n in snap["kv_evictions"].items():
                self._m_kv_evictions.set_total(n, {"reason": reason})
        if snap["tuner_k"] is not None:
            self._m_gauges["tuner_k"].set(float(snap["tuner_k"]))
        if "spec_dispatches" in snap:  # engine runs speculative decoding
            for name, c in self._m_spec.items():
                c.set_total(snap[name])
            self._m_gauges["spec_accept_ema"].set(float(snap["spec_accept_ema"]))
            self._m_gauges["spec_draft_hit_rate"].set(
                float(snap.get("spec_draft_hit_rate", 0.0))
            )
            # the engine keeps {chain length -> dispatch count}; observe
            # only the delta since the last scrape
            for n, total in snap["spec_accept_counts"].items():
                seen = self._spec_counts_seen.get(n, 0)
                for _ in range(total - seen):
                    self._m_spec_accepted.observe(float(n))
                self._spec_counts_seen[n] = total
        now = time.monotonic()
        if self._tps_last is not None:
            t0, tok0 = self._tps_last
            dt = now - t0
            if dt > 0:
                self._m_gauges["tokens_per_second"].set(
                    (snap["tokens_generated"] - tok0) / dt
                )
        self._tps_last = (now, snap["tokens_generated"])

    # -- lifecycle -------------------------------------------------------------

    @property
    def address(self):
        return self._server.address

    @property
    def metrics_address(self):
        if self._metrics_server is None:
            return None
        return self._metrics_server.address

    def start(self) -> "ServingService":
        self._server.start()
        if self._supervisor is not None:
            self._stepper_child = self._supervisor.spawn(
                "serving-stepper", self._loop_supervised,
                on_giveup=self._on_stepper_giveup,
            )
        else:
            self._thread.start()
        if self._metrics_server is not None:
            self._metrics_server.start(supervisor=self._supervisor)
        return self

    def shutdown(self):
        self._stop.set()
        if self._stepper_child is not None:
            self._stepper_child.stop(timeout=10)
        else:
            self._thread.join(timeout=10)
        self._server.shutdown()
        if self._metrics_server is not None:
            self._metrics_server.shutdown()
        if self.registry is not None:
            self.registry.unregister_collector(self._update_metrics)

    # -- stepper ---------------------------------------------------------------

    @hot_path(reason="serving stepper thread")
    def _loop(self):
        import time as _time
        import traceback as _tb

        from ..resilience.faults import fault_point

        while not self._stop.is_set():
            fault_point("serving.stepper")  # chaos site, outside the lock
            with self._lock:
                busy = self.engine.pending() > 0
                if busy:
                    try:
                        self.engine.step()
                    except Exception:
                        # a dead stepper must not look like a healthy
                        # service: record and refuse further work
                        self._error = _tb.format_exc(limit=5)
                        return
                    self._done.update(
                        {f.rid: f for f in self.engine.finished}
                    )
                    self.engine.finished.clear()
            if not busy:
                _time.sleep(0.005)

    @hot_path(reason="serving stepper thread (supervised)")
    def _loop_supervised(self):
        """Supervised variant: let exceptions escape so the supervisor
        restarts the stepper instead of recording-and-wedging."""
        import time as _time

        from ..resilience.faults import fault_point

        while not self._stop.is_set():
            fault_point("serving.stepper")
            with self._lock:
                busy = self.engine.pending() > 0
                if busy:
                    self.engine.step()
                    self._done.update({f.rid: f for f in self.engine.finished})
                    self.engine.finished.clear()
            if not busy:
                _time.sleep(0.005)

    def _on_stepper_giveup(self, exc: BaseException) -> None:
        import traceback as _tb

        self._error = "".join(
            _tb.format_exception(type(exc), exc, exc.__traceback__, limit=5)
        )

    # -- handlers --------------------------------------------------------------

    def _h_submit(self, payload):
        with self._lock:
            if self._error is not None:
                raise RuntimeError(f"serving stepper died:\n{self._error}")
            if self.max_queue is not None and self.engine.pending() >= self.max_queue:
                # shed, don't hang: an explicit retry-after beats a queue
                # that grows until every caller times out
                if getattr(self, "_m_shed", None) is not None:
                    self._m_shed.inc()
                from ..obs import get_tracer

                get_tracer().instant(
                    "load_shed",
                    {"pending": self.engine.pending(), "max_queue": self.max_queue},
                )
                return {"saturated": True, "retry_after": self.retry_after_s}
            return self.engine.submit(
                np.asarray(payload["prompt"], np.int32),
                int(payload["max_new_tokens"]),
            )

    def _h_collect(self, payload):
        """Return (and remove) finished requests. ``payload`` may carry
        {"rids": [...]} to take ONLY those — concurrent waiters must not
        drain each other's results; with no rids, takes everything."""
        with self._lock:
            if self._error is not None and not self._done:
                raise RuntimeError(f"serving stepper died:\n{self._error}")
            want = payload.get("rids") if isinstance(payload, dict) else None
            rids = list(self._done) if want is None else [
                r for r in map(int, want) if r in self._done
            ]
            out = {
                str(rid): {
                    "tokens": self._done[rid].tokens.tolist(),
                    "log_probs": self._done[rid].log_probs.tolist(),
                    "finished_reason": self._done[rid].finished_reason,
                }
                for rid in rids
            }
            for rid in rids:
                del self._done[rid]
        return out

    def _h_stats(self, _payload):
        with self._lock:
            return {
                "pending": self.engine.pending(),
                "free_blocks": len(self.engine.free_blocks),
                "decode_steps": self.engine.decode_steps,
                "error": self._error,
            }


class ServiceSaturated(RuntimeError):
    """The service shed the submit; retry after ``retry_after`` seconds."""

    def __init__(self, retry_after: float):
        super().__init__(f"service saturated, retry after {retry_after}s")
        self.retry_after = retry_after


class RemoteEngine:
    """Client for :class:`ServingService` — the same submit surface over
    TCP (reference: actors talk to AsyncVLLM via Ray handles).

    ``retry`` (a :class:`rl_tpu.resilience.RetryPolicy`) makes the
    transport survivable. ``submit`` is NOT transport-idempotent (a dropped
    reply would re-enqueue the prompt), so it never retries on transport
    errors — but it DOES honor the service's explicit shed replies:
    ``max_shed_retries`` waits ``retry_after`` and resubmits (the shed
    reply proves the request was rejected, so resubmitting is safe).
    """

    def __init__(self, host: str, port: int, timeout: float = 30.0, retry=None,
                 max_shed_retries: int = 8):
        from ..comm import TCPCommandClient

        self._client = TCPCommandClient(host, port, timeout=timeout, retry=retry)
        self._retry = retry
        self.max_shed_retries = max_shed_retries

    def submit(self, prompt, max_new_tokens: int) -> int:
        import time as _time

        payload = {"prompt": np.asarray(prompt, np.int32).tolist(),
                   "max_new_tokens": int(max_new_tokens)}
        for _ in range(self.max_shed_retries + 1):
            out = self._client.call("submit", payload, idempotent=False)
            if isinstance(out, dict) and out.get("saturated"):
                retry_after = float(out.get("retry_after", 0.25))
                _time.sleep(retry_after)
                continue
            return int(out)
        raise ServiceSaturated(retry_after)

    def collect(self, rids=None) -> dict[int, dict]:
        # collect REMOVES results server-side: a reply dropped after the
        # handler ran loses them for good, so never auto-retry it
        payload = None if rids is None else {"rids": [int(r) for r in rids]}
        return {
            int(k): v
            for k, v in self._client.call("collect", payload, idempotent=False).items()
        }

    def stats(self) -> dict:
        return self._client.call("stats")

    def wait_all(self, rids, poll_s: float = 0.05, timeout: float = 120.0) -> dict:
        """Poll ``collect`` until every rid finished. The poll interval
        doubles from ``poll_s`` up to a 1 s cap (long generations don't
        deserve a 50 ms busy-poll), charged against one shared deadline."""
        import time as _time

        from ..resilience.retry import Deadline

        dl = (
            self._retry.deadline(timeout)
            if self._retry is not None
            else Deadline(timeout)
        )
        want = set(rids)
        got: dict[int, dict] = {}
        delay = poll_s
        while want - set(got) and not dl.expired:
            got.update(self.collect(sorted(want - set(got))))
            if want - set(got):
                _time.sleep(min(delay, max(dl.remaining(), 0.0)))
                delay = min(delay * 2.0, 1.0)
        missing = want - set(got)
        if missing:
            raise TimeoutError(f"requests {sorted(missing)} not finished in {timeout}s")
        return {r: got[r] for r in want}

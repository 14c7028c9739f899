"""The rlint ``--ir`` program set: tiny real configurations of the
framework's registered hot programs, compiled through an isolated
ProgramRegistry so every one passes the IR auditor.

The AST rules lint source; the R100-series rules need *lowered*
programs, which only exist once something registers and compiles them.
This module is the CLI's way to materialize that set without a bench or
a test run: shrunken-but-real serving / Anakin / off-policy builds, each
driven one step so the registry pays its normal ``lower().compile()``
(and therefore its audit) per program.

Store semantics are the interesting part: with ``fresh_store=True``
(``tools/rlint.py --ir``) every program compiles, so every program is
audited. With ``fresh_store=False`` (``--diff`` mode) the persistent
executable store is used as-is — programs whose fingerprint/signature
did not change load their serialized executable and *skip* the audit,
which is exactly the "only re-audit programs whose fingerprint changed"
contract.
"""

from __future__ import annotations

import contextlib
import io
import tempfile
import traceback
from typing import Any, Callable, Iterable

__all__ = ["AUDIT_TARGETS", "check_pool_programs", "check_spec_programs", "run_ir_audit"]


def _build_serving() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import ContinuousBatchingEngine, TransformerConfig, TransformerLM

    cfg = TransformerConfig(
        vocab_size=97, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    m = TransformerLM(cfg)
    params = m.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(
        m, params, n_slots=2, block_size=8, n_blocks=17,
        prompt_buckets=(16,), greedy=True,
    )
    eng.submit(np.arange(5) % 97, 4)
    eng.run()


def check_spec_programs(registry: Any) -> None:
    """The speculation-stays-compile-free gate: every slot-stream /
    speculative program family the engine can ever register must ride
    the EXISTING decode ladder — a verify or sdecode width outside
    ``_ChunkTuner.LADDER``, or a spec-path program name outside the
    known families, means the speculative path invented a new program
    signature and broke the steady-state CompileDelta == 0 contract.
    Raises ``RuntimeError`` (rlint --ir reports it and exits 1)."""
    from ..models.serving import _ChunkTuner

    ladder = set(_ChunkTuner.LADDER)
    known = ("serving.sprefill.", "serving.spprefill.", "serving.sadmit_update")
    for name in registry.names():
        if name.startswith(("serving.verify.k", "serving.sdecode.k")):
            k = name.rsplit("k", 1)[1]
            if not k.isdigit() or int(k) not in ladder:
                raise RuntimeError(
                    f"speculative program {name!r} is off the decode ladder "
                    f"{sorted(ladder)} — speculation must stay compile-free"
                )
        elif name.startswith("serving.s") and not name.startswith(known):
            raise RuntimeError(
                f"unknown speculative-path program family: {name!r} — new "
                "signatures outside the warmed ladder break CompileDelta == 0"
            )


def _build_serving_spec() -> None:
    """Speculative serving: prefix-cache engine with speculation on, the
    same prompt served twice so the second pass drafts from the first's
    donated continuation and dispatches a real ``serving.verify.k{K}``.
    Ends with the ladder check so rlint --ir gates the compile-free
    contract, not just the lowered IR."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import ContinuousBatchingEngine, TransformerConfig, TransformerLM
    from .registry import get_program_registry

    cfg = TransformerConfig(
        vocab_size=97, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    m = TransformerLM(cfg)
    params = m.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(
        m, params, n_slots=2, block_size=8, n_blocks=17,
        prompt_buckets=(16,), greedy=True, prefix_cache=True,
        speculative=True, spec_lookahead=3,
    )
    prompt = np.arange(5) % 97
    eng.submit(prompt, 6)
    eng.run()  # donates the continuation into the radix tree
    eng.submit(prompt, 6)
    eng.run()  # replay: drafts from the tree, dispatches a verify
    if eng.spec_dispatches < 1:
        raise RuntimeError("speculative audit build never dispatched a verify")
    check_spec_programs(get_program_registry())


# the engine's program families that take the KV pools and return them
POOL_PROGRAM_FAMILIES = (
    "serving.decode.k", "serving.sdecode.k", "serving.verify.k",
    "serving.prefill.", "serving.pprefill.", "serving.sprefill.",
    "serving.spprefill.", "serving.cowcopy.n",
)


def check_pool_programs(auditor: Any, families: Iterable[str] = ()) -> None:
    """The pools-are-consumed gate over what ``auditor`` has audited: every
    engine program that takes the KV pools must declare them donated, and
    the executable must alias EVERY donated pool to an output (R102 fires
    only when none is; one pool the compiler declines to alias is a
    whole-pool copy a call). ``families`` must each have been audited.
    Raises ``RuntimeError`` (rlint --ir reports it and exits 1)."""
    seen = set()
    for rep in auditor._snapshot():
        family = next((f for f in POOL_PROGRAM_FAMILIES if rep.name.startswith(f)), None)
        if family is None:
            continue
        seen.add(family)
        if not 0 < rep.donated_declared == rep.donated_honored:
            raise RuntimeError(
                f"engine program {rep.name!r} donates {rep.donated_declared} "
                f"pool(s) and the executable aliases {rep.donated_honored}: "
                "every call copies a pool whole into a fresh output"
            )
    missing = sorted(set(families) - seen)
    if missing:
        raise RuntimeError(f"pool program families never audited: {missing}")


def _build_serving_pools() -> None:
    """The two pool-program families the other serving builds do not
    reach: the partial prefill on the legacy stream (prefix cache, two
    prompts parting inside a block, so the copy-on-write fork runs too)
    and the full prefill on the slot stream. Ends with the donation gate
    over every engine program audited so far (``serving`` and
    ``serving_spec`` run before it in the whole set)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..analysis.ir import get_ir_auditor
    from ..models import ContinuousBatchingEngine, TransformerConfig, TransformerLM
    from .registry import get_program_registry

    cfg = TransformerConfig(
        vocab_size=97, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32,
    )
    m = TransformerLM(cfg)
    params = m.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    kw = dict(n_slots=2, block_size=8, n_blocks=17, prompt_buckets=(16,), greedy=True)
    eng = ContinuousBatchingEngine(m, params, prefix_cache=True, **kw)
    for tail in (1, 2):
        eng.submit(np.concatenate([np.arange(11), [tail]]) % 97, 4)
        eng.run()
    if eng.metrics_snapshot()["kv_cow_copies_total"] < 1:
        raise RuntimeError("pool audit build never forked a block")
    eng = ContinuousBatchingEngine(m, params, slot_rng=True, **kw)
    eng.submit(np.arange(5) % 97, 4)
    eng.run()
    check_pool_programs(
        get_program_registry().auditor or get_ir_auditor(),
        ("serving.pprefill.", "serving.cowcopy.n", "serving.sprefill.", "serving.sdecode.k"),
    )


def _build_serving_kernels() -> None:
    """Kernel-tier serving: the same tiny engine lowered WITH the Pallas
    kernels (interpret mode — real kernel lowering without a chip), so
    ``rlint --ir`` audits the kernel-bearing jaxprs: R106 sees each
    declared ``kernel_hot_path`` satisfied, and the cost model prices the
    ``pallas_call`` targets instead of zeroing them. Different model dims
    than the stock build keep the two engines' program keys distinct in
    a shared store."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import ContinuousBatchingEngine, TransformerConfig, TransformerLM

    prev = os.environ.get("RL_TPU_KERNELS_INTERPRET")
    os.environ["RL_TPU_KERNELS_INTERPRET"] = "1"
    try:
        cfg = TransformerConfig(
            vocab_size=97, d_model=48, n_layers=1, n_heads=2, d_ff=64,
            max_seq_len=64, dtype=jnp.float32,
        )
        m = TransformerLM(cfg)
        params = m.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
        eng = ContinuousBatchingEngine(
            m, params, n_slots=2, block_size=8, n_blocks=17,
            prompt_buckets=(16,), greedy=True,
        )
        eng.submit(np.arange(5) % 97, 4)
        eng.run()
    finally:
        if prev is None:
            os.environ.pop("RL_TPU_KERNELS_INTERPRET", None)
        else:
            os.environ["RL_TPU_KERNELS_INTERPRET"] = prev


def _build_anakin() -> None:
    import jax

    from ..modules import (
        MLP,
        Categorical,
        ProbabilisticActor,
        TDModule,
        ValueOperator,
    )
    from ..objectives import ClipPPOLoss
    from ..trainers import AnakinConfig, AnakinProgram

    actor = ProbabilisticActor(
        TDModule(MLP(out_features=2, num_cells=(16, 16)),
                 ["observation"], ["logits"]),
        Categorical,
        dist_keys=("logits",),
    )
    critic = ValueOperator(MLP(out_features=1, num_cells=(16, 16)))
    loss = ClipPPOLoss(actor, critic)
    loss.make_value_estimator(gamma=0.99, lmbda=0.95)
    policy = lambda p, td, k: actor(p["actor"], td, k)  # noqa: E731
    cfg = AnakinConfig(
        num_envs=4, unroll_length=4, steps_per_dispatch=1,
        num_epochs=1, minibatch_size=8,
    )
    prog = AnakinProgram("cartpole", policy, loss, cfg,
                         device_metrics=False, max_episode_steps=10)
    ts = prog.init(jax.random.key(0))
    prog.dispatch(ts)


class _AuditHostEnv:
    """Deterministic 4-obs / 2-action host env — just enough spec surface
    for the async trainer's state layout; never actually stepped (the
    collector is not started, only :meth:`AsyncOffPolicyTrainer.aot_warmup`
    runs)."""

    def __init__(self):
        import numpy as np

        from ..data.specs import Categorical, Composite, Unbounded

        self._np = np
        self.observation_spec = Composite(observation=Unbounded((4,)))
        self.action_spec = Categorical(2)

    def reset(self, seed=0):
        return {"observation": self._np.zeros(4, self._np.float32)}

    def step(self, action):
        return self.reset(), 0.0, False, False

    def close(self):
        pass


def _build_offpolicy() -> None:
    import jax

    from ..collectors import AsyncHostCollector, ThreadedEnvPool
    from ..data import DeviceStorage, ReplayBuffer
    from ..modules import MLP, TDModule
    from ..objectives import DQNLoss
    from ..trainers import AsyncOffPolicyTrainer, OffPolicyConfig

    qnet = TDModule(MLP(out_features=2, num_cells=(16, 16)),
                    ["observation"], ["action_value"])
    loss = DQNLoss(qnet, gamma=0.99)
    pool = ThreadedEnvPool([_AuditHostEnv for _ in range(2)])
    coll = AsyncHostCollector(pool, None, frames_per_batch=16)
    buffer = ReplayBuffer(DeviceStorage(256))
    trainer = AsyncOffPolicyTrainer(
        coll, loss, buffer,
        OffPolicyConfig(batch_size=16, utd_ratio=1, init_random_frames=16),
    )
    try:
        ts = trainer.init(jax.random.key(0))
        # aot_warmup compiles the donated K-update scan — the program the
        # run loop dispatches — without starting the collector thread
        trainer.aot_warmup(ts)
    finally:
        pool.close()


AUDIT_TARGETS: dict[str, Callable[[], None]] = {
    "serving": _build_serving,
    "serving_spec": _build_serving_spec,
    "serving_pools": _build_serving_pools,
    "serving_kernels": _build_serving_kernels,
    "anakin": _build_anakin,
    "offpolicy": _build_offpolicy,
}


def run_ir_audit(
    include: Iterable[str] | None = None,
    *,
    auditor: Any = None,
    fresh_store: bool = True,
    quiet: bool = True,
) -> tuple[Any, dict]:
    """Compile the audit set through an isolated registry; returns
    ``(auditor, status)`` where status maps target name to ``"ok"`` or
    the failure summary (a broken builder is reported, never raised —
    the lint gate should judge findings, not environment quirks)."""
    from ..analysis.ir import IRAuditor
    from .registry import ProgramRegistry, set_program_registry
    from .store import ExecutableStore

    if auditor is None:
        auditor = IRAuditor()
    store = (
        ExecutableStore(root=tempfile.mkdtemp(prefix="rlint_ir_"))
        if fresh_store
        else None
    )
    registry = ProgramRegistry(store=store, auditor=auditor)
    prev = set_program_registry(registry)
    status: dict[str, str] = {}
    try:
        for name in include if include is not None else AUDIT_TARGETS:
            build = AUDIT_TARGETS.get(name)
            if build is None:
                status[name] = f"unknown target (want one of {sorted(AUDIT_TARGETS)})"
                continue
            try:
                ctx = (
                    contextlib.redirect_stdout(io.StringIO())
                    if quiet
                    else contextlib.nullcontext()
                )
                with ctx:
                    build()
                status[name] = "ok"
            except Exception as e:  # noqa: BLE001 — reported, not raised
                status[name] = f"build failed: {type(e).__name__}: {e}"
                if not quiet:
                    traceback.print_exc()
    finally:
        set_program_registry(prev)
    return auditor, status

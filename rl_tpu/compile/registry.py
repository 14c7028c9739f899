"""ProgramRegistry: every hot jitted program, registered, AOT-compiled,
and persistently cached.

``jax.jit`` hides three costs behind the first call: trace, lower, and
backend-compile — 20-40s per fused program on the cpu tier (ROADMAP item
5), multiplied by every mesh topology, fleet member, and chunk size. The
registry replaces anonymous ``jax.jit(fn)`` sites with *named* programs:

- :meth:`ProgramRegistry.register` returns a :class:`CachedProgram` that
  is called exactly like the jitted function, but routes every dispatch
  through an explicit executable table instead of jit's hidden dispatch
  cache. A signature miss resolves store-load → lower+compile (never the
  reverse), so a warm process *loads* serialized executables and skips
  ``lower()`` entirely.
- :meth:`CachedProgram.add_signature` records the program's abstract call
  signature (``jax.ShapeDtypeStruct`` pytrees); :meth:`aot_warmup` then
  drives ``jit.lower().compile()`` (or the store load) for the whole
  registered set — optionally on a background thread, so warm-up overlaps
  host setup (env construction, checkpoint IO, TCP binds).
- every compile is attributed to its program name via
  :func:`~rl_tpu.compile.metrics.compile_scope`, feeding the
  ``rl_tpu_compiles_total{program}`` counter and the per-compile tracer
  span (observability satellite).

The registry holds programs by *weak* reference: a ``CachedProgram``
usually closes over its trainer/engine (bound methods), and a process
that constructs many short-lived engines (the test suite, a fleet churn
bench) must not leak every one of them through a global table.

Opt-outs: ``RL_TPU_NO_AOT=1`` keeps registration (names, metrics) but
dispatches through plain ``jax.jit``; the persistent layers have their
own knobs (``RL_TPU_NO_EXEC_STORE``, ``RL_TPU_NO_COMPILE_CACHE``).
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Iterable

from .metrics import compile_scope, install_compile_listener
from .store import ExecutableStore, default_store

__all__ = [
    "CachedProgram",
    "ProgramRegistry",
    "WarmupHandle",
    "get_program_registry",
    "set_program_registry",
]

_ENV_NO_AOT = "RL_TPU_NO_AOT"
_ENV_NO_ATTR = "RL_TPU_NO_ATTRIBUTION"
_ENV_NO_IR_AUDIT = "RL_TPU_NO_IR_AUDIT"
_ENV_PEAK_FLOPS = "RL_TPU_PEAK_FLOPS"
_ENV_PEAK_BW = "RL_TPU_PEAK_BYTES_PER_S"
_ATTR_SAMPLE_EVERY = 8
_WARMUP_THREADS = 4  # signatures an aot_warmup() builds or loads at a time


def _attr_worker(q) -> None:
    """Attribution drain loop (its own daemon thread, never a dispatch
    thread): block until the sampled dispatch's first output leaf is
    device-ready, then credit the elapsed wall time to the program. The
    host sync lives HERE, off every hot path — dispatch only enqueues.

    The loop keeps nothing of a sample while it waits for the next: a
    program pins its function's closure (``grpo.update`` the whole
    trainer, a serving program its engine) and the leaf a device buffer,
    and the last sample of a run would hold them for the process's life."""
    while True:
        item = q.get()
        if item is None:
            return
        _attr_credit(*item)
        del item


def _attr_credit(ref, t0: float, leaf: Any) -> None:
    import jax

    try:
        jax.block_until_ready(leaf)
    except Exception:
        return
    dt = time.perf_counter() - t0
    prog = ref()
    if prog is None:
        return
    with prog._lock:
        prog.stats["device_s"] += dt
        prog.stats["device_samples"] += 1
        prog.stats["device_flops"] += prog.flops_per_call
    _notify_dispatch(prog, dt)


def _notify_dispatch(prog: "CachedProgram", dt: float) -> None:
    """Fan one sampled dispatch timing out to the armed profiler ring
    and drift detector (PR 18). Disarmed-by-default: each hook is a
    single None check when off. Runs ONLY on the attribution worker
    thread — never a dispatch thread — so the EWMA/z-score math and any
    triggered capture stay off every hot path (R001)."""
    try:
        from ..obs.drift import get_drift_detector
        from ..obs.profiling import get_profiler

        p = get_profiler()
        if p is not None:
            p.record_dispatch(prog.name, dt)
        d = get_drift_detector()
        if d is not None:
            d.observe(prog.name, dt, prog=prog)
    except Exception:
        pass


class _Attribution:
    """Sampled per-program device-time accounting.

    Every ``_ATTR_SAMPLE_EVERY``-th dispatch of a :class:`CachedProgram`
    enqueues ``(weakref(prog), t0, first_output_leaf)`` on a bounded
    queue; a lazily-started worker thread waits for the leaf and folds
    ``device_s`` / ``device_samples`` / ``device_flops`` into the
    program's ``stats`` (so :meth:`ProgramRegistry.stats` — and the
    flight recorder's ``programs.json`` — pick them up for free).
    Holding the leaf briefly pins its buffer; sampling plus the bounded
    queue keeps that footprint to a handful of arrays. A full queue
    drops the sample — that is just the sampler running behind, not an
    error. Opt out entirely with ``RL_TPU_NO_ATTRIBUTION=1``."""

    def __init__(self, maxsize: int = 256):
        import queue

        self._q: Any = queue.Queue(maxsize=maxsize)
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()

    def submit(self, prog: "CachedProgram", t0: float, out: Any) -> None:
        if os.environ.get(_ENV_NO_ATTR, "") not in ("", "0"):
            return
        import jax

        leaves = jax.tree_util.tree_leaves(out)
        if not leaves:
            return
        self._ensure_thread()
        try:
            self._q.put_nowait((weakref.ref(prog), t0, leaves[0]))
        except Exception:
            pass

    def _ensure_thread(self) -> None:
        if self._thread is not None:
            return
        with self._lock:
            if self._thread is None:
                t = threading.Thread(
                    target=_attr_worker, args=(self._q,), name="prog-attr", daemon=True
                )
                t.start()
                self._thread = t


_ATTR = _Attribution()


def _memkey(args: tuple) -> tuple:
    """Cheap per-call signature: tree structure + per-leaf shape/dtype.

    This is the in-memory executable-table key, computed on EVERY
    dispatch — so no hashing, no sharding reprs, just the tuple jit's own
    dispatch would build. Shardings are deliberately excluded: one
    CachedProgram belongs to one trainer/engine, which pins placements at
    construction (the persistent-store key DOES include them)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (
        treedef,
        tuple(
            (getattr(x, "shape", None), str(getattr(x, "dtype", type(x).__name__)))
            for x in leaves
        ),
    )


class CachedProgram:
    """A registered program: called like ``jax.jit(fn)``, dispatched via
    an explicit executable table with store-load → compile resolution.

    ``stats`` counts the events the cold-start tests assert on:
    ``compiles`` (entered ``lower()``), ``loads`` (deserialized from the
    store), ``aot_hits`` (dispatched straight to a cached executable).
    """

    def __init__(
        self,
        name: str,
        fn: Callable,
        *,
        registry: "ProgramRegistry",
        fingerprint: str = "",
        ir_contract: dict | None = None,
        **jit_kwargs: Any,
    ):
        import jax

        self.name = name
        self.fn = fn
        self.fingerprint = fingerprint
        self.jit_kwargs = jit_kwargs
        self.ir_contract = dict(ir_contract or {})
        self._registry = registry
        self._jit = jax.jit(fn, **jit_kwargs)
        self._lock = threading.Lock()
        self._compiled: dict[tuple, Any] = {}
        self._unvalidated: set[tuple] = set()  # store-loads before 1st call
        self._signatures: list[tuple] = []
        self.flops_per_call = 0.0  # from cost_analysis, when the backend has it
        self.static_flops = 0.0    # from the IR auditor's static cost model
        self.static_bytes = 0.0
        self.ir_report: Any = None  # latest rl_tpu.analysis.ir.ProgramAudit
        self._attr_tick = 0
        self.stats = {
            "calls": 0,
            "aot_hits": 0,
            "compiles": 0,
            "loads": 0,
            "jit_calls": 0,
            "compile_s": 0.0,
            "load_s": 0.0,
            "device_s": 0.0,
            "device_samples": 0,
            "device_flops": 0.0,
        }

    # -- keys ------------------------------------------------------------

    def _store_extra(self) -> str:
        # donation/shardings change the executable; they are part of the
        # persistent identity (sorted for dict-order stability)
        return repr(sorted((k, repr(v)) for k, v in self.jit_kwargs.items()))

    def store_key(self, args: tuple) -> str:
        return self._registry.store.key_for(
            self.name, args, fingerprint=self.fingerprint, extra=self._store_extra()
        )

    # -- warm-up ---------------------------------------------------------

    def add_signature(self, *abstract_args: Any) -> "CachedProgram":
        """Record an abstract call signature (``ShapeDtypeStruct`` trees)
        for :meth:`warmup` / registry-level ``aot_warmup``. Idempotent on
        shape/dtype, so re-warming (restart paths call it again) doesn't
        grow the list."""
        mk = _memkey(abstract_args)
        with self._lock:
            if all(_memkey(s) != mk for s in self._signatures):
                self._signatures.append(abstract_args)
        return self

    @property
    def signatures(self) -> list[tuple]:
        with self._lock:
            return list(self._signatures)

    def warmup(self, *args: Any) -> tuple[str, float]:
        """Materialize the executable for one signature (abstract or
        concrete args — only shapes/dtypes are read). Returns
        ``(source, seconds)`` with source one of ``"memory"``/``"store"``
        /``"compile"``."""
        mk = _memkey(args)
        with self._lock:
            if mk in self._compiled:
                return ("memory", 0.0)
        key = self.store_key(args)
        t0 = time.perf_counter()
        prog = self._registry.store.load(key)
        if prog is not None:
            dt = time.perf_counter() - t0
            with self._lock:
                self._compiled[mk] = prog
                self._unvalidated.add(mk)
                self.stats["loads"] += 1
                self.stats["load_s"] += dt
            self._note_flops(prog)
            return ("store", dt)
        prog, dt = self._compile(args)
        return ("compile", dt)

    def _compile(self, args: tuple) -> tuple[Any, float]:
        mk = _memkey(args)
        t0 = time.perf_counter()
        with compile_scope(self.name):
            prog = self._jit.lower(*args).compile()
        dt = time.perf_counter() - t0
        with self._lock:
            self._compiled[mk] = prog
            self._unvalidated.discard(mk)
            self.stats["compiles"] += 1
            self.stats["compile_s"] += dt
        self._registry.store.save(
            key=self.store_key(args), compiled=prog, meta={"name": self.name}
        )
        self._note_flops(prog)
        self._ir_audit(args, mk, prog)
        return prog, dt

    # -- IR audit --------------------------------------------------------

    def _donated_leaf_count(self, args: tuple) -> int:
        import jax

        nums = self.jit_kwargs.get("donate_argnums")
        if nums is None:
            return 0
        if isinstance(nums, int):
            nums = (nums,)
        n = 0
        for i in nums:
            if 0 <= i < len(args):
                n += len(jax.tree_util.tree_leaves(args[i]))
        return n

    def _ir_audit(self, args: tuple, mk: tuple, compiled: Any) -> None:
        """Audit the program we just lowered+compiled (rlint deep tier).

        Runs ONLY on the compile path — a store-loaded executable was
        audited by the process that first built it — so dispatch never
        pays for this. Extraction is best-effort (``trace``/``as_text``
        are feature-detected); the rules themselves are pure and the
        whole thing is fenced so an audit bug can never break a build.
        Opt out with ``RL_TPU_NO_IR_AUDIT=1``.
        """
        if os.environ.get(_ENV_NO_IR_AUDIT, "") not in ("", "0"):
            return
        try:
            auditor = self._registry.auditor
            if auditor is None:
                from ..analysis.ir import get_ir_auditor

                auditor = get_ir_auditor()
            jaxpr = None
            trace = getattr(self._jit, "trace", None)
            if callable(trace):
                try:
                    jaxpr = trace(*args).jaxpr
                except Exception:
                    jaxpr = None
            try:
                text = compiled.as_text()
            except Exception:
                text = ""
            donate = self.jit_kwargs.get("donate_argnums")
            declared = donate is not None and donate != ()
            declared = declared or bool(self.jit_kwargs.get("donate_argnames"))
            report = auditor.audit(
                name=self.name,
                fingerprint=self.fingerprint,
                jaxpr=jaxpr,
                compiled_text=text,
                donated_leaves=self._donated_leaf_count(args),
                donation_declared=declared,
                contract=self.ir_contract,
                sig_key=mk,
            )
            with self._lock:
                self.ir_report = report
                if report.cost is not None:
                    self.static_flops = report.cost.flops
                    self.static_bytes = report.cost.bytes
        except Exception:
            pass

    def _note_flops(self, prog: Any) -> None:
        # cost_analysis is backend-dependent (absent on some platforms,
        # a one-element list on others) — best effort, never raises
        try:
            ca = prog.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if isinstance(ca, dict):
                flops = float(ca.get("flops", 0.0))
                if flops > 0.0:
                    self.flops_per_call = flops
        except Exception:
            pass

    # -- dispatch --------------------------------------------------------

    def _dispatch(self, prog: Callable, args: tuple):
        """One executable dispatch, sampled for device-time attribution.
        The sampled path only stamps a timestamp and enqueues the output
        — the ready-wait happens on the attribution worker thread."""
        self._attr_tick += 1
        if self._attr_tick % _ATTR_SAMPLE_EVERY:
            return prog(*args)
        t0 = time.perf_counter()
        out = prog(*args)
        _ATTR.submit(self, t0, out)
        return out

    def __call__(self, *args: Any):
        self.stats["calls"] += 1
        if self._registry.aot_disabled:
            self.stats["jit_calls"] += 1
            with compile_scope(self.name):
                return self._dispatch(self._jit, args)
        mk = _memkey(args)
        with self._lock:
            prog = self._compiled.get(mk)
            fresh_load = mk in self._unvalidated
        if prog is None:
            src, _ = self.warmup(*args)
            fresh_load = src == "store"
            with self._lock:
                prog = self._compiled[mk]
        else:
            self.stats["aot_hits"] += 1
        if not fresh_load:
            return self._dispatch(prog, args)
        # first call of a deserialized executable: an incompatible entry
        # (stale jax/XLA, foreign topology) surfaces here — evict it and
        # fall back to a real compile rather than wedging the caller
        try:
            out = prog(*args)
        except Exception:
            self._registry.store.evict(self.store_key(args))
            with self._lock:
                self._compiled.pop(mk, None)
                self._unvalidated.discard(mk)
            prog, _ = self._compile(args)
            return prog(*args)
        with self._lock:
            self._unvalidated.discard(mk)
        return out

    def program_count(self) -> int:
        with self._lock:
            return len(self._compiled)

    def hlo_texts(self) -> list[str]:
        """Optimized HLO of every executable in the table (compiled here
        or loaded from the store) — what ``chip_smoke.py`` reads to see
        that a kernel is in the program as a ``tpu_custom_call``."""
        with self._lock:
            compiled = list(self._compiled.values())
        return [c.as_text() for c in compiled]


class WarmupHandle:
    """Background ``aot_warmup``: join via :meth:`result` (re-raises any
    warm-up failure there, never in the worker thread)."""

    def __init__(self, thread: threading.Thread, box: dict):
        self._thread = thread
        self._box = box

    def done(self) -> bool:
        return not self._thread.is_alive()

    def result(self, timeout: float | None = None) -> dict:
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("aot_warmup still running")
        if "error" in self._box:
            raise self._box["error"]
        return self._box["result"]


class ProgramRegistry:
    """Process-wide table of named hot programs (weakly held).

    Construction wires the two persistent layers: the JAX compilation
    cache (:func:`rl_tpu.config.enable_compile_cache`, opt-out
    ``RL_TPU_NO_COMPILE_CACHE``) and the executable store (opt-out
    ``RL_TPU_NO_EXEC_STORE``), plus the compile-event listener feeding
    ``/metrics``.
    """

    def __init__(
        self,
        store: ExecutableStore | None = None,
        aot: bool | None = None,
        auditor: Any = None,
    ):
        from ..config import enable_compile_cache

        enable_compile_cache()
        install_compile_listener()
        self.store = store if store is not None else default_store()
        if aot is None:
            aot = os.environ.get(_ENV_NO_AOT, "") in ("", "0")
        self.aot_disabled = not aot
        # IR auditor receiving every compile's audit; None = the process
        # default (rl_tpu.analysis.ir.get_ir_auditor), which the tier-1
        # gate and /metrics read. Tests compiling deliberately-poisoned
        # fixtures pass an isolated IRAuditor here.
        self.auditor = auditor
        self._lock = threading.Lock()
        self._programs: dict[str, list] = {}  # name -> [weakref.ref]

    # -- registration ----------------------------------------------------

    def register(
        self,
        name: str,
        fn: Callable,
        *,
        fingerprint: str = "",
        ir_contract: dict | None = None,
        **jit_kwargs: Any,
    ) -> CachedProgram:
        """Create a :class:`CachedProgram` for ``fn`` under ``name``.
        ``jit_kwargs`` go to ``jax.jit`` (donate_argnums, in_shardings,
        ...); ``fingerprint`` distinguishes same-name/same-shape programs
        whose Python closures differ (model config, loss flavor);
        ``ir_contract`` declares semantic invariants the IR auditor
        enforces at compile time (``{"shard_local": True}`` = the program
        must never emit a collective — R103)."""
        prog = CachedProgram(
            name, fn, registry=self, fingerprint=fingerprint,
            ir_contract=ir_contract, **jit_kwargs
        )
        with self._lock:
            refs = self._programs.setdefault(name, [])
            refs.append(weakref.ref(prog))
        return prog

    def _alive(self, name: str) -> list[CachedProgram]:
        with self._lock:
            refs = self._programs.get(name, [])
            progs = [p for r in refs if (p := r()) is not None]
            self._programs[name] = [weakref.ref(p) for p in progs]
        return progs

    def names(self) -> list[str]:
        with self._lock:
            names = list(self._programs)
        return sorted(n for n in names if self._alive(n))

    def program(self, name: str) -> CachedProgram:
        """The most recently registered live program under ``name``."""
        progs = self._alive(name)
        if not progs:
            raise KeyError(f"no live program registered as {name!r}")
        return progs[-1]

    def programs(self) -> list[CachedProgram]:
        return [p for n in self.names() for p in self._alive(n)]

    # -- warm-up ---------------------------------------------------------

    def aot_warmup(
        self,
        names: Iterable[str] | None = None,
        *,
        programs: Iterable[CachedProgram] | None = None,
        background: bool = False,
    ) -> dict | WarmupHandle:
        """Drive ``lower().compile()`` (or store loads) for every recorded
        signature of the named programs (default: all live programs), or
        of an explicit ``programs`` iterable (how an engine warms exactly
        its own set). Returns ``{name: [(source, seconds), ...]}``, or a
        :class:`WarmupHandle` when ``background=True`` so warm-up overlaps
        host setup."""
        if programs is not None:
            todo = list(programs)
        else:
            want = list(names) if names is not None else self.names()
            todo = [p for name in want for p in self._alive(name)]

        def work() -> dict:
            # a few at a time: a store load (read, decompress, hand to the
            # runtime) and a compile both leave the interpreter lock, and an
            # engine's ladder is twenty of them
            jobs = [(prog, sig) for prog in todo for sig in prog.signatures]
            out: dict[str, list] = {}
            with ThreadPoolExecutor(
                max_workers=min(_WARMUP_THREADS, len(jobs)) or 1,
                thread_name_prefix="aot-warmup",
            ) as pool:
                for (prog, _), res in zip(
                    jobs, pool.map(lambda j: j[0].warmup(*j[1]), jobs)
                ):
                    out.setdefault(prog.name, []).append(res)
            return out

        if not background:
            return work()
        box: dict = {}

        def run():
            try:
                box["result"] = work()
            except BaseException as e:  # surfaced at .result()
                box["error"] = e

        t = threading.Thread(target=run, name="aot-warmup", daemon=True)
        t.start()
        return WarmupHandle(t, box)

    # -- introspection ---------------------------------------------------

    def stats(self) -> dict:
        """Aggregated per-name stats (all live instances summed)."""
        out: dict[str, dict] = {}
        for name in self.names():
            agg: dict[str, float] = {}
            n_exec = 0
            for p in self._alive(name):
                n_exec += p.program_count()
                for k, v in p.stats.items():
                    agg[k] = agg.get(k, 0) + v
            agg["executables"] = n_exec
            out[name] = agg
        return out


_default: ProgramRegistry | None = None
_default_lock = threading.Lock()


def get_program_registry() -> ProgramRegistry:
    """The process-default registry (created on first use)."""
    global _default
    with _default_lock:
        if _default is None:
            _default = ProgramRegistry()
            _wire_obs(_default)
        return _default


def set_program_registry(reg: ProgramRegistry | None) -> ProgramRegistry | None:
    """Swap the process default (tests pair this with a tmpdir store);
    returns the previous registry."""
    global _default
    with _default_lock:
        prev = _default
        _default = reg
        return prev


def _wire_obs(reg: ProgramRegistry) -> None:
    """Publish registry totals as gauges at scrape time (the per-compile
    counter/histogram are fed by the metrics listener, not here)."""
    try:
        from ..obs import get_registry

        obs = get_registry()
        g_progs = obs.gauge(
            "rl_tpu_aot_programs", "registered hot programs (live)"
        )
        g_exec = obs.gauge(
            "rl_tpu_aot_executables", "materialized executables across programs"
        )
        g_loads = obs.gauge(
            "rl_tpu_aot_store_loads", "executables deserialized from the store"
        )
        c_dev = obs.counter(
            "rl_tpu_program_device_seconds_total",
            "sampled device time attributed per program",
            labels=("program",),
        )
        c_samp = obs.counter(
            "rl_tpu_program_sampled_dispatches_total",
            "dispatches sampled for device-time attribution",
            labels=("program",),
        )
        g_mfu = obs.gauge(
            "rl_tpu_program_mfu",
            "model FLOPs utilization per program "
            "(set RL_TPU_PEAK_FLOPS to the accelerator peak to enable)",
            labels=("program",),
        )
        c_ir = obs.counter(
            "rl_tpu_ir_audit_findings_total",
            "IR-audit findings (R100-series) across audited programs",
            labels=("rule",),
        )
        g_audited = obs.gauge(
            "rl_tpu_ir_audited_programs",
            "program signatures audited at compile time",
        )
        g_pred = obs.gauge(
            "rl_tpu_program_predicted_mfu",
            "roofline-predicted MFU from the static IR cost model "
            "(needs RL_TPU_PEAK_FLOPS; RL_TPU_PEAK_BYTES_PER_S adds the "
            "transfer ceiling)",
            labels=("program",),
        )
        g_bw = obs.gauge(
            "rl_tpu_program_bandwidth_util",
            "memory-bandwidth utilization per program: static IR bytes "
            "per dispatch x sampled dispatch rate over "
            "RL_TPU_PEAK_BYTES_PER_S",
            labels=("program",),
        )
        # kernel-tier activation gauges (rl_tpu_kernel_active) live in
        # rl_tpu.kernels.registry; wiring them here keeps every /metrics
        # process that serves programs also reporting which Pallas
        # kernels those programs were lowered with
        try:
            from ..kernels.registry import wire_kernel_obs

            wire_kernel_obs()
        except Exception:
            pass

        def collect():
            stats = reg.stats()
            g_progs.set(float(len(stats)))
            g_exec.set(float(sum(s["executables"] for s in stats.values())))
            g_loads.set(float(sum(s["loads"] for s in stats.values())))
            try:
                peak = float(os.environ.get(_ENV_PEAK_FLOPS, "0") or 0.0)
            except ValueError:
                peak = 0.0
            try:
                bw = float(os.environ.get(_ENV_PEAK_BW, "0") or 0.0)
            except ValueError:
                bw = 0.0
            for name, s in stats.items():
                dev_s = float(s.get("device_s", 0.0))
                c_dev.set_total(dev_s, {"program": name})
                c_samp.set_total(float(s.get("device_samples", 0)), {"program": name})
                if peak > 0.0 and dev_s > 0.0:
                    mfu = float(s.get("device_flops", 0.0)) / dev_s / peak
                    g_mfu.set(mfu, {"program": name})
            if bw > 0.0:
                for p in reg.programs():
                    st = p.stats
                    dev_s = float(st.get("device_s", 0.0))
                    if dev_s > 0.0 and p.static_bytes > 0.0:
                        bps = (
                            p.static_bytes
                            * float(st.get("device_samples", 0))
                            / dev_s
                        )
                        g_bw.set(bps / bw, {"program": p.name})
            try:
                from ..analysis.ir import get_ir_auditor, roofline

                aud = reg.auditor or get_ir_auditor()
                for rule, n in aud.counts_by_rule().items():
                    c_ir.set_total(float(n), {"rule": rule})
                g_audited.set(float(aud.programs_audited()))
                if peak > 0.0:
                    for p in reg.programs():
                        rep = p.ir_report
                        if rep is None or rep.cost is None:
                            continue
                        rf = roofline(rep.cost, peak, bw)
                        if "predicted_mfu" in rf:
                            g_pred.set(rf["predicted_mfu"], {"program": p.name})
            except Exception:
                pass

        obs.register_collector(collect)
    except Exception:
        pass

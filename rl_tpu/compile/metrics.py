"""Compile-event observability: who compiled, what, and for how long.

XLA compilation is the framework's biggest cold-start tax (ROADMAP item
5: 20-40s per program on the cpu tier), and — worse — *silent* steady-
state recompiles are how a serving engine quietly loses its latency SLO.
This module turns every backend compile into a first-class observable
event:

- ``install_compile_listener()`` hooks :mod:`jax.monitoring`'s
  ``/jax/core/compile/backend_compile_duration`` stream (emitted once
  per XLA backend compile, *not* per cache hit) and fans each event out
  to the process :class:`~rl_tpu.obs.registry.MetricsRegistry`
  (``rl_tpu_compiles_total{program}`` counter +
  ``rl_tpu_compile_seconds`` histogram) and the
  :class:`~rl_tpu.obs.trace.TraceRecorder` (one ``xla_compile:<name>``
  span per compile, stamped after the fact via ``end_span``).
- ``compile_scope(name)`` attributes compiles to a logical program name
  (a contextvar, so concurrent warm-up threads attribute correctly);
  compiles outside any scope land under ``"unattributed"`` — a nonzero
  unattributed count is itself a finding (some program bypassed the
  :class:`~rl_tpu.compile.registry.ProgramRegistry`).
- ``CompileDelta`` is the steady-state assertion primitive: wrap a
  traffic window in it and ``delta == 0`` *proves* no silent recompiles
  (used by the serve/fleet benches and ``bench_warmup``).

The listener cannot be unregistered (:mod:`jax.monitoring` only offers a
global clear, which would nuke JAX's own listeners), so installation is
idempotent and permanent for the process — the counters it feeds are
monotone, and all consumers read deltas.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from typing import Iterator

__all__ = [
    "CompileDelta",
    "compile_counts",
    "compile_scope",
    "compile_seconds_total",
    "compiles_total",
    "install_compile_listener",
]

# The jax.monitoring event emitted once per XLA backend compile. Trace /
# lowering durations are emitted under sibling keys; only the backend
# compile marks "XLA built a new executable", which is the event both
# the recompile assertions and the cold-start accounting care about.
_COMPILE_EVENT_SUFFIX = "backend_compile_duration"

_UNATTRIBUTED = "unattributed"

_scope: contextvars.ContextVar[str] = contextvars.ContextVar(
    "rl_tpu_compile_scope", default=_UNATTRIBUTED
)

_lock = threading.Lock()
_installed = False
_total = 0
_seconds_total = 0.0
_counts: dict[str, int] = {}
_seconds: dict[str, float] = {}

# compile_seconds spans 1ms toy programs to minutes-long fused trainers;
# the default obs buckets top out at 10s.
_COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)


@contextlib.contextmanager
def compile_scope(name: str) -> Iterator[None]:
    """Attribute any XLA compiles inside the block to ``name``."""
    token = _scope.set(str(name))
    try:
        yield
    finally:
        _scope.reset(token)


def current_scope() -> str:
    return _scope.get()


def _on_event(event: str, duration: float) -> None:
    label = _scope.get()
    global _total, _seconds_total
    with _lock:
        _total += 1
        _seconds_total += duration
        _counts[label] = _counts.get(label, 0) + 1
        _seconds[label] = _seconds.get(label, 0.0) + duration
    # obs wiring resolves the registry/tracer per event: tests swap both
    # via set_registry/set_tracer, and a cached handle would leak writes
    # into a previous test's registry.
    try:
        from rl_tpu.obs import get_registry, get_tracer

        reg = get_registry()
        reg.counter(
            "rl_tpu_compiles_total",
            "XLA backend compiles by logical program",
            labels=("program",),
        ).inc(labels={"program": label})
        reg.histogram(
            "rl_tpu_compile_seconds",
            "XLA backend compile duration",
            buckets=_COMPILE_BUCKETS,
        ).observe(duration)
        tracer = get_tracer()
        # the compile already happened — stamp a completed span covering it
        tracer.end_span(
            f"xla_compile:{label}",
            tracer.now_us() - duration * 1e6,
            {"seconds": round(duration, 4)},
        )
    except Exception:
        # observability must never break compilation itself
        pass


def _listener(event: str, duration_secs: float, **kwargs) -> None:
    if event.endswith(_COMPILE_EVENT_SUFFIX):
        _on_event(event, float(duration_secs))


def install_compile_listener() -> bool:
    """Idempotently register the compile-duration listener. Returns True
    when the hook is live (False if this jax lacks :mod:`jax.monitoring`)."""
    global _installed
    with _lock:
        if _installed:
            return True
    try:
        from jax import monitoring
    except Exception:
        return False
    fn = getattr(monitoring, "register_event_duration_secs_listener", None)
    if fn is None:
        return False
    with _lock:
        if _installed:  # lost the race to another thread
            return True
        fn(_listener)
        _installed = True
    return True


def compiles_total() -> int:
    """Process-lifetime count of XLA backend compiles (0 until the
    listener is installed)."""
    with _lock:
        return _total


def compile_seconds_total() -> float:
    with _lock:
        return _seconds_total


def compile_counts() -> dict[str, int]:
    """Snapshot of per-program compile counts."""
    with _lock:
        return dict(_counts)


def compile_seconds() -> dict[str, float]:
    """Snapshot of per-program cumulative compile seconds."""
    with _lock:
        return dict(_seconds)


class CompileDelta:
    """Count XLA compiles across a block: the steady-state assertion.

    ::

        with CompileDelta() as d:
            run_traffic(engine)
        assert d.delta == 0, d.explain()

    Installs the listener on entry (so the first use in a process still
    counts correctly) and snapshots per-program counts, so ``explain()``
    names exactly which programs recompiled.
    """

    def __init__(self):
        self.delta = 0
        self.seconds = 0.0
        self.by_program: dict[str, int] = {}
        self._t0 = 0
        self._s0 = 0.0
        self._c0: dict[str, int] = {}
        self.supported = False

    def __enter__(self) -> "CompileDelta":
        self.supported = install_compile_listener()
        with _lock:
            self._t0 = _total
            self._s0 = _seconds_total
            self._c0 = dict(_counts)
        return self

    def __exit__(self, *exc) -> None:
        with _lock:
            self.delta = _total - self._t0
            self.seconds = _seconds_total - self._s0
            self.by_program = {
                k: v - self._c0.get(k, 0)
                for k, v in _counts.items()
                if v - self._c0.get(k, 0) > 0
            }

    def explain(self) -> str:
        if not self.supported:
            return "compile counting unsupported (no jax.monitoring)"
        if not self.delta:
            return "no compiles"
        progs = ", ".join(f"{k}: {v}" for k, v in sorted(self.by_program.items()))
        return (
            f"{self.delta} compile(s) ({self.seconds:.2f}s) inside a window "
            f"expected to be steady-state [{progs}]"
        )


def _timed_compile(fn, *args, **kwargs):
    """Run ``fn`` (a lower/compile/deserialize step) and return
    ``(result, seconds)`` — shared helper for registry bookkeeping."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0

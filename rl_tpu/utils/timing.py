"""Global named-timer registry (reference torchrl/_utils.py:221 ``timeit``).

Usable as decorator, context manager, or explicit start/stop. On TPU, wall
timing of jitted calls measures dispatch unless the result is blocked on, so
``timeit`` optionally calls ``block_until_ready`` on the wrapped function's
output. For a span that also lands in a ``jax.profiler`` capture use
``rl_tpu.obs.get_tracer().span``.

``timeit`` is a thin client of :class:`rl_tpu.obs.trace.TraceRecorder`:
every timed block is also recorded as a span on the calling thread, so a
``get_tracer().export()`` shows the same names on trainer/collector/serving
tracks. The registry itself is shared across threads (trainer loop and the
``AsyncHostCollector`` actor both time into it), so all mutation is behind
a class-level lock and per-call start times live in thread-local stacks.
"""

from __future__ import annotations

import functools
import threading
import time
from collections import defaultdict
from typing import Any, Callable

import jax

from ..obs.trace import get_tracer

__all__ = ["timeit"]


class timeit:
    """Named accumulating timer.

    >>> with timeit("rollout"):
    ...     ...
    >>> timeit.print()
    """

    _REG: dict[str, list[float]] = defaultdict(lambda: [0.0, 0.0, 0])
    # name -> [total_s, last_s, count]
    _REG_LOCK = threading.Lock()

    def __init__(self, name: str, block: bool = False):
        self.name = name
        self.block = block
        # one decorator instance can be entered concurrently from several
        # threads (and re-entered recursively), so starts are a
        # thread-local stack rather than a shared attribute.
        self._starts = threading.local()

    def __call__(self, fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with self:
                out = fn(*args, **kwargs)
                if self.block:
                    jax.block_until_ready(out)
                return out

        return wrapper

    def __enter__(self):
        stack = getattr(self._starts, "stack", None)
        if stack is None:
            stack = self._starts.stack = []
        tracer = get_tracer()
        stack.append((time.perf_counter(), tracer.begin_span(self.name)))
        return self

    def __exit__(self, *exc):
        t0, span_start = self._starts.stack.pop()
        dt = time.perf_counter() - t0
        tracer = get_tracer()
        tracer.end_span(self.name, span_start)
        with timeit._REG_LOCK:
            rec = timeit._REG[self.name]
            rec[0] += dt
            rec[1] = dt
            rec[2] += 1
        return False

    @classmethod
    def todict(cls, percall: bool = True) -> dict[str, float]:
        with cls._REG_LOCK:
            items = {k: list(v) for k, v in cls._REG.items()}
        if percall:
            return {k: v[0] / max(v[2], 1) for k, v in items.items()}
        return {k: v[0] for k, v in items.items()}

    @classmethod
    def print(cls, prefix: str = "") -> None:  # noqa: A003
        with cls._REG_LOCK:
            items = sorted((k, list(v)) for k, v in cls._REG.items())
        for k, v in items:
            print(f"{prefix}{k}: total={v[0]:.4f}s count={v[2]} percall={v[0] / max(v[2], 1):.4f}s")

    @classmethod
    def erase(cls) -> None:
        with cls._REG_LOCK:
            cls._REG.clear()

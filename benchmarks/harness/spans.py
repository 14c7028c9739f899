"""The program's own spans (``rl_tpu.obs.trace``), read from the recorder
in memory: the arithmetic the span readers share.

A span is ``(name, start, dur, args)`` with times in microseconds on the
recorder's clock, as ``TraceRecorder.export()`` gives them. The window a
reader sums over is chosen BY COUNT: the ``engine.launch`` spans whose
``launch`` (the engine's cumulative ``decode_launches``) lies in
``(c0, c1]``, the ``engine.step`` spans that hold them, and for a GRPO
job the ``grpo.step`` spans that hold those. A reader gets ``None``, and
never a number from part of a window, where the ring has lapped the
window's first step or the counts do not match; against a program that
has no such spans it gets ``None`` too.

Self time is a span's duration less its direct children's (the
``choosing-metrics`` guide, section 4). A blocking read from the device is
a child named ``*.wait`` and a program call a child named ``*.dispatch``,
so neither is in the self time of the spans in ``ENGINE_HOST``: on the
TPU runtime a dispatch blocks while its output buffers cannot be
allocated (the rollout cell's prefill, behind the chunk in flight), and
is then a device wait no transfer shows. What is left is the host's own
work around those calls. Against a program whose spans lack the
``*.dispatch`` children the call stays inside its parent's self time.
"""

from __future__ import annotations

import bisect
import dataclasses

ENGINE_HOST = ("engine.step", "engine.admit", "engine.launch", "engine.flush_tables", "engine.drain")
NESTED = ("engine.", "collector.", "grpo.")  # spans of these families nest on their thread


@dataclasses.dataclass
class Thread:
    dropped: int = 0  # events the ring has lapped
    oldest_end: float = float("inf")  # end of the oldest event the ring still holds
    spans: list = dataclasses.field(default_factory=list)  # nested families, sorted by start
    requests: list = dataclasses.field(default_factory=list)  # the ``request`` events


@dataclasses.dataclass
class Window:
    lo: float
    hi: float
    launches: int
    steps: list  # the engine.step spans (or, widened, the grpo.step spans) that bound it
    spans: list  # every nested span inside [lo, hi]
    requests: list


def by_thread(trace_events: list) -> dict:
    """``export()["traceEvents"]`` -> {tid: Thread}."""
    threads: dict = {}
    for e in trace_events:
        t = threads.setdefault(e["tid"], Thread())
        if e["ph"] == "M":
            t.dropped = int(e["args"].get("dropped", 0))
            continue
        t.oldest_end = min(t.oldest_end, e["ts"] + e.get("dur", 0.0))
        if e["ph"] != "X":
            continue
        span = (e["name"], e["ts"], e["dur"], e.get("args") or {})
        if e["name"] == "request":
            t.requests.append(span)
        elif e["name"].startswith(NESTED):
            t.spans.append(span)
    for t in threads.values():
        t.spans.sort(key=lambda s: (s[1], -s[2]))
    return threads


def snapshot() -> dict:
    """The threads of the process's recorder, as it stands."""
    from rl_tpu.obs.trace import get_tracer

    return by_thread(get_tracer().export()["traceEvents"])


def _holders(spans: list, name: str, inner: list) -> list:
    """The spans called ``name`` that hold at least one span of ``inner``
    (both sorted by start, all nested on one thread)."""
    starts = [i[1] for i in inner]
    out = []
    for s in spans:
        if s[0] != name:
            continue
        k = bisect.bisect_left(starts, s[1])
        if k < len(inner) and inner[k][1] + inner[k][2] <= s[1] + s[2]:
            out.append(s)
    return out


def choose_window(threads: dict, first: int, last: int, log, outer: str | None = None):
    """The window of the launches numbered ``first + 1 .. last``, or None.
    ``outer`` widens it to the spans of that name around its engine steps."""
    want = last - first

    def launches_of(t):
        return [s for s in t.spans if s[0] == "engine.launch" and first < s[3].get("launch", -1) <= last]

    t, launches = next(((t, found) for t in threads.values() if (found := launches_of(t))), (None, []))
    if t is None:
        log("the recorder holds no engine.launch span of the window")
        return None
    # an engine numbers its launches from 1: one built earlier in the process
    # may have left the same numbers in the ring, so take the newest run of them
    launches = launches[-want:]
    if [s[3]["launch"] for s in launches] != list(range(first + 1, last + 1)):
        log(f"the recorder holds {len(launches)} of the window's {want} launches, or holds them out of order")
        return None
    steps = _holders(t.spans, "engine.step", launches)
    if len(steps) != want:
        log(f"{want} launches lie in {len(steps)} engine.step spans: not one a step")
        return None
    if outer is not None:
        steps = _holders(t.spans, outer, steps)
        if not steps:
            log(f"no {outer} span holds the window's engine steps")
            return None
    lo, hi = steps[0][1], max(s[1] + s[2] for s in steps)
    if t.dropped and t.oldest_end > lo:
        log(f"the ring lapped the window's first step ({t.dropped} events dropped)")
        return None
    return Window(lo, hi, want, steps, [s for s in t.spans if lo <= s[1] and s[1] + s[2] <= hi], t.requests)


def self_times(spans: list) -> dict:
    """{name: [self microseconds, count]} over spans nested on one thread."""
    out: dict = {}
    stack: list = []  # [end, name, dur, children's dur]

    def close():
        _end, name, dur, kids = stack.pop()
        e = out.setdefault(name, [0.0, 0])
        e[0] += dur - kids
        e[1] += 1

    for name, ts, dur, _args in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= ts:
            close()
        if stack:
            stack[-1][3] += dur
        stack.append([ts + dur, name, dur, 0.0])
    while stack:
        close()
    return out


def log_table(log, title: str, table: dict, per: int, unit: str) -> None:
    """Self time by span name, in ms a launch or a step, largest first."""
    rows = sorted(table.items(), key=lambda kv: -kv[1][0])
    log(f"{title}: " + ", ".join(f"{n} {us / 1e3 / per:.4f} ms/{unit} ({c})" for n, (us, c) in rows))


def engine_window(run: dict, outer: str | None = None):
    return choose_window(snapshot(), run["c0"]["decode_launches"], run["c1"]["decode_launches"], run["log"], outer)


def engine_host_ms_per_launch(run: dict):
    """Host work inside the engine a launch: the self time of the spans in
    ``ENGINE_HOST`` (program calls and blocking reads are children, and
    left out) over the window's launches. Logs the table it summed, the
    dispatches and waits beside it, and what part of the window's wall the
    engine's steps account for."""
    w = engine_window(run)
    if w is None:
        return None
    table = self_times([s for s in w.spans if s[0].startswith("engine.")])
    log_table(run["log"], f"engine self time over {w.launches} launches", table, w.launches, "launch")
    host = sum(table.get(n, (0.0, 0))[0] for n in ENGINE_HOST)
    calls = sum(us for n, (us, _) in table.items() if n.endswith(".dispatch"))
    waits = sum(us for n, (us, _) in table.items() if n.endswith(".wait"))
    in_steps = sum(s[2] for s in w.spans if s[0] == "engine.step")
    run["log"](f"window {(w.hi - w.lo) / 1e6:.6f} s: engine.step {in_steps / 1e6:.6f} s = host {host / 1e6:.6f} s "
               f"+ dispatches {calls / 1e6:.6f} s + waits {waits / 1e6:.6f} s "
               f"+ other {(in_steps - host - calls - waits) / 1e6:.6f} s; "
               f"outside engine.step {(w.hi - w.lo - in_steps) / 1e6:.6f} s")
    return host / 1e3 / w.launches


def non_rollout_ms_per_step(run: dict):
    """What a GRPO step spends outside its rollout: ``grpo.step`` less the
    ``collector.rollout`` inside it, mean over the window's steps. Logs ms
    a step by span name."""
    w = engine_window(run, outer="grpo.step")
    if w is None:
        return None
    want = run["c1"].get("steps", 0) - run["c0"].get("steps", 0)
    if want and want != len(w.steps):
        run["log"](f"the window's launches lie in {len(w.steps)} grpo.step spans, the driver counted {want}")
        return None
    table = self_times([s for s in w.spans if not s[0].startswith("engine.")])
    log_table(run["log"], f"self time over {len(w.steps)} grpo steps (the engine's spans folded into "
              "collector.rollout)", table, len(w.steps), "step")
    rollout = sum(s[2] for s in w.spans if s[0] == "collector.rollout")
    return (sum(s[2] for s in w.steps) - rollout) / 1e3 / len(w.steps)


def slot_refills(w: Window) -> tuple[list, int]:
    """Microseconds each freed slot waited for its next occupant's first
    token: from a ``request``'s end to the end of the ``engine.prefill.wait``
    of the next ``engine.admit`` that names the slot, both inside the
    window, so a prefill dispatch that blocked is in it. Also the count of
    slots freed in the window and not refilled before it closed."""
    admits = []  # (first token on the host, slots)
    for a in (s for s in w.spans if s[0] == "engine.admit"):
        wait = next((s for s in w.spans if s[0] == "engine.prefill.wait"
                     and a[1] <= s[1] and s[1] + s[2] <= a[1] + a[2]), None)
        if wait is not None:
            admits.append((wait[1] + wait[2], a[3].get("slots", ())))
    refills, open_ = [], 0
    for _name, ts, dur, args in w.requests:
        freed = ts + dur
        if not w.lo <= freed <= w.hi:
            continue
        nxt = [t for t, slots in admits if t >= freed and args.get("slot") in slots]
        if nxt:
            refills.append(min(nxt) - freed)
        else:
            open_ += 1
    return refills, open_


def slot_refill_ms(run: dict):
    w = engine_window(run)
    if w is None:
        return None
    refills, open_ = slot_refills(w)
    run["log"](f"slot refills inside the window: {len(refills)} (and {open_} slots freed and not yet refilled), "
               f"over {w.launches} launches")
    if not refills:
        return None
    return sum(refills) / len(refills) / 1e3

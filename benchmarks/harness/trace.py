"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, time by operation and by
program, and idle gaps named by the host span that covers them.

Reads the file with ``jax.profiler.ProfileData`` alone. Times are seconds.
A device plane is one whose name starts with ``/device:``; on it the line
``XLA Ops`` holds one event per operation run and ``XLA Modules`` one per
program run. Host spans are the benchmark's own ``TraceAnnotation``s
(``bench.*``) on the host planes; the profiler puts both on one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Reduced:
    window_s: float  # length of the bench.window span
    busy_s: float  # union of op intervals inside it, averaged over devices
    n_devices: int
    ops: dict  # op name -> [seconds, count], summed over devices / n_devices
    modules: dict  # program name -> [seconds, count]
    gaps: dict  # host span name (the window's, where no other covers it) -> idle seconds inside it

    def op_seconds(self, *needles: str) -> tuple[float, int]:
        """Summed device time and count of ops whose name holds any needle."""
        return _match(self.ops, needles)

    def module_seconds(self, *needles: str) -> tuple[float, int]:
        return _match(self.modules, needles)

    def breakdown(self, top: int = 10) -> dict:
        fam: dict = {}
        for name, (sec, _) in self.ops.items():
            if _family(name) in CONTAINERS:
                continue  # a loop's event spans its body's ops, which are listed themselves
            fam[_family(name)] = fam.get(_family(name), 0.0) + sec
        for name, (sec, _) in self.modules.items():
            fam["program " + name] = sec
        ops = sorted(fam.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": [[n, s] for n, s in gaps]}


def _match(table: dict, needles) -> tuple[float, int]:
    sec = cnt = 0
    for name, (s, c) in table.items():
        if any(n in name for n in needles):
            sec, cnt = sec + s, cnt + c
    return sec, cnt


def short_name(name: str) -> str:
    """The trace names a device op by its whole HLO instruction
    (``%fusion.3 = f32[...] fusion(...)``): keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def _family(name: str) -> str:
    """``_paged_decode_kernel.71`` -> ``_paged_decode_kernel``: the same
    instruction once per layer is one line of a breakdown."""
    base, _, tail = name.rpartition(".")
    return base if base and tail.isdigit() else name


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    t = lo
    for a, b in busy:
        if a > t:
            yield t, min(a, hi)
        t = max(t, b)
        if t >= hi:
            return
    if t < hi:
        yield t, hi


def reduce_planes(planes: list[dict]) -> Reduced:
    """``planes``: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}] — the plain form :func:`load` produces (tests build it by
    hand too)."""
    spans: list[tuple[str, float, float]] = []
    devices = []
    for pl in planes:
        if pl["name"].startswith("/device:"):
            devices.append(pl)
            continue
        for ln in pl["lines"]:
            for name, t0, dur in ln["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.append((name, t0, t0 + dur))
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    inner = sorted(((n, a, b) for n, a, b in spans if n != WINDOW_SPAN), key=lambda s: s[2] - s[1])
    ops: dict = {}
    modules: dict = {}
    gaps: dict = {}
    busy_total = 0.0
    used = 0
    for pl in devices:
        lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
        if OPS_LINE not in lines:
            continue
        used += 1
        iv = []
        for name, t0, dur in lines[OPS_LINE]:
            a, b = max(t0, lo), min(t0 + dur, hi)
            if b <= a:
                continue
            iv.append((a, b))
            e = ops.setdefault(name, [0.0, 0])
            e[0] += (b - a) * 1e-9
            e[1] += 1
        for name, t0, dur in lines.get(MODULES_LINE, ()):
            a, b = max(t0, lo), min(t0 + dur, hi)
            if b <= a:
                continue
            e = modules.setdefault(name, [0.0, 0])
            e[0] += (b - a) * 1e-9
            e[1] += 1
        busy = _union(iv)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        for a, b in _gaps(busy, lo, hi):
            mid = (a + b) / 2
            # the shortest benchmark span over the gap's middle names it
            owner = next((n for n, s, e in inner if s <= mid <= e), WINDOW_SPAN)
            gaps[owner] = gaps.get(owner, 0.0) + (b - a) * 1e-9
    n = max(used, 1)
    for table in (ops, modules):
        for e in table.values():
            e[0] /= n
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / n, n_devices=used,
        ops=ops, modules=modules, gaps={k: v / n for k, v in gaps.items()},
    )


def load(path: str) -> list[dict]:
    """The planes of an ``.xplane.pb`` in the plain form, keeping only what
    the reduction reads (device op/program lines, ``bench.*`` host spans)."""
    from jax.profiler import ProfileData

    planes = []
    for pl in ProfileData.from_file(path).planes:
        device = pl.name.startswith("/device:")
        lines = []
        for ln in pl.lines:
            if device and ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [
                (short_name(e.name) if device else e.name, float(e.start_ns), float(e.duration_ns))
                for e in ln.events
                if device or e.name.startswith(SPAN_PREFIX)
            ]
            if evs:
                lines.append({"name": ln.name, "events": evs})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return planes


def reduce_file(path: str) -> Reduced:
    return reduce_planes(load(path))

"""Offline performance sentry: the committed-artifact regression gate.

Benchmark runs commit measurement artifacts (``BENCH_*`` round
captures, plus the distilled per-subsystem files: ``FLEET_pr6``,
``REPLAY_pr20``, ...). Nothing *read* them back — a regression landed in
a commit looked identical to a win until a human diffed the JSON. This
tool closes that loop offline, the artifact-side complement of the
runtime :class:`~rl_tpu.obs.drift.DriftDetector`:

1. **Distill** every committed artifact into one schema-tolerant time
   series (whole-file JSON or JSONL; missing files, rounds that
   recorded ``parsed: null``, and pre-PR checkouts all tolerated — an
   absent series is *skipped*, never failed, so the gate works at every
   point in history).
2. **Enforce** the declared gate table below: headline throughput
   ratios, accepted-tokens/dispatch, cache hit rates, lost==0
   accounting, steady-state ``CompileDelta == 0``, and the PR-18
   armed-profiler overhead bound.
3. **Write** the roll-up to ``PERF_HISTORY.json`` (committed alongside
   the artifacts it summarizes) and exit nonzero iff any gate failed —
   the CI contract.

Usage::

    python tools/perf_sentry.py [--dir REPO] [--out PERF_HISTORY.json]
"""

from __future__ import annotations

import argparse
import datetime
import glob
import json
import os
import sys
from typing import Any, NamedTuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

__all__ = ["GATES", "Gate", "check", "load_records", "main"]


def _utcnow() -> str:
    return datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%dT%H:%M:%SZ"
    )


# -- schema-tolerant readers ---------------------------------------------------


def load_records(path: str) -> list[dict]:
    """Read one artifact into a list of dict records. Tolerates the two
    on-disk shapes (a single JSON object, or a JSONL stream like
    ``BENCH_pr2.json``) and skips unparseable lines instead of raising —
    the sentry must keep gating the healthy series even when one round's
    capture was cut off mid-write."""
    try:
        with open(path) as f:
            raw = f.read()
    except OSError:
        return []
    try:
        d = json.loads(raw)
        return [d] if isinstance(d, dict) else []
    except ValueError:
        pass
    out: list[dict] = []
    for ln in raw.splitlines():
        try:
            d = json.loads(ln)
        except ValueError:
            continue
        if isinstance(d, dict):
            out.append(d)
    return out


def _lookup(d: Any, dotted: str) -> Any:
    """Nested dict lookup by dotted path; None when any hop is absent."""
    cur = d
    for part in dotted.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur


# -- the gate table ------------------------------------------------------------


class Gate(NamedTuple):
    file: str  # artifact filename in --dir
    key: str  # dotted path inside the artifact
    op: str  # >=, >, <, <=, ==
    bound: float
    why: str  # what a failure means, for the report line


_OPS = {
    ">=": lambda v, b: v >= b,
    ">": lambda v, b: v > b,
    "<": lambda v, b: v < b,
    "<=": lambda v, b: v <= b,
    "==": lambda v, b: v == b,
}

# Bounds sit well below the committed values (e.g. spec_speedup_x
# measured 2.36, gated at 1.3) — the sentry is a regression floor, not a
# flakiness amplifier. Every ==0 gate is an invariant, not a margin.
GATES: list[Gate] = [
    Gate("FLEET_pr6.json", "lost", "==", 0,
         "chaos fleet lost an admitted request across the crash"),
    Gate("FLEET_pr6.json", "fleet_tokens_per_sec", ">", 0.0,
         "fleet produced no tokens"),
    Gate("FLEET_pr6.json", "steady_state_compile_delta", "==", 0,
         "the chaos window recompiled mid-traffic"),
    Gate("COMPILE_pr10.json", "compile.metrics.warm_speedup", ">=", 2.0,
         "warm start no longer beats cold start 2x"),
    Gate("COMPILE_pr10.json", "compile.metrics.steady_state_compile_delta",
         "==", 0, "warmed process still compiled in steady state"),
    Gate("PREFIX_pr11.json", "prefix.kv_prefix_hit_rate", ">=", 0.5,
         "prefix-KV hit rate collapsed on the shared-prefix workload"),
    Gate("PREFIX_pr11.json", "prefix.prefill_reduction_x", ">=", 2.0,
         "prefix reuse no longer halves prefill compute"),
    Gate("PREFIX_pr11.json", "prefix.lost", "==", 0,
         "prefix bench lost an admitted request under kvmem.evict"),
    Gate("PREFIX_pr11.json", "prefix.steady_state_compile_delta", "==", 0,
         "prefix traffic recompiled in steady state"),
    Gate("SPEC_pr16.json", "spec.spec_speedup_x", ">=", 1.3,
         "speculative decoding no longer beats the spec-off arm"),
    Gate("SPEC_pr16.json", "spec.accepted_tokens_per_dispatch", ">", 1.0,
         "draft acceptance fell below one token per verify dispatch"),
    Gate("SPEC_pr16.json", "spec.lost", "==", 0,
         "spec bench lost an admitted request under engine_crash"),
    Gate("SPEC_pr16.json", "spec.steady_state_compile_delta_spec", "==", 0,
         "the spec arm recompiled in steady state"),
    Gate("KERNELS_pr17.json", "kernels.int8_capacity_ratio_x", ">=", 1.5,
         "int8 KV no longer buys its capacity multiplier"),
    Gate("KERNELS_pr17.json", "kernels.steady_state_compile_delta_kernel",
         "==", 0, "the kernel arm recompiled in steady state"),
    Gate("PROF_pr18.json", "profiling.armed_overhead_frac", "<", 0.02,
         "the armed profiler/drift feed costs more than 2% of wall"),
    # PR-19 elasticity: the committed run measured burst attainment
    # 0.53 (autoscale) vs 0.22 (fixed), vs_baseline 2.46 — floors sit
    # well under that; the ==0 gates are invariants.
    Gate("AUTOSCALE_pr19.json", "autoscale.lost", "==", 0,
         "the elastic fleet lost a request across scale-up/down/crash"),
    Gate("AUTOSCALE_pr19.json", "autoscale.scale_up_compile_delta_max",
         "==", 0,
         "a scale-up warm compiled instead of loading from the store"),
    Gate("AUTOSCALE_pr19.json", "autoscale.steady_state_compile_delta",
         "==", 0, "the autoscale arm recompiled mid-traffic"),
    Gate("AUTOSCALE_pr19.json", "autoscale.value", ">=", 0.3,
         "burst-window SLO attainment under autoscaling collapsed"),
    Gate("AUTOSCALE_pr19.json", "autoscale.vs_baseline", ">=", 1.2,
         "the elastic arm no longer beats the fixed fleet through the burst"),
    Gate("AUTOSCALE_pr19.json", "autoscale.rollout_tokens_per_sec", ">", 0.0,
         "the batch-lane tenant harvested nothing from fleet slack"),
    Gate("AUTOSCALE_pr19.json", "autoscale.waste_frac", "<=", 0.65,
         "idle-capacity waste under autoscaling exceeded its ceiling"),
    Gate("AUTOSCALE_pr19.json", "autoscale.scale_ups", ">=", 1,
         "no scale-up fired on the seeded burst"),
    Gate("AUTOSCALE_pr19.json", "autoscale.scale_downs", ">=", 1,
         "no scale-down drained the post-burst slack"),
    # PR-20 sharded experience tier (REPLAY_pr20.json). Measured on the
    # cpu tier: 2.81x aggregate extend throughput over one endpoint at
    # the same total capacity (the PER write program carries O(capacity)
    # full-array work per extend, so N shards at C/N each pay 1/N of
    # it), chaos recovery 0.91s. Floors sit under those; the chaos
    # gates are invariants of the acceptance scenario.
    Gate("REPLAY_pr20.json", "replay_shard.shard_speedup_x", ">=", 2.0,
         "N shards no longer beat one endpoint by the 2x acceptance bound"),
    Gate("REPLAY_pr20.json", "replay_shard.value", ">", 0.0,
         "the sharded tier wrote nothing during the timed window"),
    Gate("REPLAY_pr20.json", "replay_shard.chaos.faults_fired", ">=", 1,
         "the seeded shard crash never fired — the chaos phase ran empty"),
    Gate("REPLAY_pr20.json", "replay_shard.chaos.learner_errors", "==", 0,
         "a shard crash leaked through the mixture to the learner"),
    Gate("REPLAY_pr20.json", "replay_shard.chaos.readmitted", ">=", 1,
         "the supervisor never re-admitted the crashed shard"),
    Gate("REPLAY_pr20.json", "replay_shard.chaos.recovery_s", "<=", 10.0,
         "crash-to-readmit exceeded the degradation budget"),
]


# -- distillation --------------------------------------------------------------


def _headline_series(dir: str) -> dict:
    """All ``{"metric": ..., "value": ...}`` headline records across the
    committed ``BENCH_*`` captures, keyed by metric name — the long-run
    time series a human (or a future trend gate) reads."""
    series: dict[str, list[dict]] = {}

    def _add(src: str, rec: dict) -> None:
        m, v = rec.get("metric"), rec.get("value")
        if not isinstance(m, str) or not isinstance(v, (int, float)):
            return
        series.setdefault(m, []).append({
            "source": src,
            "value": v,
            "unit": rec.get("unit"),
            "vs_baseline": rec.get("vs_baseline"),
        })

    for path in sorted(glob.glob(os.path.join(dir, "BENCH_*.json"))):
        src = os.path.basename(path)
        for rec in load_records(path):
            _add(src, rec)
            # round captures wrap the result: {"n": .., "parsed": {...}}
            parsed = rec.get("parsed")
            if isinstance(parsed, dict):
                _add(src, parsed)
            # aggregate lines nest sub-results under their mode names
            # ("parsed" was already taken above)
            for k, v in rec.items():
                if k != "parsed" and isinstance(v, dict):
                    _add(src, v)
    return series


def check(dir: str) -> tuple[list[dict], dict]:
    """Evaluate every gate against the artifacts in ``dir``. Returns
    (results, history): per-gate dicts with status pass/fail/skip, and
    the full PERF_HISTORY payload."""
    results: list[dict] = []
    for g in GATES:
        path = os.path.join(dir, g.file)
        recs = load_records(path)
        rec = recs[0] if recs else None
        value = _lookup(rec, g.key) if rec is not None else None
        if value is None or not isinstance(value, (int, float)):
            status = "skip"  # pre-PR checkout or never-captured artifact
        elif _OPS[g.op](value, g.bound):
            status = "pass"
        else:
            status = "fail"
        results.append({
            "file": g.file,
            "key": g.key,
            "op": g.op,
            "bound": g.bound,
            "value": value,
            "status": status,
            "why": g.why,
        })
    history = {
        "generated": _utcnow(),
        "gates": results,
        "gate_counts": {
            s: sum(1 for r in results if r["status"] == s)
            for s in ("pass", "fail", "skip")
        },
        "headline_series": _headline_series(dir),
    }
    return results, history


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=REPO,
                    help="artifact directory (default: repo root)")
    ap.add_argument("--out", default=None,
                    help="history roll-up path (default <dir>/PERF_HISTORY.json)")
    args = ap.parse_args(argv)

    results, history = check(args.dir)
    out = args.out or os.path.join(args.dir, "PERF_HISTORY.json")
    with open(out, "w") as f:
        json.dump(history, f, indent=2, sort_keys=True)
        f.write("\n")

    failed = [r for r in results if r["status"] == "fail"]
    for r in results:
        mark = {"pass": "ok  ", "fail": "FAIL", "skip": "skip"}[r["status"]]
        print(f"{mark} {r['file']}:{r['key']} {r['op']} {r['bound']}"
              f" (value={r['value']})")
        if r["status"] == "fail":
            print(f"     -> {r['why']}")
    print(f"perf_sentry: {history['gate_counts']['pass']} pass, "
          f"{len(failed)} fail, {history['gate_counts']['skip']} skip "
          f"-> {os.path.relpath(out, args.dir)}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

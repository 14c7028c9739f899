"""Tiny stand-ins for the CPU tests: the same drivers, references and
harness code paths at sizes a test run can hold, with the harness's look
for a chip stubbed (in the tests only)."""

from __future__ import annotations

import copy
import io
import json
import os
import sys
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run  # noqa: E402

FAKE_PEAKS = {"flops": 1e12, "int8_ops": 2e12, "bytes_per_s": 1e11, "hbm_bytes": 1e9}

GPT2_TINY = {
    "vocab_size": 211, "n_positions": 64, "n_embd": 32, "n_layer": 2, "n_head": 4, "n_inner": 64,
    "layer_norm_epsilon": 1e-6,
}

TRAFFIC_TINY = {
    "grpo": {
        "job": {"dataset_size": 16, "num_prompts": 2, "group_repeats": 4, "max_prompt_len": 8,
                "max_new_tokens": 8, "microbatch_size": 4, "attention_impl": "local",
                "learning_rate": 1e-3},
        "check_block": 4, "trace_seconds": 0.2,
    },
    "rollout": {
        "engine": {"n_slots": 4, "n_blocks": 41, "block_size": 4, "prompt_buckets": [8, 16]},
        "requests": {"count": 64, "group": 4, "prompt": {"dist": "uniform", "low": 4, "high": 12},
                     "output": {"dist": "lognormal", "median": 8, "sigma": 0.8, "low": 2, "high": 24}},
        "outstanding": 8, "warm_seconds": 1.0, "check_requests": 6, "trace_seconds": 0.2,
    },
}


def tiny_traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        t = json.load(f)
    over = copy.deepcopy(TRAFFIC_TINY.get(name, {}))
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(t.get(k), dict):
            t[k].update(v)
        else:
            t[k] = v
    return t


def run_cell(monkeypatch, tmp_path, workload: str, config: dict, *, trace=0, seconds=0.3, seed=3,
             traffic_edit=None):
    """Drive ``run.main`` for one cell on the CPU; returns (exit code, the
    parsed last line, all of standard output)."""
    bench = bench_run.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg_file = tmp_path / "config.json"
    cfg_file.write_text(json.dumps(config))
    for c in bench["configs"]:
        if c["name"] == cell["config"]:
            c["file"] = os.path.relpath(cfg_file, ROOT)
    traffic = tiny_traffic(cell["traffic"])
    if traffic_edit:
        traffic_edit(traffic)
    monkeypatch.setattr(bench_run, "load_benchmark", lambda: bench)
    monkeypatch.setattr(bench_run, "load_json", lambda kind, name: traffic)
    info = {"platform": "cpu", "kind": "test", "count": cell["chips"]}
    monkeypatch.setattr(bench_run, "check_device", lambda chips: {"info": dict(info), "peaks": FAKE_PEAKS})
    monkeypatch.setattr(bench_run, "memory_peak_bytes", lambda chips: 1)
    monkeypatch.setattr(bench_run, "enable_cache", lambda: "(none)")
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
                             "--trace", str(trace)])
    text = out.getvalue()
    last = json.loads(text.strip().splitlines()[-1]) if text.strip() else None
    return rc, last, text

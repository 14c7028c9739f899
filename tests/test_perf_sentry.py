"""Offline perf sentry (PR-18): the committed-artifact regression gate.

Two contracts under test: (1) the sentry PASSES on the repo's actual
committed artifact series — if this fails, a perf regression (or a gate
mis-declared against the real values) is already in-tree; (2) a
synthetically regressed copy of the series FAILS with the regression
named. Plus the schema tolerance the long history demands: JSONL
streams, missing artifacts, and half-written files all gate as *skip*,
never as crash."""

import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from perf_sentry import GATES, REPO, check, load_records, main  # noqa: E402


def _copy_artifacts(dst) -> None:
    for g in GATES:
        src = os.path.join(REPO, g.file)
        if os.path.exists(src):
            shutil.copy(src, os.path.join(str(dst), g.file))


def _write(dst, file: str, doc: dict) -> None:
    """A synthetic artifact for a gate whose committed record is gone
    (the gate table outlives the records: a missing artifact skips)."""
    (dst / file).write_text(json.dumps(doc))


# passing values for the gates the synthetic-regression tests exercise
_HEALTHY_PREFIX = {"prefix": {
    "kv_prefix_hit_rate": 0.8, "prefill_reduction_x": 6.2, "lost": 0,
    "steady_state_compile_delta": 0,
}}
_HEALTHY_SPEC = {"spec": {
    "spec_speedup_x": 2.4, "accepted_tokens_per_dispatch": 3.1, "lost": 0,
    "steady_state_compile_delta_spec": 0,
}}
_HEALTHY_KERNELS = {"kernels": {
    "int8_capacity_ratio_x": 3.9, "steady_state_compile_delta_kernel": 0,
}}


class TestLoadRecords:
    def test_single_object_and_jsonl_and_garbage(self, tmp_path):
        p1 = tmp_path / "one.json"
        p1.write_text(json.dumps({"a": 1}))
        assert load_records(str(p1)) == [{"a": 1}]
        p2 = tmp_path / "stream.json"
        p2.write_text('{"a": 1}\nnot json\n{"b": 2}\n')
        assert load_records(str(p2)) == [{"a": 1}, {"b": 2}]
        assert load_records(str(tmp_path / "missing.json")) == []
        p3 = tmp_path / "cutoff.json"
        p3.write_text('{"a": ')  # killed mid-write
        assert load_records(str(p3)) == []

    def test_committed_jsonl_artifact_parses(self):
        # BENCH_pr2.json is a JSONL stream in-tree; the reader must not
        # choke on the shape the real history already contains
        recs = load_records(os.path.join(REPO, "BENCH_pr2.json"))
        assert len(recs) > 1


class TestGateTable:
    def test_committed_series_passes(self):
        """THE sentry contract: every declared gate holds on the actual
        committed artifacts (or is skipped for a not-yet-captured one).
        A failure here means a regression is sitting in-tree."""
        results, history = check(REPO)
        failed = [r for r in results if r["status"] == "fail"]
        assert failed == []
        assert history["gate_counts"]["pass"] >= 8  # the series is real

    def test_synthetic_regression_fails_and_is_named(self, tmp_path):
        _copy_artifacts(tmp_path)
        _write(tmp_path, "PREFIX_pr11.json", _HEALTHY_PREFIX)
        _write(tmp_path, "SPEC_pr16.json", {"spec": {
            **_HEALTHY_SPEC["spec"],
            "spec_speedup_x": 1.01,  # spec decoding stopped paying
            "lost": 3,  # and the crash lost requests
        }})
        results, _ = check(str(tmp_path))
        failed = {(r["file"], r["key"]) for r in results
                  if r["status"] == "fail"}
        assert ("SPEC_pr16.json", "spec.spec_speedup_x") in failed
        assert ("SPEC_pr16.json", "spec.lost") in failed
        # untouched artifacts keep passing — the failure is localized
        assert not any(f == "PREFIX_pr11.json" for f, _ in failed)
        assert any(r["file"] == "PREFIX_pr11.json" and r["status"] == "pass"
                   for r in results)

    def test_missing_artifact_skips_not_fails(self, tmp_path):
        results, history = check(str(tmp_path))  # empty dir: all skip
        assert all(r["status"] == "skip" for r in results)
        assert history["gate_counts"]["fail"] == 0

    def test_compile_delta_gate_is_an_invariant(self, tmp_path):
        _copy_artifacts(tmp_path)
        _write(tmp_path, "PREFIX_pr11.json", {"prefix": {
            **_HEALTHY_PREFIX["prefix"],
            "steady_state_compile_delta": 2,  # silent recompiles
        }})
        results, _ = check(str(tmp_path))
        bad = [r for r in results
               if r["key"] == "prefix.steady_state_compile_delta"]
        assert bad[0]["status"] == "fail" and bad[0]["value"] == 2


class TestCLI:
    def test_exit_zero_writes_history(self, tmp_path):
        _copy_artifacts(tmp_path)
        out = tmp_path / "PERF_HISTORY.json"
        rc = main(["--dir", str(tmp_path), "--out", str(out)])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert doc["gate_counts"]["fail"] == 0
        assert {g["status"] for g in doc["gates"]} <= {"pass", "skip"}

    def test_exit_nonzero_on_regression(self, tmp_path):
        _copy_artifacts(tmp_path)
        _write(tmp_path, "KERNELS_pr17.json", {"kernels": {
            **_HEALTHY_KERNELS["kernels"], "int8_capacity_ratio_x": 1.0,
        }})
        rc = main(["--dir", str(tmp_path), "--out", str(tmp_path / "h.json")])
        assert rc == 1
        # the roll-up is still written: the regression is visible in-tree
        doc = json.loads((tmp_path / "h.json").read_text())
        assert doc["gate_counts"]["fail"] == 1

    def test_headline_series_collects_bench_history(self, tmp_path):
        (tmp_path / "BENCH_pr2.json").write_text(
            json.dumps({"metric": "m1", "value": 10.0, "unit": "x"}) + "\n"
            + json.dumps({"probe": {"platform": "tpu"}}) + "\n")
        (tmp_path / "BENCH_r09.json").write_text(
            json.dumps({"n": 9, "parsed": {"metric": "m1", "value": 12.0}}))
        _, history = check(str(tmp_path))
        series = history["headline_series"]["m1"]
        assert [s["value"] for s in series] == [10.0, 12.0]
        assert series[0]["source"] == "BENCH_pr2.json"

"""Adaptive profiling + drift sentry (PR-18): TriggeredProfiler rings /
triggers / rate-limited capture bundles, DriftDetector's three channels
(timing EWMA vs frozen baseline, kernel-selection staleness — the
runtime complement of rlint R106 — and measured vs roofline prediction),
and the end-to-end feed through the compile registry's attribution
worker.

The acceptance demo lives in ``TestAttributionFeed``: a program whose
fingerprint was baked under ``RL_TPU_KERNELS_INTERPRET=1`` keeps
dispatching after ``RL_TPU_NO_KERNELS=paged_attention`` lands mid-run —
the detector must fire ``kernel_selection`` within a bounded number of
sampled dispatches and the profiler bundle's meta must name the
regressed program. The burn-rate trigger is exercised through the real
``ServingFleet._profiler_tick`` path with a frozen clock so repeated
monitor sweeps produce EXACTLY one rate-limited capture."""

import json
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from rl_tpu.compile import ExecutableStore, ProgramRegistry
from rl_tpu.obs import (
    DriftDetector,
    MetricsRegistry,
    TraceRecorder,
    TriggeredProfiler,
    set_drift_detector,
    set_profiler,
    set_registry,
    set_tracer,
)


@pytest.fixture
def fresh_obs():
    """Fresh registry+tracer swapped in process-wide (the profiler and
    detector resolve globals at event time); restored after."""
    reg, tracer = MetricsRegistry(), TraceRecorder()
    prev_reg, prev_tracer = set_registry(reg), set_tracer(tracer)
    yield reg, tracer
    set_registry(prev_reg)
    set_tracer(prev_tracer)


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, s: float) -> None:
        self.t += s


def _meta(bundle: str) -> dict:
    with open(os.path.join(bundle, "meta.json")) as f:
        return json.load(f)


# -- TriggeredProfiler ---------------------------------------------------------


class TestTriggeredProfiler:
    def test_ring_feed_and_snapshot(self, tmp_path):
        prof = TriggeredProfiler(str(tmp_path), ring_capacity=4)
        for i in range(10):
            prof.record_dispatch("prog_a", 0.01 * (i + 1))
        prof.record_dispatch("prog_b", 0.5)
        snap = prof.ring_snapshot()
        a = snap["prog_a"]
        assert a["samples"] == 10
        assert len(a["recent_s"]) == 4  # bounded by ring_capacity
        assert a["mean_s"] == pytest.approx(0.055)
        assert a["p99_recent_s"] == pytest.approx(0.10)
        assert snap["prog_b"]["samples"] == 1
        assert prof.snapshot()["programs_ringed"] == 2

    def test_capture_bundle_contents(self, tmp_path, fresh_obs):
        _, tracer = fresh_obs
        with tracer.span("serving.decode"):
            pass
        prof = TriggeredProfiler(str(tmp_path), trace_s=0.0)
        prof.record_dispatch("serving.decode", 0.02)
        path = prof.trigger("manual", {"source": "test"})
        assert path is not None and os.path.isdir(path)
        assert os.path.basename(path).startswith("profile-manual-")
        meta = _meta(path)
        assert meta["trigger"] == "manual"
        assert meta["detail"] == {"source": "test"}
        assert meta["failed_artifacts"] == []
        assert isinstance(meta["jax_trace"], str)  # captured | unsupported:...
        with open(os.path.join(path, "timings.json")) as f:
            timings = json.load(f)
        assert timings["serving.decode"]["samples"] == 1
        with open(os.path.join(path, "trace.json")) as f:
            trace = json.load(f)
        names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "X"]
        assert "serving.decode" in names

    def test_rate_limit_suppresses_then_interval_reopens(self, tmp_path, fresh_obs):
        reg, _ = fresh_obs
        clock = FakeClock()
        prof = TriggeredProfiler(str(tmp_path), trace_s=0.0,
                                 min_interval_s=30.0, clock=clock)
        assert prof.trigger("spike") is not None
        assert prof.trigger("spike") is None  # inside the interval
        assert prof.suppressed == {"spike": 1}
        clock.advance(31.0)
        assert prof.trigger("spike") is not None
        assert prof.fired == {"spike": 2}
        text = reg.render()
        assert 'rl_tpu_profiler_captures_total{trigger="spike"} 2' in text
        assert 'rl_tpu_profiler_suppressed_total{trigger="spike"} 1' in text

    def test_force_bypasses_interval_but_not_cap(self, tmp_path, fresh_obs):
        clock = FakeClock()
        prof = TriggeredProfiler(str(tmp_path), trace_s=0.0,
                                 min_interval_s=3600.0, max_captures=2,
                                 clock=clock)
        assert prof.trigger("a") is not None
        assert prof.trigger("b", force=True) is not None  # interval bypassed
        assert prof.trigger("c", force=True) is None  # hard cap holds
        assert len(prof.captures) == 2

    def test_trigger_never_raises_on_broken_dir(self, tmp_path):
        blocker = tmp_path / "not_a_dir"
        blocker.write_text("")  # a file where the bundle dir must go
        prof = TriggeredProfiler(str(blocker / "sub"), trace_s=0.0)
        assert prof.trigger("manual") is None  # swallowed, not raised

    def test_poll_runs_conditions_first_hit_wins(self, tmp_path, fresh_obs):
        prof = TriggeredProfiler(str(tmp_path), trace_s=0.0, min_interval_s=0.0)
        prof.add_trigger("broken", lambda: (_ for _ in ()).throw(RuntimeError()))
        prof.add_trigger("hit", lambda: {"n": 1})
        prof.add_trigger("also_hit", lambda: {"n": 2})
        path = prof.poll()
        assert path is not None
        assert sum(prof.fired.values()) == 1  # one capture per poll
        assert _meta(path)["detail"] == {"n": 1} or _meta(path)["detail"] == {"n": 2}

    def test_p99_spike_fires_on_single_outlier(self, tmp_path, fresh_obs):
        prof = TriggeredProfiler(str(tmp_path), trace_s=0.0,
                                 min_interval_s=0.0)
        prof.arm_p99_spike(zscore=4.0, min_samples=16)
        for _ in range(31):
            prof.record_dispatch("steady", 0.010)
        assert prof.poll() is None  # flat history: no spike
        prof.record_dispatch("steady", 0.200)  # 20x outlier lands
        path = prof.poll()
        assert path is not None
        meta = _meta(path)
        assert meta["trigger"] == "p99_spike"
        assert meta["detail"]["program"] == "steady"
        assert meta["detail"]["zscore"] > 4.0

    def test_compile_delta_trigger_fires_and_rearms(self, tmp_path, fresh_obs,
                                                    monkeypatch):
        from rl_tpu.compile import metrics as cmetrics

        box = {"n": 7}
        monkeypatch.setattr(cmetrics, "compiles_total", lambda: box["n"])
        prof = TriggeredProfiler(str(tmp_path), trace_s=0.0, min_interval_s=0.0)
        prof.arm_compile_delta()  # baseline = 7
        assert prof.poll() is None
        box["n"] = 9  # two steady-state compiles sneak in
        path = prof.poll()
        assert path is not None
        assert _meta(path)["detail"] == {"compiles": 2, "total": 9}
        assert prof.poll() is None  # re-armed at the new baseline


# -- DriftDetector -------------------------------------------------------------


class TestDriftDetector:
    def test_timing_drift_fires_gauge_counter_and_profiler(self, tmp_path,
                                                           fresh_obs):
        reg, _ = fresh_obs
        prof = TriggeredProfiler(str(tmp_path), trace_s=0.0, min_interval_s=0.0)
        det = DriftDetector(tolerance=1.5, baseline_samples=4, alpha=1.0,
                            refire_s=0.0, profiler=prof)
        for _ in range(4):
            assert det.observe("serving.decode", 0.010) == []
        assert det.observe("serving.decode", 0.012) == []  # within tolerance
        events = det.observe("serving.decode", 0.050)  # 5x the baseline
        assert [e["kind"] for e in events] == ["timing"]
        assert events[0]["program"] == "serving.decode"
        assert events[0]["ratio"] == pytest.approx(5.0)
        text = reg.render()
        assert ('rl_tpu_program_drift_events_total'
                '{program="serving.decode",kind="timing"} 1') in text
        # the capture bundle names the regressed program
        assert len(prof.captures) == 1
        meta = _meta(prof.captures[0])
        assert meta["trigger"] == "drift"
        assert meta["detail"]["program"] == "serving.decode"
        snap = det.snapshot()
        assert snap["events_total"] == 1
        assert snap["programs"]["serving.decode"]["ratio"] == pytest.approx(5.0)

    def test_drift_gauge_tracks_worst_channel(self, fresh_obs):
        reg, _ = fresh_obs
        det = DriftDetector(tolerance=2.0, baseline_samples=2, alpha=1.0,
                            refire_s=0.0)
        det.observe("p", 0.010)
        det.observe("p", 0.010)
        det.observe("p", 0.010)  # ratio 1.0 -> gauge 0.5
        g = reg.gauge("rl_tpu_program_drift", labels=("program",))
        assert g.value({"program": "p"}) == pytest.approx(0.5)
        det.observe("p", 0.030)  # ratio 3.0 -> gauge 1.5 (> 1 = drifted)
        assert g.value({"program": "p"}) == pytest.approx(1.5)

    def test_refire_rate_limited_per_program_and_kind(self, fresh_obs):
        clock = FakeClock()
        det = DriftDetector(tolerance=1.5, baseline_samples=2, alpha=1.0,
                            refire_s=60.0, clock=clock)
        det.observe("p", 0.01)
        det.observe("p", 0.01)
        assert len(det.observe("p", 0.05)) == 1
        assert det.observe("p", 0.05) == []  # still inside refire_s
        clock.advance(61.0)
        assert len(det.observe("p", 0.05)) == 1
        assert det.snapshot()["programs"]["p"]["events"] == {"timing": 2}

    def test_predicted_channel_vs_roofline(self, fresh_obs, monkeypatch):
        import types

        reg, _ = fresh_obs
        monkeypatch.setenv("RL_TPU_PEAK_FLOPS", "1e12")
        monkeypatch.delenv("RL_TPU_PEAK_BYTES_PER_S", raising=False)
        # 1e9 flops at 1e12 flops/s -> predicted_s = 1e-3
        prog = types.SimpleNamespace(
            fingerprint="",
            ir_report=types.SimpleNamespace(
                cost=types.SimpleNamespace(flops=1e9, bytes=0.0)),
        )
        det = DriftDetector(tolerance=1.5, baseline_samples=2, alpha=1.0,
                            refire_s=0.0)
        det.observe("p", 0.010, prog=prog)
        det.observe("p", 0.010, prog=prog)
        events = det.observe("p", 0.010, prog=prog)  # 10x the prediction
        assert [e["kind"] for e in events] == ["predicted"]
        assert events[0]["ratio"] == pytest.approx(10.0)
        g = reg.gauge("rl_tpu_program_drift_vs_predicted", labels=("program",))
        assert g.value({"program": "p"}) == pytest.approx(10.0)

    def test_selection_drift_channel_runtime_r106(self, fresh_obs, monkeypatch):
        import types

        import rl_tpu.kernels  # noqa: F401  (self-registers the kernel set)
        from rl_tpu.kernels.registry import kernels_fingerprint

        monkeypatch.setenv("RL_TPU_KERNELS_INTERPRET", "1")
        monkeypatch.delenv("RL_TPU_NO_KERNELS", raising=False)
        # fingerprint baked the way serving bakes it: kernels fragment
        # embedded in a repr tuple
        prog = types.SimpleNamespace(
            fingerprint=repr(("M", "cfg", kernels_fingerprint())),
            ir_report=None,
        )
        det = DriftDetector(tolerance=1.5, baseline_samples=2, alpha=1.0,
                            refire_s=0.0)
        det.observe("p", 0.01, prog=prog)
        det.observe("p", 0.01, prog=prog)
        assert det.observe("p", 0.01, prog=prog) == []  # selections agree
        monkeypatch.setenv("RL_TPU_NO_KERNELS", "paged_attention")
        events = det.observe("p", 0.01, prog=prog)
        assert [e["kind"] for e in events] == ["kernel_selection"]
        assert events[0]["kernels"] == ["paged_attention"]
        reg, _ = fresh_obs
        g = reg.gauge("rl_tpu_program_drift", labels=("program",))
        assert g.value({"program": "p"}) > 1.0  # selection drift alone drifts

    def test_observe_never_raises(self):
        det = DriftDetector(tolerance=1.5)
        assert det.observe("p", float("nan")) == []
        assert det.observe("p", "bogus") == []  # type: ignore[arg-type]

    def test_tolerance_must_exceed_one(self):
        with pytest.raises(ValueError):
            DriftDetector(tolerance=0.9)


# -- end-to-end: the attribution-worker feed ----------------------------------


class TestAttributionFeed:
    def test_worker_keeps_no_sample_while_idle(self, tmp_path):
        """The attribution worker credits a sampled dispatch and lets go:
        between samples it must hold neither the program (whose function
        pins its owner: ``grpo.update`` the trainer with its parameters
        and optimizer state) nor the output leaf."""
        import gc
        import weakref

        from rl_tpu.compile.registry import _ATTR_SAMPLE_EVERY

        class Owner:
            def double(self, x):
                return x * 2.0

        creg = ProgramRegistry(store=ExecutableStore(str(tmp_path / "store")))
        owner = Owner()
        prog = creg.register("t.pin_demo", owner.double)
        x = jnp.ones((4, 4), jnp.float32)
        for _ in range(_ATTR_SAMPLE_EVERY):  # the last of these is sampled
            out = prog(x)
        deadline = time.monotonic() + 30.0
        while prog.stats["device_samples"] < 1:  # the worker drains async
            assert time.monotonic() < deadline, "sample never credited"
            time.sleep(0.01)
        time.sleep(0.05)  # the worker is back in its queue's get()
        alive, leaf = weakref.ref(owner), weakref.ref(out)
        del owner, prog, out
        gc.collect()
        assert alive() is None, "the idle worker pins the program's owner"
        assert leaf() is None, "the idle worker pins the sampled output"

    def test_forced_kernel_fallback_detected_within_sampled_dispatches(
            self, tmp_path, fresh_obs, monkeypatch):
        """The PR-18 acceptance demo: a program registered (and
        fingerprinted) under the interpret kernel regime keeps running
        after ``RL_TPU_NO_KERNELS=paged_attention`` lands mid-run. The
        drift detector — fed only by the attribution worker's sampled
        dispatches — must fire ``kernel_selection`` within a bounded
        number of dispatches, and the profiler bundle must name the
        regressed program."""
        import rl_tpu.kernels  # noqa: F401
        from rl_tpu.kernels.registry import kernels_fingerprint

        reg_obs, tracer = fresh_obs
        monkeypatch.setenv("RL_TPU_KERNELS_INTERPRET", "1")
        monkeypatch.delenv("RL_TPU_NO_KERNELS", raising=False)
        fp = repr(("TinyModel", "cfg", kernels_fingerprint()))
        creg = ProgramRegistry(store=ExecutableStore(str(tmp_path / "store")))
        prog = creg.register("t.drift_demo", lambda x: x * 2.0, fingerprint=fp)

        prof = TriggeredProfiler(str(tmp_path / "prof"), trace_s=0.0,
                                 min_interval_s=0.0)
        det = DriftDetector(tolerance=1.5, baseline_samples=2, refire_s=0.0,
                            profiler=prof)
        prev_p, prev_d = set_profiler(prof), set_drift_detector(det)
        try:
            x = jnp.ones((4, 4), jnp.float32)
            for _ in range(32):  # >= (baseline_samples+1) sampled dispatches
                prog(x)
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:  # attr worker drains async
                if det.snapshot()["programs"].get("t.drift_demo", {}).get(
                        "baseline_s") is not None:
                    break
                time.sleep(0.01)
            else:
                raise AssertionError("baseline never froze (attr feed dead?)")

            monkeypatch.setenv("RL_TPU_NO_KERNELS", "paged_attention")
            fired, n_calls = [], 0
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline and not fired:
                prog(x)
                n_calls += 1
                fired = [e for e in det.snapshot()["fired"]
                         if e["kind"] == "kernel_selection"]
            assert fired, "drift never fired after the forced fallback"
            # within N sampled dispatches: the very next sampled dispatch
            # carries the stale fingerprint; allow queue-drain slack
            assert n_calls <= 32 * 8
            assert fired[0]["program"] == "t.drift_demo"
            assert fired[0]["kernels"] == ["paged_attention"]
            assert prof.captures, "drift fired but no profiler capture"
            meta = _meta(prof.captures[0])
            assert meta["trigger"] == "drift"
            assert meta["detail"]["program"] == "t.drift_demo"
            assert meta["detail"]["kind"] == "kernel_selection"
        finally:
            set_profiler(prev_p)
            set_drift_detector(prev_d)

    def test_disarmed_feed_is_a_noop(self, tmp_path):
        """With no profiler/detector armed (the default), sampled
        dispatches must flow through _notify_dispatch untouched."""
        from rl_tpu.obs.drift import get_drift_detector
        from rl_tpu.obs.profiling import get_profiler

        assert get_profiler() is None and get_drift_detector() is None
        creg = ProgramRegistry(store=ExecutableStore(str(tmp_path)))
        prog = creg.register("t.disarmed", lambda x: x + 1.0)
        x = jnp.ones((2, 2), jnp.float32)
        for _ in range(16):
            prog(x)  # crosses a sampled dispatch; must not raise


# -- the fleet burn-rate trigger ----------------------------------------------


class TestFleetBurnTrigger:
    def test_burn_rate_produces_exactly_one_rate_limited_capture(
            self, tmp_path, fresh_obs):
        """Chaos-window contract: a TTFT SLO burning hot across many
        monitor sweeps yields EXACTLY one capture — the rate limiter
        absorbs the rest as counted suppressions."""
        from rl_tpu.models import (
            ContinuousBatchingEngine,
            TransformerConfig,
            TransformerLM,
        )
        from rl_tpu.models.fleet import ServingFleet

        reg, _ = fresh_obs
        import jax

        cfg = TransformerConfig(vocab_size=97, d_model=64, n_layers=2,
                                n_heads=4, d_ff=128, max_seq_len=128,
                                dtype=jnp.float32)
        m = TransformerLM(cfg)
        params = m.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
        eng = ContinuousBatchingEngine(
            m, params, n_slots=2, block_size=8, n_blocks=65,
            prompt_buckets=(16,), greedy=True, seed=0)
        eng.submit(np.arange(8), 4)
        eng.run()

        clock = FakeClock()
        prof = TriggeredProfiler(str(tmp_path), trace_s=0.0,
                                 min_interval_s=3600.0, clock=clock)
        prev = set_profiler(prof)
        fleet = ServingFleet([eng], registry=reg, probe_interval_s=0.01).start()
        try:
            for _ in range(50):  # every TTFT blows the objective threshold
                fleet._slo_ttft.record(30.0)
            assert fleet._slo_ttft.burn_rate(60.0) > fleet._profile_burn_threshold
            for _ in range(5):  # five monitor sweeps worth of ticks
                fleet._profiler_tick()
            assert prof.fired.get("slo_burn") == 1
            assert len(prof.captures) == 1
            assert prof.suppressed.get("slo_burn", 0) >= 4
            meta = _meta(prof.captures[0])
            assert meta["trigger"] == "slo_burn"
            assert meta["detail"]["slo"] == "fleet_ttft"
        finally:
            fleet.shutdown()
            set_profiler(prev)

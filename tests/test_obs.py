"""Unified observability subsystem tests (PR: in-program device metrics,
cross-thread Perfetto tracing, /metrics surface): Prometheus rendering,
trace export round-trip + per-thread span nesting, the timeit
thread-safety regression, logger handle lifecycle, liveness/preemption
telemetry, DeviceMetrics accumulate-in-jit + single-drain, and the HTTP
scrape endpoint."""

import json
import random
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from rl_tpu.obs import (
    MetricsHTTPServer,
    MetricsRegistry,
    StreamingHistogram,
    TraceRecorder,
    TriggeredProfiler,
    merge_histograms,
    set_registry,
    set_tracer,
    wire_tracer_obs,
)
from rl_tpu.obs.device import DeviceMetrics


@pytest.fixture
def fresh_obs():
    """Swap in a fresh registry+tracer so tests never see each other's (or
    the import-time hooks') series; restore the process defaults after."""
    reg, tracer = MetricsRegistry(), TraceRecorder()
    prev_reg, prev_tracer = set_registry(reg), set_tracer(tracer)
    yield reg, tracer
    set_registry(prev_reg)
    set_tracer(prev_tracer)


# -- registry -----------------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_render_prometheus_text(self):
        reg = MetricsRegistry()
        c = reg.counter("rl_tpu_things_total", "things", labels=("kind",))
        c.inc(2, {"kind": "a"})
        c.inc(1, {"kind": "b"})
        g = reg.gauge("rl_tpu_depth", "queue depth")
        g.set(3.5)
        text = reg.render()
        assert "# TYPE rl_tpu_things_total counter" in text
        assert '# HELP rl_tpu_things_total things' in text
        assert 'rl_tpu_things_total{kind="a"} 2' in text
        assert 'rl_tpu_things_total{kind="b"} 1' in text
        assert "rl_tpu_depth 3.5" in text
        assert text.endswith("\n")

    def test_counter_set_total_is_monotone(self):
        c = MetricsRegistry().counter("x_total")
        c.set_total(10)
        c.set_total(4)  # a stale drain (older dispatch) must not rewind
        assert c.value() == 10.0

    def test_counter_rejects_negative_inc(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("x_total").inc(-1)

    def test_gauge_set_fn_evaluated_at_render(self):
        reg = MetricsRegistry()
        box = {"v": 1.0}
        reg.gauge("live").set_fn(lambda: box["v"])
        assert "live 1" in reg.render()
        box["v"] = 2.0
        assert "live 2" in reg.render()

    def test_histogram_cumulative_buckets_and_sum_count(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
        h.observe_many([0.05, 0.5, 5.0, 50.0])
        text = reg.render()
        assert 'lat_bucket{le="0.1"} 1' in text
        assert 'lat_bucket{le="1"} 2' in text
        assert 'lat_bucket{le="10"} 3' in text
        assert 'lat_bucket{le="+Inf"} 4' in text
        assert "lat_count 4" in text
        assert "lat_sum 55.55" in text

    def test_histogram_set_cumulative_overwrites(self):
        h = MetricsRegistry().histogram("h", buckets=(1.0, 2.0))
        h.set_cumulative([3, 2, 1], 9.0)
        h.set_cumulative([4, 2, 1], 11.0)  # later drain replaces
        snap = h.snapshot()[""]
        assert snap["counts"] == [4.0, 2.0, 1.0]
        assert snap["sum"] == 11.0 and snap["count"] == 7.0
        with pytest.raises(ValueError):
            h.set_cumulative([1, 2], 0.0)  # wrong bucket arity

    def test_get_or_create_idempotent_but_type_mismatch_raises(self):
        reg = MetricsRegistry()
        assert reg.counter("n") is reg.counter("n")
        with pytest.raises(ValueError):
            reg.gauge("n")
        with pytest.raises(ValueError):
            reg.counter("n", labels=("x",))

    def test_collector_runs_before_render_and_unregisters(self):
        reg = MetricsRegistry()
        g = reg.gauge("scraped")
        calls = []
        fn = reg.register_collector(lambda: (calls.append(1), g.set(len(calls)))[0])
        assert "scraped 1" in reg.render()
        assert "scraped 2" in reg.render()
        reg.unregister_collector(fn)
        reg.render()
        assert len(calls) == 2

    def test_snapshot_is_json_serializable(self):
        reg = MetricsRegistry()
        reg.counter("c_total", labels=("k",)).inc(1, {"k": "a"})
        reg.histogram("h", buckets=(1.0,)).observe(0.5)
        json.dumps(reg.snapshot())  # must not raise


# -- tracing ------------------------------------------------------------------


def _spans_by_tid(trace):
    out = {}
    for ev in trace["traceEvents"]:
        if ev["ph"] == "X":
            out.setdefault(ev["tid"], []).append(ev)
    return out


def _assert_stack_discipline(spans):
    """Spans on one thread must nest like a call stack: sorted by start
    (ties broken longest-first), each span either starts after the current
    innermost span ends or ends within it."""
    ends = []
    for ev in sorted(spans, key=lambda e: (e["ts"], -e["dur"])):
        while ends and ev["ts"] >= ends[-1] - 1e-9:
            ends.pop()
        if ends:
            assert ev["ts"] + ev["dur"] <= ends[-1] + 1e-6
        ends.append(ev["ts"] + ev["dur"])


class TestTraceRecorder:
    def test_export_round_trip_multi_thread_nested(self, tmp_path):
        tracer = TraceRecorder()

        def work():
            with tracer.span("outer"):
                for _ in range(3):
                    with tracer.span("inner"):
                        pass
            tracer.instant("tick", {"n": 1})
            tracer.counter("depth", {"q": 2.0})

        threads = [threading.Thread(target=work, name=f"w{i}") for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        work()  # main thread too

        path = tmp_path / "trace.json"
        trace = tracer.export(str(path))
        loaded = json.loads(path.read_text())  # round-trips through disk
        assert loaded == json.loads(json.dumps(trace))
        evs = loaded["traceEvents"]
        names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert {"w0", "w1", "w2"} <= names  # every thread got a name track
        by_tid = _spans_by_tid(loaded)
        assert len(by_tid) == 4
        for tid, spans in by_tid.items():
            assert [e["name"] for e in spans].count("outer") == 1
            assert [e["name"] for e in spans].count("inner") == 3
            _assert_stack_discipline(spans)
        assert sum(e["ph"] == "i" for e in evs) == 4
        assert sum(e["ph"] == "C" for e in evs) == 4

    def test_ring_bounded_drop_oldest(self):
        tracer = TraceRecorder(capacity=8)
        for i in range(20):
            tracer.instant(f"e{i}")
        evs = [e for e in tracer.export()["traceEvents"] if e["ph"] == "i"]
        assert [e["name"] for e in evs] == [f"e{i}" for i in range(12, 20)]

    def test_disabled_records_nothing(self):
        tracer = TraceRecorder(enabled=False)
        with tracer.span("x"):
            tracer.instant("y")
        assert all(e["ph"] == "M" for e in tracer.export()["traceEvents"])


# -- timeit (thread-safety regression + tracer bridge) ------------------------


class TestTimeit:
    def test_concurrent_timing_counts_exact(self):
        """The pre-PR registry was a bare defaultdict mutated from the
        trainer loop AND collector threads — lost updates under the race.
        8 threads x 500 enters must count exactly 4000."""
        from rl_tpu.utils.timing import timeit

        timeit.erase()
        N, M = 8, 500

        def work():
            for _ in range(M):
                with timeit("obs_race"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        with timeit._REG_LOCK:
            total_s, _last, count = timeit._REG["obs_race"]
        assert count == N * M
        assert total_s >= 0.0
        timeit.erase()

    def test_timeit_emits_tracer_spans(self, fresh_obs):
        from rl_tpu.obs import get_tracer
        from rl_tpu.utils.timing import timeit

        _, tracer = fresh_obs
        with timeit("timed_block"):
            pass
        with get_tracer().span("rf_block"):
            pass
        names = [
            e["name"] for e in tracer.export()["traceEvents"] if e["ph"] == "X"
        ]
        assert "timed_block" in names and "rf_block" in names
        timeit.erase()


# -- logger lifecycle ---------------------------------------------------------


class TestLoggerLifecycle:
    def test_csv_logger_context_manager_closes_handles(self, tmp_path):
        from rl_tpu.record import CSVLogger

        with CSVLogger("exp", log_dir=str(tmp_path)) as lg:
            lg.log_scalar("loss", 1.0, step=0)
            assert len(lg._files) == 1
        assert lg._files == {}  # handles released on exit
        lg.close()  # idempotent

    def test_csv_logger_lru_bounds_open_files_and_keeps_rows(self, tmp_path):
        from rl_tpu.record import CSVLogger

        lg = CSVLogger("exp", log_dir=str(tmp_path), max_open_files=2)
        for step in range(3):
            for name in ("a", "b", "c"):  # 3 streams > 2 handles
                lg.log_scalar(name, float(step), step)
            assert len(lg._files) <= 2
        lg.close()
        for name in ("a", "b", "c"):  # eviction reopened in append mode
            rows = (tmp_path / "exp" / f"{name}.csv").read_text().strip().splitlines()
            assert len(rows) == 3

    def test_multi_logger_close_fans_out_and_aggregates_errors(self):
        from rl_tpu.record.loggers import Logger, MultiLogger

        closed = []

        class Ok(Logger):
            def __init__(self, tag):
                super().__init__(tag)
                self.tag = tag

            def log_scalar(self, *a, **k):
                pass

            def close(self):
                closed.append(self.tag)

        class Bad(Ok):
            def close(self):
                super().close()
                raise RuntimeError("sink died")

        ml = MultiLogger(Ok("a"), Bad("b"), Ok("c"))
        with pytest.raises(RuntimeError, match="sink died"):
            ml.close()
        assert closed == ["a", "b", "c"]  # the failure did not skip "c"


# -- liveness / resilience telemetry ------------------------------------------


class TestLivenessTelemetry:
    def test_watchdog_death_emits_counter_and_instant(self, fresh_obs):
        import time as _time

        from rl_tpu.comm import Watchdog

        reg, tracer = fresh_obs
        wd = Watchdog(timeout=0.01)
        wd.register("actor0")
        _time.sleep(0.03)
        assert wd.check() == ["actor0"]
        assert wd.check() == []  # reported exactly once
        c = reg.counter(
            "rl_tpu_watchdog_deaths_total",
            "actors declared dead by the watchdog",
            labels=("name",),
        )
        assert c.value({"name": "actor0"}) == 1.0
        instants = [
            e for e in tracer.export()["traceEvents"] if e["ph"] == "i"
        ]
        assert any(
            e["name"] == "watchdog_death" and e["args"]["name"] == "actor0"
            for e in instants
        )

    def test_preemption_emits_counter_and_instant_once(self, fresh_obs):
        from rl_tpu.trainers.resilience import PreemptionHandler

        reg, tracer = fresh_obs

        class FakeTrainer:
            step_count = 7
            checkpoint = None
            stopped = False

            def request_stop(self):
                self.stopped = True

        h = PreemptionHandler()
        tr = FakeTrainer()
        h(tr)  # flag not raised: no-op
        assert not tr.stopped
        h.preempt()
        h(tr)
        h(tr)  # already handled: no double count
        assert tr.stopped
        assert reg.counter("rl_tpu_preemptions_total").value() == 1.0
        evs = tracer.export()["traceEvents"]
        pre = [e for e in evs if e["ph"] == "i" and e["name"] == "preemption"]
        assert len(pre) == 1 and pre[0]["args"]["step"] == 7


# -- device metrics -----------------------------------------------------------


class TestDeviceMetrics:
    SPEC = DeviceMetrics(
        counters=("updates",),
        gauges=("loss",),
        histograms={"td": (0.1, 1.0, 10.0)},
    )

    def test_accumulate_in_jit_then_single_drain_exact(self):
        spec = self.SPEC

        @jax.jit
        def step(dm, vals):
            dm = spec.inc(dm, "updates")
            dm = spec.set_gauge(dm, "loss", vals.mean())
            return spec.observe(dm, "td", vals)

        dm = spec.init()
        vals = jnp.asarray([0.05, 0.5, 5.0, 50.0])
        for _ in range(3):
            dm = step(dm, vals)
        DeviceMetrics.drain_async(dm)
        flat = spec.to_flat(DeviceMetrics.drain(dm))
        assert flat["updates"] == 3.0
        assert flat["loss"] == pytest.approx(float(vals.mean()))
        assert flat["td"]["counts"] == [3.0, 3.0, 3.0, 3.0]
        assert flat["td"]["sum"] == pytest.approx(3 * 55.55, rel=1e-5)

    def test_publish_lands_in_registry_render(self, fresh_obs):
        reg, _ = fresh_obs
        spec = self.SPEC
        dm = spec.init()
        dm = spec.inc(dm, "updates", 5.0)
        dm = spec.observe(dm, "td", jnp.asarray([0.5]))
        spec.publish(DeviceMetrics.drain(dm), reg)
        text = reg.render()
        assert "rl_tpu_device_updates_total 5" in text
        assert 'rl_tpu_device_td_bucket{le="+Inf"} 1' in text
        assert "rl_tpu_device_loss 0" in text

    def test_schema_is_hashable_and_scan_safe(self):
        spec = self.SPEC
        hash(spec)  # closable over by jit

        def body(dm, _):
            return spec.inc(dm, "updates"), None

        dm, _ = jax.lax.scan(body, spec.init(), jnp.arange(4))
        assert float(dm["counters"]["updates"]) == 4.0


# -- HTTP surface -------------------------------------------------------------


class TestMetricsHTTP:
    def test_scrape_and_404(self):
        reg = MetricsRegistry()
        reg.counter("rl_tpu_up_total").inc(3)
        srv = MetricsHTTPServer(reg).start()
        try:
            host, port = srv.address
            with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10
            ) as r:
                assert r.status == 200
                assert r.headers["Content-Type"].startswith("text/plain")
                body = r.read().decode()
            assert "rl_tpu_up_total 3" in body
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"http://{host}:{port}/nope", timeout=10)
            assert ei.value.code == 404
        finally:
            srv.shutdown()


# -- trace drop accounting (PR-18) --------------------------------------------


class TestTraceDrops:
    def test_dropped_events_counts_overwrites(self):
        tracer = TraceRecorder(capacity=8)
        for i in range(20):
            tracer.instant(f"e{i}")
        # 20 events into an 8-slot ring: 12 oldest were overwritten
        assert tracer.dropped_events() == {"MainThread": 12}

    def test_export_metadata_carries_drop_count_only_when_nonzero(self):
        tracer = TraceRecorder(capacity=4)
        for i in range(10):
            tracer.instant(f"e{i}")
        metas = [e for e in tracer.export()["traceEvents"] if e["ph"] == "M"]
        assert metas[0]["args"] == {"name": "MainThread", "dropped": 6}
        tracer2 = TraceRecorder(capacity=64)
        tracer2.instant("fits")
        metas2 = [e for e in tracer2.export()["traceEvents"] if e["ph"] == "M"]
        assert "dropped" not in metas2[0]["args"]

    def test_clear_resets_drop_counts(self):
        tracer = TraceRecorder(capacity=2)
        for i in range(5):
            tracer.instant(f"e{i}")
        assert tracer.dropped_events()["MainThread"] == 3
        tracer.clear()
        assert tracer.dropped_events() == {"MainThread": 0}

    def test_per_thread_attribution(self):
        tracer = TraceRecorder(capacity=4)

        def noisy():
            for i in range(9):
                tracer.instant(f"n{i}")

        t = threading.Thread(target=noisy, name="noisy")
        t.start()
        t.join()
        tracer.instant("quiet")  # main thread: under capacity, zero drops
        drops = tracer.dropped_events()
        assert drops["noisy"] == 5
        assert drops["MainThread"] == 0  # zero-drop threads still listed

    def test_wire_tracer_obs_exports_counter(self, fresh_obs):
        reg, tracer = fresh_obs
        wire_tracer_obs(reg)
        wire_tracer_obs(reg)  # idempotent: no duplicate-collector explosion
        for i in range(10):
            tracer.instant(f"e{i}")
        # default capacity is large; force the drop path with a tiny ring
        small = TraceRecorder(capacity=4)
        prev = set_tracer(small)
        try:
            for i in range(10):
                small.instant(f"e{i}")
            text = reg.render()
        finally:
            set_tracer(prev)
        assert 'rl_tpu_trace_dropped_events_total{thread="MainThread"} 6' in text


# -- fleet-wide quantile merge (PR-18) ----------------------------------------


class TestHistogramMerge:
    def test_merged_quantiles_equal_pooled_raw_samples(self):
        """The fleet-gauge contract: merging per-member histograms is
        EXACTLY equivalent to one histogram fed every raw sample —
        bucket counts add, so every interpolated quantile is identical."""
        rng = random.Random(18)
        members = [StreamingHistogram() for _ in range(3)]
        pooled = StreamingHistogram()
        for i, h in enumerate(members):
            for _ in range(200 + 100 * i):  # deliberately uneven loads
                v = rng.lognormvariate(-2.0, 1.5)
                h.observe(v)
                pooled.observe(v)
        merged = merge_histograms(members)
        assert merged is not None
        for q in (0.5, 0.9, 0.99):
            assert merged.quantile(q) == pooled.quantile(q)
        assert merged.snapshot()["count"] == pooled.snapshot()["count"]

    def test_merge_requires_matching_edges(self):
        a = StreamingHistogram(edges=(0.1, 1.0))
        b = StreamingHistogram(edges=(0.2, 2.0))
        with pytest.raises(ValueError):
            a.merge(b)

    def test_merge_histograms_empty_iterable_is_none(self):
        assert merge_histograms([]) is None

    def test_merge_does_not_mutate_members(self):
        a, b = StreamingHistogram(), StreamingHistogram()
        a.observe(0.5)
        b.observe(0.7)
        before = (a.snapshot()["count"], b.snapshot()["count"])
        merge_histograms([a, b])
        assert (a.snapshot()["count"], b.snapshot()["count"]) == before


# -- HTTP debug surface (PR-18) -----------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.headers["Content-Type"], r.read()


def _post(url, data=b""):
    req = urllib.request.Request(url, data=data, method="POST")
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, r.read()


class TestHTTPDebugSurface:
    def test_healthz(self):
        srv = MetricsHTTPServer(MetricsRegistry()).start()
        try:
            host, port = srv.address
            status, ctype, body = _get(f"http://{host}:{port}/healthz")
            assert status == 200 and body == b"ok\n"
            assert ctype.startswith("text/plain")
        finally:
            srv.shutdown()

    def test_debug_state_round_trips_snapshot(self):
        snap = {"queued": 3, "members": [{"id": 0, "ok": True}]}
        srv = MetricsHTTPServer(MetricsRegistry(), state_fn=lambda: snap).start()
        try:
            host, port = srv.address
            status, ctype, body = _get(f"http://{host}:{port}/debug/state")
            assert status == 200 and ctype.startswith("application/json")
            assert json.loads(body) == snap
        finally:
            srv.shutdown()

    def test_debug_state_404_without_state_fn(self):
        srv = MetricsHTTPServer(MetricsRegistry()).start()
        try:
            host, port = srv.address
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://{host}:{port}/debug/state")
            assert ei.value.code == 404
        finally:
            srv.shutdown()

    def test_debug_state_bounds_oversize_snapshot(self):
        big = {"blob": "x" * 4096}
        srv = MetricsHTTPServer(
            MetricsRegistry(), state_fn=lambda: big, max_state_bytes=256
        ).start()
        try:
            host, port = srv.address
            _, _, body = _get(f"http://{host}:{port}/debug/state")
            doc = json.loads(body)
            assert doc["error"] == "state snapshot too large"
            assert doc["bytes"] > doc["limit"] == 256
        finally:
            srv.shutdown()

    def test_debug_state_raising_state_fn_degrades_to_error(self):
        def boom():
            raise RuntimeError("snapshot deadlocked")

        srv = MetricsHTTPServer(MetricsRegistry(), state_fn=boom).start()
        try:
            host, port = srv.address
            _, _, body = _get(f"http://{host}:{port}/debug/state")
            assert "snapshot deadlocked" in json.loads(body)["error"]
        finally:
            srv.shutdown()

    def test_post_profile_fires_manual_trigger(self, tmp_path):
        prof = TriggeredProfiler(str(tmp_path), trace_s=0.0)
        srv = MetricsHTTPServer(MetricsRegistry(), profiler=prof).start()
        try:
            host, port = srv.address
            status, body = _post(f"http://{host}:{port}/profile")
            assert status == 200
            capture = json.loads(body)["capture"]
            assert capture is not None
            meta = json.loads(
                open(f"{capture}/meta.json").read()
            )
            assert meta["trigger"] == "manual"
            assert meta["detail"] == {"source": "http"}
        finally:
            srv.shutdown()

    def test_post_profile_404_when_no_profiler_armed(self):
        from rl_tpu.obs.profiling import set_profiler

        prev = set_profiler(None)
        srv = MetricsHTTPServer(MetricsRegistry()).start()
        try:
            host, port = srv.address
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(f"http://{host}:{port}/profile")
            assert ei.value.code == 404
        finally:
            srv.shutdown()
            set_profiler(prev)

    def test_method_discipline_405(self):
        srv = MetricsHTTPServer(MetricsRegistry()).start()
        try:
            host, port = srv.address
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(f"http://{host}:{port}/profile")  # GET a POST route
            assert ei.value.code == 405
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(f"http://{host}:{port}/metrics")  # POST a GET route
            assert ei.value.code == 405
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(f"http://{host}:{port}/nope")
            assert ei.value.code == 404
        finally:
            srv.shutdown()

"""Causal request tracing, SLO burn-rate engine, and crash flight
recorder (ISSUE 12).

The acceptance spine: a fleet chaos run (injected ``fleet.engine_crash``
mid-decode) must render as ONE parent-linked trace tree spanning >= 3
threads and >= 1 TCP hop, with the failover re-dispatch span parented to
the original request span — verified here by walking the Perfetto
export. Around it: TraceContext propagation across thread and wire
boundaries, the timestamp-interleaved export fix, the SLO engine's
attainment/burn-rate math and gauges, the flight recorder's postmortem
bundle on an injected Supervisor budget exhaustion, and what a span
costs (ns a span against fixed limits, spans a decode launch)."""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rl_tpu.obs import (
    FlightRecorder,
    MetricsRegistry,
    Objective,
    SLOEngine,
    StreamingHistogram,
    TraceContext,
    TraceRecorder,
    carry_context,
    ctx_args,
    current_context,
    new_trace,
    set_registry,
    set_tracer,
    use_context,
)
from rl_tpu.obs.flight import set_flight_recorder

# imported at module scope (not inside tests): the lock_witness fixture
# wraps threading.Lock while armed, and stdlib modules imported mid-test
# (concurrent.futures.thread via the collectors) break under the wrap
from rl_tpu.collectors import AsyncHostCollector, ThreadedEnvPool
from rl_tpu.comm import TCPCommandClient, TCPCommandServer
from rl_tpu.comm.liveness import Watchdog
from rl_tpu.data.specs import Bounded, Composite, Unbounded
from rl_tpu.models import (
    ContinuousBatchingEngine,
    FinishedRequest,
    ServingFleet,
    TransformerConfig,
    TransformerLM,
)
from rl_tpu.resilience import Fault, FaultInjector, Supervisor, injection
from rl_tpu.resilience.faults import fault_point

# rlint runtime sanitizer: every lock created inside these tests is
# witnessed; any observed lock-order inversion fails the test at teardown
pytestmark = pytest.mark.usefixtures("lock_witness")


@pytest.fixture
def fresh_obs():
    """Fresh process-default registry + tracer (restored after); the
    propagation hooks all record into the process default, so tests must
    never see each other's events."""
    reg, tracer = MetricsRegistry(), TraceRecorder()
    prev_reg, prev_tracer = set_registry(reg), set_tracer(tracer)
    yield reg, tracer
    set_registry(prev_reg)
    set_tracer(prev_tracer)


def _events(tracer, name=None):
    evs = tracer.export()["traceEvents"]
    return [e for e in evs if name is None or e.get("name") == name]


# -- TraceContext ---------------------------------------------------------


class TestTraceContext:
    def test_child_links_under_parent_same_trace(self):
        root = new_trace()
        assert root.parent_id is None
        kid = root.child()
        assert kid.trace_id == root.trace_id
        assert kid.parent_id == root.span_id
        assert kid.span_id != root.span_id

    def test_wire_round_trip(self):
        kid = new_trace().child()
        assert TraceContext.from_wire(kid.to_wire()) == kid
        root = new_trace()
        assert "parent_id" not in root.to_wire()
        assert TraceContext.from_wire(root.to_wire()) == root

    def test_from_wire_tolerates_garbage(self):
        # old peers / hand-written clients: trace metadata must never
        # fail the control plane
        for junk in (None, "x", 7, [], {}, {"trace_id": 1, "span_id": "s"},
                     {"trace_id": "t"}):
            assert TraceContext.from_wire(junk) is None

    def test_ctx_args_active_and_explicit(self):
        assert ctx_args() == {}
        kid = new_trace().child()
        with use_context(kid):
            a = ctx_args()
            assert a == {"trace_id": kid.trace_id, "span_id": kid.span_id,
                         "parent_id": kid.parent_id}
        assert ctx_args() == {}
        assert ctx_args(kid)["span_id"] == kid.span_id


class TestThreadPropagation:
    def test_plain_thread_does_not_carry(self):
        got = {"ctx": "unset"}
        with use_context(new_trace()):
            t = threading.Thread(
                target=lambda: got.update(ctx=current_context()))
            t.start()
            t.join()
        assert got["ctx"] is None  # why carry_context exists

    def test_carry_context_crosses_thread(self):
        got = {}
        root = new_trace()
        with use_context(root):
            t = threading.Thread(target=carry_context(
                lambda: got.update(ctx_args())))
        t.start()  # started OUTSIDE the block: capture happened at wrap
        t.join()
        assert got["trace_id"] == root.trace_id
        assert got["span_id"] == root.span_id

    def test_supervisor_child_inherits_spawn_context(self):
        sup = Supervisor(name="t", registry=MetricsRegistry())
        got, done = {}, threading.Event()

        def child():
            got.update(ctx_args())
            done.set()

        root = new_trace()
        try:
            with use_context(root):
                sup.spawn("probe", child, escalate=False)
            assert done.wait(10.0)
        finally:
            sup.stop()
        assert got["trace_id"] == root.trace_id


class TestCtxSpan:
    def test_derives_activates_and_stamps(self):
        tracer = TraceRecorder()
        root = new_trace()
        with use_context(root):
            with tracer.ctx_span("op", {"k": 1}) as ctx:
                assert current_context() is ctx
                assert ctx.parent_id == root.span_id
                assert ctx.trace_id == root.trace_id
            assert current_context() is root  # restored
        (ev,) = _events(tracer, "op")
        assert ev["ph"] == "X" and ev["args"]["k"] == 1
        assert ev["args"]["span_id"] == ctx.span_id
        assert ev["args"]["parent_id"] == root.span_id

    def test_roots_new_trace_without_active_context(self):
        tracer = TraceRecorder()
        with tracer.ctx_span("root_op") as ctx:
            assert ctx.parent_id is None
        (ev,) = _events(tracer, "root_op")
        assert "parent_id" not in ev["args"]

    def test_disabled_recorder_no_derivation_no_event(self):
        tracer = TraceRecorder(enabled=False)
        root = new_trace()
        with use_context(root):
            with tracer.ctx_span("op") as ctx:
                assert ctx is root  # zero propagation overhead when off
        assert _events(tracer, "op") == []


# -- export interleave (satellite c) --------------------------------------


class TestExportInterleave:
    def test_cross_thread_events_sorted_by_timestamp(self):
        tracer = TraceRecorder()

        def rec(name):
            t = threading.Thread(target=lambda: tracer.instant(name))
            t.start()
            t.join()

        tracer.instant("e0")  # main ring
        rec("e1")             # ring 2
        tracer.instant("e2")  # main ring again
        rec("e3")             # ring 3 (fresh thread, fresh ring)
        evs = tracer.export()["traceEvents"]
        instants = [e for e in evs if e["ph"] == "i"]
        # per-ring grouping would give e0,e2,e1,e3 — the regression fixed
        assert [e["name"] for e in instants] == ["e0", "e1", "e2", "e3"]
        assert instants[0]["tid"] != instants[1]["tid"]
        # thread-name metadata carries no ts and must lead the stream
        n_meta = sum(1 for e in evs if e["ph"] == "M")
        assert n_meta == 3
        assert all(e["ph"] == "M" for e in evs[:n_meta])

    def test_span_sorts_by_start_not_end(self):
        tracer = TraceRecorder()
        with tracer.span("outer"):
            tracer.instant("inner")
        names = [e["name"] for e in tracer.export()["traceEvents"]
                 if e["ph"] in ("X", "i")]
        assert names == ["outer", "inner"]


# -- TCP propagation ------------------------------------------------------


class TestTCPPropagation:
    def test_wire_context_links_handler_under_caller(self, fresh_obs):
        _, tracer = fresh_obs
        seen = {}
        srv = TCPCommandServer().start()
        try:
            def handler(payload):
                seen.update(ctx_args())
                return payload

            srv.register_handler("work", handler)
            host, port = srv.address
            cli = TCPCommandClient(host, port)
            root = new_trace()
            with use_context(root):
                assert cli.call("work", 42) == 42
        finally:
            srv.shutdown()
        (call,) = _events(tracer, "comm/call:work")
        (handle,) = _events(tracer, "comm/handle:work")
        # one TCP hop: the handler span (server thread) hangs under the
        # call span (client thread), same trace as the caller's root
        assert call["args"]["trace_id"] == root.trace_id
        assert call["args"]["parent_id"] == root.span_id
        assert handle["args"]["trace_id"] == root.trace_id
        assert handle["args"]["parent_id"] == call["args"]["span_id"]
        assert handle["tid"] != call["tid"]
        # the handler body ran under the handle span's context
        assert seen["parent_id"] == call["args"]["span_id"]

    def test_untraced_call_sends_no_trace_key(self, fresh_obs):
        from rl_tpu.comm import TCPCommandClient, TCPCommandServer

        _, tracer = fresh_obs
        seen = {}
        srv = TCPCommandServer().start()
        try:
            srv.register_handler("work", lambda p: seen.update(ctx_args()) or p)
            host, port = srv.address
            assert current_context() is None
            assert TCPCommandClient(*srv.address).call("work", 1) == 1
        finally:
            srv.shutdown()
        assert seen == {}  # wire-compatible both directions
        assert _events(tracer, "comm/call:work") == []


# -- fault stamping (satellite b) -----------------------------------------


class TestFaultTraceLink:
    def test_fired_fault_carries_active_context(self, fresh_obs):
        _, tracer = fresh_obs
        inj = FaultInjector(
            {"grpo.rollout": Fault("delay", at=(2,), seconds=0.0)},
            registry=MetricsRegistry(),
        )
        root = new_trace()
        with injection(inj):
            fault_point("grpo.rollout")  # n=1: no fire, outside any ctx
            with use_context(root):
                fault_point("grpo.rollout")  # n=2: fires inside the ctx
        # the `fired` tuple shape is load-bearing for older chaos tests
        assert inj.fired == [("grpo.rollout", "delay", 2)]
        assert inj.fired_trace == [
            {"trace_id": root.trace_id, "span_id": root.span_id}
        ]
        (ev,) = _events(tracer, "fault_injected")
        assert ev["args"]["trace_id"] == root.trace_id
        assert ev["args"]["site"] == "grpo.rollout"

    def test_unfired_and_untraced_visits(self):
        inj = FaultInjector(
            {"grpo.rollout": Fault("delay", at=(1,), seconds=0.0)},
            registry=MetricsRegistry(), tracer=TraceRecorder(),
        )
        with injection(inj):
            fault_point("grpo.rollout")  # fires with no context active
        assert inj.fired_trace == [None]


# -- SLO engine -----------------------------------------------------------


class TestStreamingHistogram:
    def test_observe_quantile_interpolates(self):
        h = StreamingHistogram(edges=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.5, 3.0):
            h.observe(v)
        assert h.count == 4 and h.sum == pytest.approx(6.5)
        # rank q*n lands mid-bucket; linear within the bucket
        assert 0.0 < h.quantile(0.25) <= 1.0
        assert 1.0 < h.quantile(0.5) <= 2.0
        assert 2.0 < h.quantile(1.0) <= 4.0

    def test_overflow_clamps_to_last_edge(self):
        h = StreamingHistogram(edges=(1.0, 2.0))
        h.observe(100.0)
        assert h.quantile(0.99) == 2.0

    def test_empty_is_none_and_bad_q_raises(self):
        h = StreamingHistogram()
        assert h.quantile(0.5) is None
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_merge_rolls_up_same_edges_only(self):
        a = StreamingHistogram(edges=(1.0, 2.0))
        b = StreamingHistogram(edges=(1.0, 2.0))
        a.observe(0.5)
        b.observe(1.5)
        a.merge(b)
        assert a.count == 2 and a.sum == pytest.approx(2.0)
        assert a.counts == [1, 1, 0]
        with pytest.raises(ValueError):
            a.merge(StreamingHistogram(edges=(1.0, 3.0)))

    def test_bad_edges_raise(self):
        for edges in ((), (1.0, 1.0), (2.0, 1.0)):
            with pytest.raises(ValueError):
                StreamingHistogram(edges=edges)


class TestObjective:
    def test_attainment_and_burn_rate_windows(self):
        t = [1000.0]
        o = Objective("ttft", threshold=1.0, target=0.9, ring_s=3600,
                      clock=lambda: t[0])
        for v in (0.5, 0.5, 2.0, 0.5):
            o.record(v)
        assert o.attainment() == pytest.approx(0.75)
        assert o.attainment(60.0) == pytest.approx(0.75)
        assert o.burn_rate(60.0) == pytest.approx(0.25 / 0.1)
        t[0] += 120.0  # events age out of the 60s window
        assert o.attainment(60.0) is None
        assert o.burn_rate(60.0) == 0.0  # idle service burns nothing
        assert o.attainment() == pytest.approx(0.75)  # all-time unchanged

    def test_ring_lapping_discards_stale_slots(self):
        t = [50.0]
        o = Objective("x", threshold=1.0, ring_s=10, clock=lambda: t[0])
        o.record(0.5)
        t[0] += 10.0  # exactly one lap: same slot, different second
        o.record(0.5)
        g, tot = o._window_counts(10.0)
        assert (g, tot) == (1, 1)  # the lapped write invalidated the old slot

    def test_event_objective_and_type_guard(self):
        o = Objective("avail", threshold=None, target=0.5)
        o.record_event(True)
        o.record_event(False)
        assert o.attainment() == pytest.approx(0.5)
        assert o.burn_rate(60.0) == pytest.approx(1.0)  # exactly sustainable
        with pytest.raises(ValueError, match="event-based"):
            o.record(1.0)

    def test_good_is_strictly_threshold_le(self):
        o = Objective("x", threshold=1.0)
        assert o.record(1.0) is True
        assert o.record(1.0001) is False


class TestSLOEngine:
    def test_gauges_published_on_first_scrape(self):
        reg = MetricsRegistry()
        eng = SLOEngine(registry=reg)
        o = eng.objective("ttft", threshold=1.0, target=0.9)
        o.record(0.5)
        o.record(2.0)
        text = reg.render()
        # families must exist on the FIRST render (created at init, not
        # inside the collector: render snapshots families pre-collector)
        assert 'rl_tpu_slo_attainment{slo="ttft",window="all"} 0.5' in text
        assert 'rl_tpu_slo_attainment{slo="ttft",window="60s"} 0.5' in text
        assert 'rl_tpu_slo_burn_rate{slo="ttft",window="60s"} 5' in text
        assert 'rl_tpu_slo_value_seconds{slo="ttft",quantile="0.5"}' in text
        assert 'rl_tpu_slo_value_seconds{slo="ttft",quantile="0.99"}' in text

    def test_objective_idempotent_or_loud(self):
        eng = SLOEngine()
        a = eng.objective("x", threshold=1.0)
        assert eng.objective("x", threshold=1.0) is a
        with pytest.raises(ValueError, match="already defined"):
            eng.objective("x", threshold=2.0)
        assert eng.names() == ["x"]
        assert eng.get("x") is a

    def test_snapshot_is_bench_artifact_shaped(self):
        eng = SLOEngine(windows=(60.0,))
        eng.objective("lat", threshold=1.0).record(0.5)
        snap = eng.snapshot()
        assert snap["lat"]["attainment"] == 1.0
        assert snap["lat"]["burn_rate_60s"] == 0.0
        assert "p50" in snap["lat"] and "p99" in snap["lat"]
        json.dumps(snap)  # must be artifact-serializable as-is


# -- flight recorder ------------------------------------------------------


class TestFlightRecorder:
    def test_bundle_contents(self, tmp_path, fresh_obs):
        reg, tracer = fresh_obs
        reg.counter("rl_tpu_test_total").inc(3)
        tracer.instant("before_death")
        rec = FlightRecorder(str(tmp_path), window_s=60.0)
        rec.add_source("acc", lambda: {"x": 1})
        rec.add_source("bad", lambda: 1 / 0)
        path = rec.dump("test_trigger", RuntimeError("boom"))
        assert path is not None and os.path.isdir(path)
        assert rec.dumps == [path]
        meta = json.load(open(os.path.join(path, "meta.json")))
        assert meta["trigger"] == "test_trigger"
        assert "boom" in meta["error"]
        assert meta["failed_artifacts"] == []
        trace = json.load(open(os.path.join(path, "trace.json")))
        assert any(e.get("name") == "before_death"
                   for e in trace["traceEvents"])
        metrics = json.load(open(os.path.join(path, "metrics.json")))
        assert "rl_tpu_test_total" in json.dumps(metrics)
        json.load(open(os.path.join(path, "programs.json")))
        assert json.load(open(os.path.join(path, "source-acc.json"))) == {"x": 1}
        # a raising source lands as its error, never kills the dump
        bad = json.load(open(os.path.join(path, "source-bad.json")))
        assert "ZeroDivisionError" in bad["error"]

    def test_window_cuts_old_events(self, tmp_path, fresh_obs):
        _, tracer = fresh_obs
        tracer.instant("old")
        time.sleep(0.3)  # "old" is >=0.3s stale at dump time
        tracer.instant("new")
        rec = FlightRecorder(str(tmp_path), window_s=0.15)
        path = rec.dump("t")
        names = [e.get("name") for e in
                 json.load(open(os.path.join(path, "trace.json")))["traceEvents"]]
        assert "new" in names and "old" not in names

    def test_rate_limit_and_cap(self, tmp_path):
        t = [0.0]
        rec = FlightRecorder(str(tmp_path), max_dumps=2, min_interval_s=1.0,
                             clock=lambda: t[0])
        assert rec.dump("a") is not None
        assert rec.dump("b") is None  # inside min_interval
        t[0] += 2.0
        assert rec.dump("c") is not None
        t[0] += 2.0
        assert rec.dump("d") is None  # max_dumps cap: bounded black box

    def test_dump_never_raises(self, tmp_path):
        blocker = tmp_path / "file"  # a FILE where the dump dir must go:
        blocker.write_text("x")      # makedirs fails even when run as root
        rec = FlightRecorder(str(blocker))
        assert rec.dump("t") is None

    def test_watchdog_death_triggers_dump(self, tmp_path, fresh_obs):
        rec = FlightRecorder(str(tmp_path))
        prev = set_flight_recorder(rec)
        try:
            wd = Watchdog(timeout=0.01)
            wd.register("actor-0")
            time.sleep(0.05)
            assert wd.check() == ["actor-0"]
        finally:
            set_flight_recorder(prev)
        assert len(rec.dumps) == 1
        meta = json.load(open(os.path.join(rec.dumps[0], "meta.json")))
        assert meta["trigger"] == "watchdog_death-actor-0"

    def test_budget_exhaustion_escalation_dumps_and_links_path(
            self, tmp_path, fresh_obs):
        """Acceptance: an injected Supervisor budget exhaustion produces a
        complete postmortem bundle whose path rides on the escalation
        error all the way out of ``get_batch``."""
        class _Env:
            observation_spec = Composite(observation=Unbounded((2,)))
            action_spec = Bounded(shape=(1,), low=-1.0, high=1.0)

            def reset(self, seed=None):
                return {"observation": np.zeros(2, np.float32)}

            def step(self, action):
                return (self.reset(), np.float32(0.0), False, False)

            def close(self):
                pass

        rec = FlightRecorder(str(tmp_path))
        prev = set_flight_recorder(rec)
        sup = Supervisor(name="t", max_restarts=1, backoff_base_s=0.005,
                         backoff_max_s=0.05, registry=MetricsRegistry())
        pool = ThreadedEnvPool([lambda: _Env() for _ in range(2)])
        coll = AsyncHostCollector(pool, None, frames_per_batch=16,
                                  supervisor=sup)
        inj = FaultInjector({"collector.actor_loop": Fault("crash", prob=1.0)},
                            registry=MetricsRegistry())
        try:
            with injection(inj):
                coll.start()
                with pytest.raises(RuntimeError,
                                   match="actor thread failed") as ei:
                    deadline = time.monotonic() + 60
                    while time.monotonic() < deadline:
                        coll.get_batch(timeout=0.2)
                    raise AssertionError("collector never exhausted budget")
        finally:
            coll.stop()
            sup.stop()
            pool.close()
            set_flight_recorder(prev)
        cause = ei.value.__cause__
        dump = getattr(cause, "flight_record", None)
        assert dump is not None and os.path.isdir(dump)
        assert rec.dumps == [dump]
        # the bundle is complete
        for artifact in ("meta.json", "trace.json", "metrics.json",
                         "programs.json"):
            assert os.path.isfile(os.path.join(dump, artifact))
        meta = json.load(open(os.path.join(dump, "meta.json")))
        assert meta["trigger"] == "supervisor_giveup-async-collector"
        assert "InjectedFault" in meta["error"]
        assert meta["failed_artifacts"] == []
        # the giveup instant in the trace marks the moment of death
        trace = json.load(open(os.path.join(dump, "trace.json")))
        assert any(e.get("name") == "supervisor_giveup"
                   for e in trace["traceEvents"])


# -- fleet chaos trace tree (the acceptance criterion) --------------------


def _small_model():
    cfg = TransformerConfig(
        vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq_len=128, dtype=jnp.float32,
    )
    m = TransformerLM(cfg)
    params = m.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return m, params


def _wait_until(pred, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.005)


class TestFleetTraceTree:
    def test_chaos_request_tree_spans_threads_and_tcp(self, fresh_obs,
                                                      tmp_path):
        """One interactive request's lifecycle — TCP submit, fleet admit,
        dispatch, injected mid-decode crash, failover re-dispatch,
        completion — renders as a single parent-linked tree."""
        reg, tracer = fresh_obs
        m, params = _small_model()
        engines = [
            ContinuousBatchingEngine(
                m, params, n_slots=2, block_size=8, n_blocks=65,
                prompt_buckets=(16,), greedy=True, seed=i,
            )
            for i in range(2)
        ]
        for e in engines:  # compile outside the fleet: no probe trips
            e.submit(np.arange(8), 4)
            e.run()
        fleet = ServingFleet(engines, registry=reg,
                             probe_interval_s=0.01).start()
        srv = TCPCommandServer().start()
        rng = np.random.default_rng(0)
        roots = {}
        try:
            srv.register_handler(
                "submit",
                lambda p: fleet.submit(np.asarray(p["prompt"]),
                                       p["max_new_tokens"]),
            )
            cli = TCPCommandClient(*srv.address)
            for _ in range(6):
                root = new_trace()
                with use_context(root):
                    frid = cli.call("submit", {
                        "prompt": rng.integers(0, 97, 8).tolist(),
                        "max_new_tokens": 24,
                    })
                roots[frid] = root
            _wait_until(lambda: engines[0].pending() > 0, msg="engine 0 busy")
            inj = FaultInjector(
                {"fleet.engine_crash.0": Fault("crash", at=(1,))},
                registry=MetricsRegistry(),
            )
            with injection(inj):
                got = fleet.wait(list(roots), timeout=90)
            assert sorted(got) == sorted(roots)
            assert all(isinstance(r, FinishedRequest) for r in got.values())
            acc = fleet.accounting()
            assert acc["lost"] == 0 and acc["redispatched"] >= 1
            scrape = reg.render()
        finally:
            srv.shutdown()
            fleet.shutdown()

        # ---- walk the Perfetto export ----
        out = tracer.export(str(tmp_path / "trace.json"))
        assert json.load(open(tmp_path / "trace.json")) == out
        evs = [e for e in out["traceEvents"]
               if e.get("args", {}).get("trace_id")]
        admits = {e["args"]["frid"]: e for e in evs
                  if e["name"] == "fleet_admit"}
        assert sorted(admits) == sorted(roots)
        fails = [e for e in evs if e["name"] == "fleet_failover_redispatch"]
        assert fails, "crash mid-decode must force >=1 failover re-dispatch"
        fail = fails[0]
        frid = fail["args"]["frid"]
        root, req = roots[frid], admits[frid]

        # (1) ONE tree: every leg shares the submitter's trace id, and the
        # failover re-dispatch is parented to the ORIGINAL request span
        assert req["args"]["trace_id"] == root.trace_id
        assert fail["args"]["trace_id"] == root.trace_id
        assert fail["args"]["parent_id"] == req["args"]["span_id"]

        # (2) parent-link chain from the request span back to the root
        # crosses the TCP hop: admit -> comm/handle -> comm/call -> root
        tree = [e for e in evs if e["args"]["trace_id"] == root.trace_id]
        by_span = {e["args"]["span_id"]: e for e in tree}
        chain, cur = [], req
        while cur["args"].get("parent_id") in by_span:
            cur = by_span[cur["args"]["parent_id"]]
            chain.append(cur["name"])
        assert chain == ["comm/handle:submit", "comm/call:submit"]
        assert cur["args"]["parent_id"] == root.span_id

        # (3) the tree spans >= 3 threads (client, TCP handler, fleet
        # dispatcher, member stepper...)
        assert len({e["tid"] for e in tree}) >= 3

        # dispatch + completion legs are present and correctly parented
        names = {e["name"] for e in tree}
        assert "fleet/dispatch" in names and "fleet_request_done" in names
        for e in tree:
            if e["name"] == "fleet/dispatch":
                assert e["args"]["parent_id"] == req["args"]["span_id"]

        # satellite b: the injected crash fired inside an admitted
        # request's context
        stamped = [c for c in inj.fired_trace if c]
        assert stamped
        assert stamped[0]["trace_id"] in {r.trace_id for r in roots.values()}

        # satellite a: real TTFT quantiles exported from the streaming
        # histogram (not the EMA), plus the fleet SLO burn-rate gauges
        assert 'rl_tpu_fleet_ttft_seconds{quantile="0.5"}' in scrape
        assert 'rl_tpu_fleet_ttft_seconds{quantile="0.99"}' in scrape
        assert 'rl_tpu_slo_attainment{slo="fleet_ttft",window="all"}' in scrape
        assert 'rl_tpu_slo_burn_rate{slo="fleet_availability"' in scrape
        snap = fleet.slo.snapshot()
        assert snap["fleet_availability"]["attainment"] == 1.0
        assert snap["fleet_latency"]["total"] == 6


# -- tracing overhead (satellite d) ---------------------------------------


class TestTracingOverhead:
    """What a span costs, as its own best-of-N reading against fixed
    limits (a ratio of two wall times of a parallel run guards nothing),
    and how many spans a decode launch records."""

    @staticmethod
    def _ns_a_span(make_span, n=20_000, rounds=7):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                with make_span("x"):
                    pass
            best = min(best, (time.perf_counter_ns() - t0) / n)
        return best

    def test_span_cost_within_budget(self):
        from contextlib import contextmanager

        armed, off = TraceRecorder(), TraceRecorder(enabled=False)

        @contextmanager
        def span_before(name):  # TraceRecorder.span as it was before PR 25
            start = armed.now_us()
            try:
                yield
            finally:
                armed._emit({"ph": "X", "name": name, "ts": start, "dur": armed.now_us() - start})

        cost_armed = self._ns_a_span(armed.span)
        cost_off = self._ns_a_span(off.span)
        cost_before = self._ns_a_span(span_before)
        print(f"ns a span: armed {cost_armed:.0f}, disabled {cost_off:.0f}, before PR 25 {cost_before:.0f}")
        assert cost_armed < 5_000, f"armed span costs {cost_armed:.0f} ns"
        assert cost_off < 2_000, f"disabled span costs {cost_off:.0f} ns"
        assert cost_armed <= cost_before, (cost_armed, cost_before)
        assert len(armed.export()["traceEvents"]) > 1  # and it did record

    def test_armed_ctx_span_cost_within_budget(self):
        """The causal span (context derivation and ids) stays cheap too."""
        tracer = TraceRecorder()
        with use_context(new_trace()):
            cost = self._ns_a_span(tracer.ctx_span, n=5_000)
        assert cost < 50_000, f"ctx_span costs {cost:.0f} ns"
        spans = _events(tracer, "x")
        assert spans and all("trace_id" in e["args"] for e in spans)

    def test_engine_records_at_most_ten_spans_a_launch(self, fresh_obs):
        _, tracer = fresh_obs
        cfg = TransformerConfig(vocab_size=97, d_model=32, n_layers=1, n_heads=2, d_ff=64,
                                max_seq_len=64, dtype=jnp.float32)
        m = TransformerLM(cfg)
        params = m.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
        eng = ContinuousBatchingEngine(m, params, n_slots=4, block_size=4, n_blocks=65,
                                       prompt_buckets=(8,), decode_chunk=1)
        for i in range(8):
            eng.submit(np.arange(1, 6 + i % 3), 12)
        eng.run()
        n = len([e for e in _events(tracer) if e["ph"] == "X" and e["name"].startswith("engine.")])
        assert eng.decode_launches >= 20
        assert n <= 10 * eng.decode_launches, (n, eng.decode_launches)

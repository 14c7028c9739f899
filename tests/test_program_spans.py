"""The program's own spans (ISSUE 25): the span primitive, the spans inside
the engine, the collector and the GRPO step, the ``request`` event, and the
bridge to ``jax.profiler``.

Nesting is read from the recorder's export the way the benchmark's readers
read it: by interval containment on one thread."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rl_tpu.envs.llm import arithmetic_dataset
from rl_tpu.models import ContinuousBatchingEngine, TransformerConfig, TransformerLM
from rl_tpu.obs import TraceRecorder, set_tracer
from rl_tpu.obs import trace as trace_mod
from rl_tpu.trainers.grpo import GRPOTrainer, PipelinedGRPOTrainer

KEY = jax.random.key(0)


@pytest.fixture
def tracer():
    rec = TraceRecorder()
    prev = set_tracer(rec)
    yield rec
    set_tracer(prev)


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(vocab_size=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
                            max_seq_len=64, dtype=jnp.float32)
    m = TransformerLM(cfg)
    return m, m.init(KEY, jnp.zeros((1, 8), jnp.int32))["params"]


def engine(model, **kw):
    m, params = model
    kw = {"n_slots": 3, "block_size": 4, "n_blocks": 49, "prompt_buckets": (8, 16),
          "temperature": 1.0, "decode_chunk": 1, "seed": 5, **kw}
    return ContinuousBatchingEngine(m, params, **kw)


def serve(eng, n=7, seed=1):
    """Submit ``n`` requests of mixed sizes, run to the end, return the
    FinishedRequests by rid."""
    rng = np.random.default_rng(seed)
    for i in range(n):
        eng.submit(rng.integers(1, 97, size=3 + i % 6), 2 + (3 * i) % 9)
    return eng.run()


def spans(rec, name=None):
    # a first call compiles, and the compile listener stamps its own spans
    evs = [e for e in rec.export()["traceEvents"] if e["ph"] == "X" and not e["name"].startswith("xla_compile")]
    return [e for e in evs if name is None or e["name"] == name]


def inside(child, parent):
    return (child["tid"] == parent["tid"] and parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def children(evs, parent):
    """Direct children of ``parent``: inside it, and inside no other span
    that is itself inside it."""
    kids = [e for e in evs if e != parent and e["name"] != "request" and inside(e, parent)]
    return [k for k in kids if not any(o is not k and inside(k, o) for o in kids)]


# -- the primitive ------------------------------------------------------------


class TestSpan:
    @pytest.mark.parametrize("enabled", [True, False])
    def test_duration_is_kept_after_exit(self, enabled):
        rec = TraceRecorder(enabled=enabled)
        with rec.span("blk") as sp:
            sum(range(1000))
        assert sp.dur_s > 0
        got = spans(rec, "blk")
        assert len(got) == (1 if enabled else 0)
        if enabled:
            assert got[0]["dur"] == pytest.approx(sp.dur_s * 1e6, rel=1e-9)

    def test_args_given_or_assigned_inside_the_block(self):
        rec = TraceRecorder()
        with rec.span("a", {"n": 1}):
            pass
        with rec.span("b") as sp:
            sp.args = {"n": 2}
        with rec.span("c"):
            pass
        a, b, c = (spans(rec, n)[0] for n in "abc")
        assert a["args"] == {"n": 1} and b["args"] == {"n": 2} and "args" not in c

    def test_export_keeps_the_chrome_schema(self, tmp_path):
        rec = TraceRecorder()
        with rec.span("outer", {"k": "v"}):
            with rec.span("inner"):
                pass
        rec.instant("mark")
        out = rec.export(str(tmp_path / "t.json"))
        assert json.load(open(tmp_path / "t.json")) == out
        outer, inner = spans(rec, "outer")[0], spans(rec, "inner")[0]
        assert set(outer) == {"ph", "name", "ts", "dur", "args", "pid", "tid"}
        assert set(inner) == {"ph", "name", "ts", "dur", "pid", "tid"}
        assert inside(inner, outer)
        names = [e.get("name") for e in out["traceEvents"]]
        assert names.index("outer") < names.index("inner") < names.index("mark")  # by start

    def test_since_us_cuts_spans_that_ended_before(self):
        rec = TraceRecorder()
        with rec.span("old"):
            pass
        cut = rec.now_us()
        with rec.span("new"):
            pass
        names = [e["name"] for e in rec.export(since_us=cut)["traceEvents"] if e["ph"] == "X"]
        assert names == ["new"]

    def test_end_span_takes_an_end_already_timed(self):
        rec = TraceRecorder()
        rec.end_span("req", 10.0, {"rid": 1}, end_us=35.0)
        (e,) = spans(rec, "req")
        assert (e["ts"], e["dur"]) == (10.0, 25.0)

    def test_a_full_ring_counts_what_it_drops(self):
        rec = TraceRecorder(capacity=4)
        for _ in range(6):
            with rec.span("x"):
                pass
        assert len(spans(rec, "x")) == 4 and sum(rec.dropped_events().values()) == 2


# -- the engine ---------------------------------------------------------------


class TestEngineSpans:
    def test_nesting_and_launch_numbers(self, tracer, model):
        eng = engine(model)
        eng.step()  # an empty engine: nothing to admit, nothing to launch
        assert [e["name"] for e in spans(tracer)] == ["engine.step"]
        serve(eng)
        evs = spans(tracer)
        steps, launches = spans(tracer, "engine.step"), spans(tracer, "engine.launch")
        for st in steps:
            assert sum(inside(la, st) for la in launches) <= 1
        assert all(any(inside(la, st) for st in steps) for la in launches)
        numbers = [la["args"]["launch"] for la in launches]
        assert numbers == list(range(1, len(launches) + 1))
        assert numbers[-1] == eng.metrics_snapshot()["decode_launches"]
        assert all(la["args"]["chunk"] == 1 and 1 <= la["args"]["active"] <= 3 for la in launches)
        # every wait and every table flush is a child where the table says
        parents = {"engine.drain.wait": ("engine.drain",), "engine.prefill.wait": ("engine.admit",),
                   "engine.prefill.dispatch": ("engine.admit",), "engine.launch.dispatch": ("engine.launch",),
                   "engine.flush_tables": ("engine.admit", "engine.launch"),
                   "engine.admit": ("engine.step",), "engine.launch": ("engine.step",),
                   "engine.drain": ("engine.step",)}
        for parent in (e for e in evs if e["name"] != "request"):
            for kid in children(evs, parent):
                assert parent["name"] in parents[kid["name"]], (kid["name"], parent["name"])
        assert {e["name"] for e in evs} == set(parents) | {"engine.step", "request"}

    def test_admit_is_recorded_only_when_it_admits(self, tracer, model):
        eng = engine(model)
        serve(eng)
        admits, steps = spans(tracer, "engine.admit"), spans(tracer, "engine.step")
        assert 0 < len(admits) < len(steps)  # most steps admit nothing and record none
        assert all(a["args"]["admitted"] == len(a["args"]["slots"]) >= 1 for a in admits)
        assert sum(a["args"]["admitted"] for a in admits) == eng.admissions == 7
        assert admits[-1]["args"]["queue_depth"] == 0
        assert sum(a["args"]["prefill_tokens"] for a in admits) == eng.prefill_tokens_computed
        assert sum(d["args"]["emitted"] for d in spans(tracer, "engine.drain")) + 7 == sum(
            r["args"]["tokens"] for r in spans(tracer, "request"))
        assert sum(d["args"]["finished"] for d in spans(tracer, "engine.drain")) <= 7

    def test_self_time_is_the_span_less_its_children(self, tracer, model):
        serve(engine(model))
        evs = spans(tracer)
        for d in spans(tracer, "engine.drain"):
            (wait,) = children(evs, d)
            assert wait["name"] == "engine.drain.wait" and 0 <= wait["dur"] <= d["dur"]
        for st in spans(tracer, "engine.step"):
            assert sum(k["dur"] for k in children(evs, st)) <= st["dur"]
        # every program call is a child of its own, once a launch and once an admission
        for parent, call in (("engine.launch", "engine.launch.dispatch"), ("engine.admit", "engine.prefill.dispatch")):
            for p in spans(tracer, parent):
                assert [k["name"] for k in children(evs, p)].count(call) == 1
        # the table write's program call is the flush's own work: no child
        flushes = spans(tracer, "engine.flush_tables")
        assert flushes and all(children(evs, f) == [] for f in flushes)

    @pytest.mark.parametrize("chunk", [1, 4])
    def test_one_request_event_a_finished_request(self, tracer, model, chunk):
        eng = engine(model, decode_chunk=chunk)
        t0 = tracer.now_us() * 1e-6
        done = serve(eng)
        t1 = tracer.now_us() * 1e-6
        reqs = {e["args"]["rid"]: e for e in spans(tracer, "request")}
        assert sorted(reqs) == sorted(done) and len(done) == 7
        for rid, fin in done.items():
            assert t0 <= fin.t_submit <= fin.t_admit <= fin.t_first <= fin.t_finish <= t1
            a = reqs[rid]["args"]
            assert a["tokens"] == len(fin.tokens) and a["slot"] == fin.slot and a["reason"] == fin.finished_reason
            assert 0 <= fin.slot < 3
            assert reqs[rid]["ts"] == pytest.approx(fin.t_submit * 1e6)
            assert reqs[rid]["ts"] + reqs[rid]["dur"] == pytest.approx(fin.t_finish * 1e6)
            assert a["queue_s"] == pytest.approx(fin.t_admit - fin.t_submit)
            assert a["prefill_s"] == pytest.approx(fin.t_first - fin.t_admit)
        json.dumps(tracer.export())  # every arg is a plain number

    def test_speculative_step_gets_the_same_spans(self, tracer, model):
        eng = engine(model, greedy=True, speculative=True, draft_source="ngram")
        serve(eng)
        launches = spans(tracer, "engine.launch")
        assert [la["args"]["launch"] for la in launches] == list(range(1, eng.decode_launches + 1))
        steps = spans(tracer, "engine.step")
        assert all(sum(inside(la, st) for la in launches) <= 1 for st in steps)
        assert len(spans(tracer, "request")) == 7


class _Ticks:
    """A clock that advances at every read, whoever reads it, by steps
    drawn from a seed: the same reads in the same order see the same times."""

    def __init__(self):
        self.n = 0
        self.rng = np.random.default_rng(0)

    def perf_counter_ns(self):
        self.n += int(self.rng.integers(1_000, 3_000_000))
        return self.n


class TestTracingChangesNoResult:
    def run(self, model, enabled, **kw):
        """Sampling keyed by (seed, rid, position) and every chunk settled
        before the next launch: the schedule follows no wall clock."""
        rec = TraceRecorder(enabled=enabled)
        prev = set_tracer(rec)
        try:
            eng = engine(model, slot_rng=True, **kw)
            eng._inflight_ready = lambda: True
            ks = []
            if eng._tuner is not None:
                observe = eng._tuner.observe

                def spy(host_s, wait_s, chunk):
                    observe(host_s, wait_s, chunk)
                    ks.append((host_s, wait_s, chunk, eng._tuner.k))

                eng._tuner.observe = spy
            done = serve(eng, n=9)
            return done, ks, eng.metrics_snapshot(), rec
        finally:
            set_tracer(prev)

    @pytest.mark.parametrize("chunk", [1, 2])
    def test_tokens_and_log_probs_bit_equal(self, model, chunk):
        on, _, snap_on, rec = self.run(model, True, decode_chunk=chunk)
        off, _, snap_off, rec_off = self.run(model, False, decode_chunk=chunk)
        assert spans(rec) and not spans(rec_off)
        assert sorted(on) == sorted(off)
        for rid in on:
            assert np.array_equal(on[rid].tokens, off[rid].tokens)
            assert np.array_equal(on[rid].log_probs, off[rid].log_probs)  # bit-equal, no tolerance
            assert on[rid].slot == off[rid].slot
        for k in ("decode_launches", "decode_steps", "admissions", "host_transfers", "tokens_generated"):
            assert snap_on[k] == snap_off[k]

    def test_the_tuner_sees_the_same_intervals(self, model, monkeypatch):
        """On a clock that ticks once a read, the intervals the chunk tuner
        is fed (and so every k it chooses) are the same with the recorder
        on and off: its inputs are clock reads the spans make either way."""
        def ticking(enabled):
            ticks = _Ticks()
            monkeypatch.setattr(trace_mod, "_clock_ns", ticks.perf_counter_ns)
            return self.run(model, enabled, decode_chunk="auto")

        ticking(True)  # compiles what this schedule meets: a compile reads the clock too
        on, ks_on, snap_on, _ = ticking(True)
        off, ks_off, snap_off, _ = ticking(False)
        assert ks_on == ks_off and len(ks_on) > 3
        assert len({k for *_, k in ks_on}) > 1  # the tuner did move
        assert snap_on["decode_launches"] == snap_off["decode_launches"]
        for rid in on:
            assert np.array_equal(on[rid].tokens, off[rid].tokens)
            assert np.array_equal(on[rid].log_probs, off[rid].log_probs)


# -- the collector and the trainer ------------------------------------------


def tiny_trainer(cls=GRPOTrainer, **kw):
    ds = arithmetic_dataset(n=32, max_operand=2)
    return cls(ds, num_prompts=2, group_repeats=2, max_prompt_len=8, max_new_tokens=4,
               learning_rate=1e-3, kl_coeff=0.005, **kw)


class TestGRPOSpans:
    @pytest.mark.parametrize("engine_path", [True, False], ids=["engine", "fixed-batch"])
    def test_one_step_span_with_the_tables_children(self, tracer, engine_path):
        t = tiny_trainer(continuous_batching=engine_path)
        t.step()
        evs = spans(tracer)
        (step,) = spans(tracer, "grpo.step")
        assert step["args"] == {"version": 1}  # the first push was the constructor's
        assert [k["name"] for k in children(evs, step)] == [
            "grpo.collect", "grpo.update", "grpo.push", "grpo.drain_metrics.wait"]
        (collect,) = spans(tracer, "grpo.collect")
        assert [k["name"] for k in children(evs, collect)] == [
            "collector.prompts", "collector.rollout", *([] if engine_path else ["collector.reward"]),
            "collector.ref_score", "collector.assemble"]
        (rollout,) = spans(tracer, "collector.rollout")
        assert rollout["args"]["requests"] == 4 and 4 <= rollout["args"]["tokens"] <= 16
        kids = {k["name"] for k in children(evs, rollout)}
        assert kids == ({"engine.step", "collector.reward"} if engine_path else set())
        (wait,) = spans(tracer, "collector.assemble.wait")
        assert inside(wait, spans(tracer, "collector.assemble")[0])
        if engine_path:
            assert rollout["args"]["tokens"] == sum(r["args"]["tokens"] for r in spans(tracer, "request"))
            assert sum(r["args"]["rows"] for r in spans(tracer, "collector.reward")) == 4
        t.step()
        assert [s["args"]["version"] for s in spans(tracer, "grpo.step")] == [1, 2]

    def test_placement_gets_its_span(self, tracer):
        t = tiny_trainer()
        t._batch_placement = jax.devices()[0]
        t.step()
        (step,) = spans(tracer, "grpo.step")
        assert [k["name"] for k in children(spans(tracer), step)] == [
            "grpo.collect", "grpo.place", "grpo.update", "grpo.push", "grpo.drain_metrics.wait"]

    def test_pipelined_step_has_the_root_and_the_shared_children(self, tracer):
        with tiny_trainer(PipelinedGRPOTrainer) as t:
            t.step()
        (step,) = spans(tracer, "grpo.step")
        assert [k["name"] for k in children(spans(tracer), step)] == [
            "grpo.update", "grpo.push", "grpo.drain_metrics.wait"]
        # the rollout runs on the producer's thread, under no grpo.step
        (rollout, *_) = spans(tracer, "collector.rollout")
        assert rollout["tid"] != step["tid"]


# -- the profiler's clock -----------------------------------------------------


def host_events(trace_dir):
    """{name: [(start_ns, dur_ns)]} of every host plane of the one profile
    under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append((e.start_ns, e.duration_ns))
    return out


class TestProfilerBridge:
    def test_program_spans_land_in_the_profile_inside_the_outer_annotation(self, tracer, model, tmp_path):
        """With nothing but a profiler session open (the benchmark's own
        options: host tracer level 1, Python tracer off), the engine's
        spans are in the ``.xplane.pb``, as many as the recorder holds,
        inside the window's annotation, with the recorder's durations."""
        eng = engine(model)
        serve(eng, n=2)  # compile outside the session
        tracer.clear()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                serve(eng, n=5)
        finally:
            jax.profiler.stop_trace()
        prof = host_events(tmp_path)
        ((w0, wdur),) = prof["bench.window"]
        for name in ("engine.step", "engine.launch", "engine.launch.dispatch", "engine.drain",
                     "engine.drain.wait", "engine.admit", "engine.prefill.dispatch", "engine.prefill.wait",
                     "engine.flush_tables"):
            mine = sorted(spans(tracer, name), key=lambda e: e["ts"])
            theirs = sorted(prof[name])
            assert len(theirs) == len(mine) > 0, name
            assert all(w0 <= s and s + d <= w0 + wdur for s, d in theirs), name
            # the annotation is entered before the recorder's clock is read
            # and left after it: never shorter, and longer by little
            # (a descheduled thread can stretch one pair: most agree closely)
            over = [d / 1e3 - e["dur"] for (_s, d), e in zip(theirs, mine)]
            assert min(over) >= -1.0 and max(over) < 50_000.0, (name, over)
            assert sorted(over)[int(0.8 * (len(over) - 1))] < 250.0, (name, over)
        # same order on both clocks: starts differ by one constant offset
        launches = sorted(spans(tracer, "engine.launch"), key=lambda e: e["ts"])
        offs = sorted(s / 1e3 - e["ts"] for (s, _), e in zip(sorted(prof["engine.launch"]), launches))
        assert offs[int(0.8 * (len(offs) - 1))] - offs[0] < 250.0 and offs[-1] - offs[0] < 50_000.0

    def test_no_session_no_profile_and_no_flag(self, tracer):
        import rl_tpu.utils as utils
        import rl_tpu.utils.timing as timing

        for gone in ("record_function", "set_profiling_enabled", "_PROFILING"):
            assert not hasattr(timing, gone) and not hasattr(utils, gone)
        with tracer.span("alone") as sp:  # no session open: still a recorder span
            pass
        assert sp.dur_s >= 0 and len(spans(tracer, "alone")) == 1

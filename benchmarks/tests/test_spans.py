"""The span readers: on hand-built span lists with exact answers, and on
the tiny engine and GRPO presets on the CPU, where every new metric reads
a number."""

import pytest

import test_cells
from harness import spans

MS = 1000.0  # a span list is in microseconds


def X(name, ts, dur, tid=1, **args):
    e = {"ph": "X", "name": name, "ts": float(ts), "dur": float(dur), "tid": tid, "pid": 1}
    if args:
        e["args"] = args
    return e


def M(tid=1, dropped=0):
    args = {"name": "main", "dropped": dropped} if dropped else {"name": "main"}
    return {"ph": "M", "name": "thread_name", "tid": tid, "pid": 1, "args": args}


def step(ts, launch, *, admit=False, calls=True):
    """One engine.step of 10 ms at ``ts`` (ms): [admit 3 ms (flush 0.5,
    prefill.dispatch 0.4, prefill.wait 1)] launch 2 ms (flush 0.5,
    launch.dispatch 0.8) drain 4 ms (wait 3). ``calls=False`` leaves the
    ``*.dispatch`` children out, as a program without them would."""
    t = ts * MS
    evs = [X("engine.step", t, 10 * MS)]
    if admit:
        evs += [X("engine.admit", t + 0.1 * MS, 3 * MS, admitted=1, slots=[0], queue_depth=0),
                X("engine.flush_tables", t + 0.2 * MS, 0.5 * MS, writes=3),
                X("engine.prefill.dispatch", t + 0.8 * MS, 0.4 * MS),
                X("engine.prefill.wait", t + 1.5 * MS, 1 * MS)]
    evs += [X("engine.launch", t + 3.5 * MS, 2 * MS, launch=launch, chunk=1, active=1),
            X("engine.flush_tables", t + 3.6 * MS, 0.5 * MS, writes=1),
            X("engine.launch.dispatch", t + 4.2 * MS, 0.8 * MS),
            X("engine.drain", t + 5.8 * MS, 4 * MS, emitted=1, finished=0),
            X("engine.drain.wait", t + 5.9 * MS, 3 * MS)]
    return [e for e in evs if calls or not e["name"].endswith(".dispatch")]


def logs():
    lines = []
    return lines, lambda *a: lines.append(" ".join(map(str, a)))


@pytest.mark.parametrize("calls", [True, False])
def test_self_time_leaves_nested_waits_and_program_calls_out(calls):
    table = spans.self_times(spans.by_thread([M()] + step(0, 1, admit=True, calls=calls))[1].spans)
    pre, dec = (0.4, 0.8) if calls else (0.0, 0.0)
    want = {"engine.step": 10 - 3 - 2 - 4, "engine.admit": 3 - 0.5 - 1 - pre, "engine.launch": 2 - 0.5 - dec,
            "engine.flush_tables": 1.0, "engine.drain": 1.0, "engine.drain.wait": 3.0, "engine.prefill.wait": 1.0}
    if calls:
        want.update({"engine.prefill.dispatch": 0.4, "engine.launch.dispatch": 0.8})
    assert {n: (round(us / MS, 9), c) for n, (us, c) in table.items()} == {
        n: (ms, 2 if n == "engine.flush_tables" else 1) for n, ms in want.items()}


def test_window_is_chosen_by_launch_counts():
    evs = [M()] + [e for k in range(6) for e in step(20 * k, k + 1, admit=(k == 3))]
    lines, log = logs()
    w = spans.choose_window(spans.by_thread(evs), 2, 5, log)  # launches 3, 4, 5
    assert w.launches == 3 and (w.lo, w.hi) == (40 * MS, 90 * MS)
    assert [s[3]["launch"] for s in w.spans if s[0] == "engine.launch"] == [3, 4, 5]
    table = spans.self_times(w.spans)
    host = sum(table[n][0] for n in spans.ENGINE_HOST) / MS
    plain = (10 - 2 - 4) + (2 - 0.5 - 0.8) + 0.5 + (4 - 3)  # step, launch, its flush, drain
    admitting = (10 - 3 - 2 - 4) + (3 - 0.5 - 0.4 - 1) + (2 - 0.5 - 0.8) + 2 * 0.5 + (4 - 3)
    assert host == pytest.approx(2 * plain + admitting)
    # the same through the reader
    run = {"c0": {"decode_launches": 2}, "c1": {"decode_launches": 5}, "log": log}
    orig, spans.snapshot = spans.snapshot, lambda: spans.by_thread(evs)
    try:
        assert spans.engine_host_ms_per_launch(run) == pytest.approx(host / 3)
        # the program calls and the waits are logged beside the number, and nothing is left over
        assert "= host 0.017200 s + dispatches 0.002800 s + waits 0.010000 s + other 0.000000 s" in lines[-1]
    finally:
        spans.snapshot = orig


def test_an_earlier_engines_launch_numbers_are_not_taken():
    """Two engines in one process both number their launches from 1."""
    earlier = [e for k in range(6) for e in step(20 * k, k + 1)]
    later = [e for k in range(6) for e in step(1000 + 20 * k, k + 1)]
    _, log = logs()
    w = spans.choose_window(spans.by_thread([M()] + earlier + later), 2, 5, log)
    assert w.launches == 3 and (w.lo, w.hi) == (1040 * MS, 1090 * MS)


@pytest.mark.parametrize("case", ["lapped", "launch_missing", "no_spans", "kept"])
def test_a_partial_window_reads_none(case):
    evs = [e for k in range(4) for e in step(20 * k, k + 1)]
    if case == "lapped":  # the ring dropped events and its oldest is younger than the first step
        evs = [M(dropped=7)] + [e for e in evs if e["ts"] + e["dur"] > 5 * MS]
    elif case == "launch_missing":
        evs = [M()] + [e for e in evs if not (e["name"] == "engine.launch" and e["args"]["launch"] == 2)]
    elif case == "no_spans":  # a program without the spans (the parent commit)
        evs = [M(), X("bench.other", 0, 5)]
    else:  # dropped long before the window: nothing of it is lost
        evs = [M(dropped=7)] + evs
    lines, log = logs()
    w = spans.choose_window(spans.by_thread(evs), 0 if case != "kept" else 1, 4, log)
    assert (w is None) == (case != "kept")
    assert bool(lines) == (case != "kept")


def test_grpo_step_less_its_rollout():
    evs = [M()]
    for k in range(2):  # two steps of 100 ms, each a rollout of 60 ms holding two engine steps
        t = 200 * k * MS
        evs += [X("grpo.step", t, 100 * MS, version=k), X("grpo.collect", t + 1 * MS, 80 * MS),
                X("collector.prompts", t + 2 * MS, 3 * MS, n=2),
                X("collector.rollout", t + 6 * MS, 60 * MS, requests=4, tokens=8),
                X("collector.assemble", t + 70 * MS, 5 * MS), X("grpo.update", t + 85 * MS, 4 * MS)]
        evs += step(200 * k + 10, 2 * k + 1) + step(200 * k + 30, 2 * k + 2)
    lines, log = logs()
    run = {"c0": {"decode_launches": 0, "steps": 0}, "c1": {"decode_launches": 4, "steps": 2}, "log": log}
    orig, spans.snapshot = spans.snapshot, lambda: spans.by_thread(evs)
    try:
        assert spans.non_rollout_ms_per_step(run) == pytest.approx(40.0)
        assert "collector.rollout 60.0000 ms/step (2)" in lines[-1] and "engine." not in lines[-1]
        run["c1"]["steps"] = 3  # the driver counted a step the recorder does not hold
        assert spans.non_rollout_ms_per_step(run) is None
    finally:
        spans.snapshot = orig


def test_slot_refill_across_two_occupants():
    """Slot 0: A finishes at 55 ms, B's first token is on the host at 62.5 ms
    (refill 7.5); B finishes at 85 ms and nobody follows inside the window
    (20 to 90 ms). Slot 1: C finished before the window opened."""
    evs = [M()] + [e for k in range(5) for e in step(20 * k, k + 1, admit=(k == 3))]
    evs[[e["name"] for e in evs].index("engine.admit")]["args"]["slots"] = [0]
    evs += [X("request", 1 * MS, 54 * MS, rid=1, slot=0, tokens=9),
            X("request", 60 * MS, 25 * MS, rid=2, slot=0, tokens=3),
            X("request", 0, 10 * MS, rid=0, slot=1, tokens=2)]
    _, log = logs()
    w = spans.choose_window(spans.by_thread(evs), 1, 5, log)
    refills, open_ = spans.slot_refills(w)
    assert refills == [pytest.approx((60 + 1.5 + 1 - 55) * MS)] and open_ == 1


@pytest.mark.parametrize("cell", sorted(test_cells.CELLS))
def test_every_new_metric_reads_on_the_tiny_cell(monkeypatch, tmp_path, cell):
    rc, last, _ = test_cells.run(monkeypatch, tmp_path, cell, trace=1)
    assert rc == 0 and last["correct"] is True
    new = {"gpt2-medium.grpo": ["engine_host_ms_per_launch.grpo", "non_rollout_ms_per_step.grpo"],
           "gpt2-medium.rollout": ["engine_host_ms_per_launch.gen", "slot_refill_ms.gen"]}[cell]
    for name in new:
        assert last["metrics"][name]["unit"] == "ms" and last["metrics"][name]["value"] > 0

"""The trace reduction: on planes built by hand (exact answers), and on
one small trace recorded on the chip and kept beside this file."""

import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6  # ns


def planes():
    ops = [("fusion.1", 10 * MS, 20 * MS), ("my_kernel", 30 * MS, 10 * MS), ("my_kernel", 70 * MS, 10 * MS),
           ("fusion.1", 75 * MS, 10 * MS), ("outside", 200 * MS, 5 * MS)]
    mods = [("jit_step(1)", 10 * MS, 30 * MS), ("jit_step(1)", 70 * MS, 15 * MS)]
    host = [("bench.window", 0.0, 100 * MS), ("bench.unit", 0.0, 50 * MS), ("bench.harvest", 41 * MS, 8 * MS),
            ("bench.unit", 50 * MS, 50 * MS), ("python_other", 0.0, 100 * MS)]
    return [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}, {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]},
    ]


def test_busy_idle_kernel_time_and_gap_owner_by_hand():
    red = trace.reduce_planes(planes())
    assert red.window_s == pytest.approx(0.100)
    # busy: [10,40] and [70,85] ms; the op outside the window does not count
    assert red.busy_s == pytest.approx(0.045)
    assert red.op_seconds("my_kernel") == (pytest.approx(0.020), 2)
    assert red.module_seconds("jit_step") == (pytest.approx(0.045), 2)
    # gaps: [0,10] unit, [40,70] -> middle 55 in the 2nd unit, [85,100] unit; none in harvest
    assert red.gaps == {"bench.unit": pytest.approx(0.055)}
    assert sum(red.gaps.values()) + red.busy_s == pytest.approx(red.window_s)
    b = red.breakdown()
    assert b["device_ops"][0][0] == "program jit_step(1)" and b["idle_gaps"][0][0] == "bench.unit"


def test_a_gap_goes_to_the_shortest_span_over_its_middle():
    pl = planes()
    pl[0]["lines"][0]["events"] = [("a", 0.0, 40 * MS), ("b", 50 * MS, 50 * MS)]
    red = trace.reduce_planes(pl)  # one gap, [40,50], middle 45: inside bench.harvest
    assert red.gaps == {"bench.harvest": pytest.approx(0.010)}


def test_no_window_span_is_an_error():
    pl = planes()
    pl[1]["lines"][0]["events"] = []
    with pytest.raises(ValueError):
        trace.reduce_planes(pl)


RECORDED = os.path.join(DATA, "small.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace kept")
def test_recorded_trace_from_the_chip():
    """Recorded on one TPU v5e by ``record_small_trace.py``: three runs of
    a jitted matmul chain inside ``bench.window``, each under a
    ``bench.unit`` span, with sleeps between them."""
    red = trace.reduce_file(RECORDED)
    assert red.n_devices == 1
    assert 0 < red.busy_s < red.window_s
    # the device trace holds two of the three launches: the one made in the
    # first microseconds after start_trace is not in it
    sec, n = red.module_seconds("small_chain")
    assert n == 2 and sec == pytest.approx(9.4777e-05, rel=1e-3)
    assert red.op_seconds("fusion") == (pytest.approx(9.4716e-05, rel=1e-3), 8)
    assert red.busy_s == pytest.approx(9.4748e-05, rel=1e-3) and red.window_s == pytest.approx(0.065253, rel=1e-3)
    # the sleeps sit between the bench.unit spans: their idle time is the window's own
    assert set(red.gaps) <= {"bench.window", "bench.unit"}
    assert red.gaps["bench.window"] > 0.055
    assert sum(red.gaps.values()) + red.busy_s == pytest.approx(red.window_s, rel=1e-6)

"""The trace reduction, on events whose numbers are worked out by hand."""
import os

import pytest

from chipbench import run, trace
from chipbench.trace import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
MOD = trace.MODULE_LINE


def _events():
    ev = [Event(HOST, "python3", "chipbench.window", 1000, 10000)]
    for name, s, e in (("draw", 1000, 1500), ("plan", 1500, 6000),
                       ("fetch", 6000, 7000), ("draw", 7000, 7200),
                       ("plan", 7200, 9000), ("fetch", 9000, 11000)):
        ev.append(Event(HOST, "python3", "chipbench." + name, s, e - s))
    for name, s, e in (("jit_old(2)", 900, 1100),  # straddles the start
                       ("jit_draw_gains(1)", 1200, 1300),
                       ("jit__multi_start(7)", 2000, 5000),
                       ("jit_broadcast(3)", 5500, 5800),
                       ("jit_draw_gains(1)", 7100, 7150),
                       ("jit__multi_start(7)", 8000, 10500),
                       ("jit_late(4)", 11500, 12000)):  # after the window
        ev.append(Event(DEV, MOD, name, s, e - s))
    # per-op events are not read
    ev.append(Event(DEV, "XLA Ops", "while.1", 5000, 500))
    return ev


def test_union():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def test_summary_by_hand():
    s = trace.summarize(_events())
    assert s.window_s == pytest.approx(10000e-9)
    # busy: 100 + 100 + 3000 + 300 + 50 + 2500 ns
    assert s.busy_s == pytest.approx(6050e-9)
    assert s.devices == 1 and s.requests == 2
    assert s.modules["jit__multi_start(7)"] == [2, pytest.approx(5500e-9)]
    assert s.modules["jit_draw_gains(1)"] == [2, pytest.approx(150e-9)]
    assert s.modules["jit_old(2)"] == [1, pytest.approx(100e-9)]
    assert "jit_late(4)" not in s.modules
    launches, secs = trace.program_modules(s)
    assert launches == 4 and secs == pytest.approx(5900e-9)
    assert s.device_ops[0] == ["jit__multi_start(7)", pytest.approx(5500e-9)]
    # idle 3950 ns: draw 100; plan 700 + 500 + 850; fetch 1300 + 500
    assert dict(s.idle_gaps) == {"plan": pytest.approx(2050e-9),
                                 "fetch": pytest.approx(1800e-9),
                                 "draw": pytest.approx(100e-9)}
    assert [g[0] for g in s.idle_gaps] == ["plan", "fetch", "draw"]


def test_metric_readers_by_hand():
    s = trace.summarize(_events())
    read = lambda name: run.load_metric(name)(s)
    assert read("plan_program_ms") == pytest.approx(5900e-9 * 1e3 / 2)
    assert read("launches_per_plan") == pytest.approx(2.0)
    assert read("idle_share.plan") == pytest.approx(39.5)


def test_readers_find_nothing_in_an_empty_window():
    ev = [Event(HOST, "python3", "chipbench.window", 0, 1000)]
    s = trace.summarize(ev)
    for name in ("plan_program_ms", "launches_per_plan", "idle_share.plan"):
        assert run.load_metric(name)(s) is None, name


def test_one_window_span_required():
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.summarize(_events() + _events()[:1])


def test_recorded_chip_trace():
    """Two ``alexnet-s6.plan`` requests traced on a TPU v5e, trimmed to the
    module executions and the benchmark's spans: 58 executions, none
    overlapping, so busy is the sum of their durations."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "alexnet_plan_trace.xplane.pb")
    events = trace.read_events(path)
    s = trace.summarize(events)
    mods = [e for e in events if e.line == trace.MODULE_LINE]
    assert len(mods) == 58
    assert s.window_s == pytest.approx(0.128433124)
    assert s.busy_s == pytest.approx(sum(e.dur_ns for e in mods) * 1e-9)
    assert s.busy_s == pytest.approx(0.092454974)
    assert s.requests == 2 and s.devices == 1
    # two plans: 2 × (1 draw + 27 eager fleet/scenario programs + 1 plan)
    assert s.modules["jit__multi_start(16445622223021564032)"] == [
        2, pytest.approx(0.092348656)]
    assert trace.program_modules(s)[0] == 56
    assert run.load_metric("launches_per_plan")(s) == 28
    assert run.load_metric("plan_program_ms")(s) == pytest.approx(
        (0.092454974 - 1.2717e-05) * 1e3 / 2)
    assert run.load_metric("idle_share.plan")(s) == pytest.approx(
        100 * (1 - 0.092454974 / 0.128433124))
    assert dict(s.idle_gaps) == {"plan": pytest.approx(0.033430996),
                                 "draw": pytest.approx(0.002244349),
                                 "host": pytest.approx(0.000302805)}

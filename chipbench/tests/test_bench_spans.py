"""The program's span table, its idle labels and the price loop's
numbers, on events whose numbers are worked out by hand."""
import os

import pytest

from chipbench import spans, trace
from chipbench.trace import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
MOD = trace.MODULE_LINE


def _events():
    ev = [Event(HOST, "python", "chipbench.window", 0, 10000)]
    for name, s, e in (("chipbench.plan", 1000, 9000),
                       ("chipbench.plan", 9200, 9900),
                       ("repro.step", -500, 500),  # starts before the window
                       ("repro.plan_sharded", 1100, 8900),
                       ("repro.step", 1200, 5000),
                       ("repro.price.lam", 1300, 4800),
                       ("repro.price.probe", 1400, 2400),
                       ("repro.price.wait", 1900, 2300),
                       ("repro.price.probe", 2600, 3600),
                       ("repro.price.wait", 3000, 3500),
                       ("repro.step", 5100, 8800),
                       ("repro.price.mu", 5200, 8000),
                       ("repro.price.probe", 5300, 6300),
                       ("repro.price.wait", 5800, 6200)):
        ev.append(Event(HOST, "python", name, s, e - s))
    # another thread: neither a child of the spans above nor their parent
    ev.append(Event(HOST, "worker", "repro.price.probe", 1500, 200))
    for s, e in ((1000, 1950), (2250, 3050), (3450, 4600), (4800, 9500)):
        ev.append(Event(DEV, MOD, "jit_bsum(1)", s, e - s))
    return ev


def test_span_table_by_hand():
    tab = spans.table(_events())
    ns = pytest.approx
    assert tab == {
        # 7800 ns less its two steps (3800 + 3700)
        "repro.plan_sharded": [1, ns(7800e-9), ns(300e-9)],
        # 3800 less lam 3500, 3700 less mu 2800
        "repro.step": [2, ns(7500e-9), ns(1200e-9)],
        "repro.price.lam": [1, ns(3500e-9), ns(1500e-9)],
        "repro.price.mu": [1, ns(2800e-9), ns(1800e-9)],
        # 600 + 500 + 600 on the main thread, the worker's 200 whole
        "repro.price.probe": [4, ns(3200e-9), ns(1900e-9)],
        "repro.price.wait": [3, ns(1300e-9), ns(1300e-9)],
    }


def test_program_idle_gaps_take_the_innermost_span():
    ev = _events()
    gaps = spans.program_idle_gaps(ev)
    # 0-1000 no span; 1950-2250 and 3050-3450 in waits; 4600-4800 in the
    # clearing between probes; 9500-10000 in the second plan request
    assert dict(gaps) == {"host": pytest.approx(1000e-9),
                          "repro.price.wait": pytest.approx(700e-9),
                          "plan": pytest.approx(500e-9),
                          "repro.price.lam": pytest.approx(200e-9)}
    assert [g[0] for g in gaps][0] == "host"
    # the same gaps as the accepted reduction, only labelled finer
    s = trace.summarize(ev)
    assert dict(s.idle_gaps) == {"plan": pytest.approx(1400e-9),
                                 "host": pytest.approx(1000e-9)}
    assert sum(v for _, v in gaps) == pytest.approx(
        sum(v for _, v in s.idle_gaps))


def test_price_loop_numbers_by_hand():
    ev = _events()
    tab, requests = spans.table(ev), trace.summarize(ev).requests
    assert requests == 2
    assert spans.price_probes_per_plan(tab, requests) == 2.0
    assert spans.price_wait_ms(tab, requests) == pytest.approx(1300e-6 / 2)
    # lam 3500 + mu 2800 less the waits 1300
    assert spans.price_host_ms(tab, requests) == pytest.approx(5000e-6 / 2)
    r = spans.reduce(ev)
    assert r["price_probes_per_plan"] == 2.0
    assert r["launches_per_plan"] == 2.0  # 4 bsum executions, 2 requests


def test_nothing_to_read_in_an_empty_window():
    ev = [Event(HOST, "python", "chipbench.window", 0, 1000)]
    tab = spans.table(ev)
    assert tab == {} and spans.program_idle_gaps(ev) == []
    for fn in (spans.price_probes_per_plan, spans.price_wait_ms,
               spans.price_host_ms):
        assert fn(tab, 0) is None and fn(tab, 3) is None, fn.__name__


def test_one_window_span_required():
    with pytest.raises(ValueError, match="chipbench.window"):
        spans.table(_events() + _events()[:1])


def test_recorded_chip_trace_has_no_program_spans():
    """The recorded ``alexnet-s6.plan`` trace predates the program's
    spans: nothing to read, and its gaps labelled as the accepted
    reduction labels them."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "alexnet_plan_trace.xplane.pb")
    events = trace.read_events(path)
    tab = spans.table(events)
    assert tab == {}
    for fn in (spans.price_probes_per_plan, spans.price_wait_ms,
               spans.price_host_ms):
        assert fn(tab, 2) is None, fn.__name__
    gaps = spans.program_idle_gaps(events)
    assert dict(gaps) == pytest.approx(dict(trace.summarize(events).idle_gaps))

"""The sharded planner's host spans in a profiler trace.

``plan_sharded`` marks its host price loop with ``repro.*``
``TraceAnnotation``s; the benchmark reduces them from the profiler's
trace (``chipbench.spans``). Here one plan of a two-group fleet is traced
on the CPU and the span tree checked: its nesting, one plan, one step per
alternation step plus the final one, and one probe for each call of the
per-group partial-sum programs over the groups — counted by wrapping
those programs here.
"""
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from chipbench import spans, trace
from repro.configs.paper_tables import mixed_spec
from repro.core import decompose
from repro.core.api import Planner, PlannerConfig, Scenario

OUTER = 2
SC = Scenario(0.2, 0.04, 30e6)
SUMS = ("bsum", "occ_sum")  # the programs one probe calls once per group
PARENT = {"repro.build_groups": ("repro.plan_sharded",),
          "repro.step": ("repro.plan_sharded",),
          "repro.price.lam": ("repro.step",),
          "repro.price.mu": ("repro.step",),
          "repro.price.probe": ("repro.price.lam", "repro.price.mu"),
          "repro.price.wait": ("repro.price.probe",)}


def _parent(e, events):
    """The innermost other ``repro.`` span on ``e``'s thread holding it."""
    end = lambda x: x.start_ns + x.dur_ns
    holders = [x for x in events if x is not e and x.name.startswith("repro.")
               and (x.plane, x.line) == (e.plane, e.line)
               and x.start_ns <= e.start_ns and end(e) <= end(x)
               and x.dur_ns >= e.dur_ns]
    return min(holders, key=lambda x: x.dur_ns, default=None)


@pytest.mark.parametrize("edge", ["slack", "binding"])
def test_plan_sharded_span_tree(edge, tmp_path, monkeypatch):
    spec = mixed_spec(8)
    gains = spec.sample_gains(jax.random.PRNGKey(11))
    calls = Counter()
    build = decompose._group_programs

    def counted(*statics):
        progs = build(*statics)

        def wrap(name):
            fn = getattr(progs, name)

            def call(*args):
                calls[name] += 1
                return fn(*args)
            return call
        return progs._replace(**{name: wrap(name) for name in SUMS})

    monkeypatch.setattr(decompose, "_group_programs", counted)
    cfg = dict(policy="robust_exact", outer_iters=OUTER)
    plan = Planner(PlannerConfig(**cfg)).plan_sharded(spec, SC, gains=gains)
    if edge == "binding":
        t_vm = spec.build(gains=gains).chain.t_vm
        occ = float(jnp.sum(jnp.take_along_axis(t_vm, plan.m_sel[:, None], -1)))
        cfg["edge_capacity_s"] = 0.3 * occ
    planner = Planner(PlannerConfig(**cfg))
    jax.block_until_ready(planner.plan_sharded(spec, SC, gains=gains))  # warm

    calls.clear()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            jax.block_until_ready(planner.plan_sharded(spec, SC, gains=gains))
    events = trace.read_events(str(next(tmp_path.rglob("*.xplane.pb"))))

    mine = [e for e in events if e.name.startswith("repro.")]
    for e in mine:
        parent = _parent(e, mine)
        if e.name == "repro.plan_sharded":
            assert parent is None
        else:
            assert parent is not None and parent.name in PARENT[e.name], e
    tab = spans.table(events)
    assert tab["repro.plan_sharded"][0] == 1
    assert tab["repro.build_groups"][0] == 1
    assert tab["repro.step"][0] == 1 + OUTER
    assert tab["repro.price.lam"][0] == 1 + OUTER
    assert ("repro.price.mu" in tab) == (edge == "binding")
    assert calls["occ_sum"] > 0 if edge == "binding" else not calls["occ_sum"]
    probes = tab["repro.price.probe"][0]
    assert probes * 2 == calls["bsum"] + calls["occ_sum"]  # two groups
    assert tab["repro.price.wait"][0] == probes
    for name, (count, secs, self_s) in tab.items():
        assert 0.0 <= self_s <= secs, name

    # the price loop's numbers, for one plan request
    assert spans.price_probes_per_plan(tab, 1) == probes
    wait_ms, host_ms = spans.price_wait_ms(tab, 1), spans.price_host_ms(tab, 1)
    assert wait_ms > 0.0 and host_ms > 0.0
    assert wait_ms + host_ms <= tab["repro.plan_sharded"][1] * 1e3

"""Layer 2: jaxpr/compile audit (DESIGN.md §analysis).

Where Layer 1 reads source, this layer checks the *actually traced*
programs: it runs ``jax.make_jaxpr`` over the real entry points at tiny
sizes and asserts graph-level invariants —

- **no host callbacks** (``pure_callback``/``io_callback``/debug
  prints): a callback in the planner hot path means a device→host sync
  per call;
- **no weak-type leaks** on outputs, and only contract dtypes
  (float64/int32/bool — the planner is an x64 precision island; a
  float32 output means an accidental downcast, an int64 output an
  unstable integer leaf);
- **no giant baked-in constants**: closures must capture only small
  index/schedule tables (≤ ``contracts.CONST_BYTE_BUDGET``), never a
  fleet or profile table that should be an argument;
- **pytree contracts**: ``Scenario``/``Plan``/``Allocation``/
  ``FaultState`` flatten to the declared leaf paths and dtypes, in
  order — what golden files and any scan/cond over plans assume;
- **recompile counting**: :class:`CompileCounter` hooks jax's
  compile-event monitoring so tests (and the CI drill) can pin "this
  K-scenario sweep compiled exactly once".
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.analysis import contracts

__all__ = [
    "AuditProblem", "EntryAudit", "CompileCounter", "audit_jaxpr",
    "check_pytree_contract", "run_audit", "tiny_fleet",
]

#: substrings of primitive names that imply a host round-trip
_CALLBACK_MARKERS = ("callback", "infeed", "outfeed", "python_callback")


@dataclass(frozen=True)
class AuditProblem:
    entry: str
    kind: str  # "callback" | "weak_type" | "dtype" | "const_budget" | "pytree"
    detail: str

    def render(self) -> str:
        return f"{self.entry}: [{self.kind}] {self.detail}"


@dataclass
class EntryAudit:
    entry: str
    problems: List[AuditProblem] = field(default_factory=list)
    num_eqns: int = 0
    const_bytes: int = 0
    out_dtypes: Tuple[str, ...] = ()

    @property
    def ok(self) -> bool:
        return not self.problems


def _iter_jaxprs(jaxpr):
    """Yield `jaxpr` and every sub-jaxpr reachable through eqn params
    (scan/while/cond bodies, custom_jvp closures, pjit calls, ...)."""
    seen = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    stack.append(sub)


def _sub_jaxprs(val):
    if isinstance(val, ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _sub_jaxprs(v)


_ALLOWED_OUT = tuple(sorted(contracts.ALLOWED_OUTPUT_DTYPES))


def audit_jaxpr(closed: ClosedJaxpr, *, entry: str,
                const_budget: int = contracts.CONST_BYTE_BUDGET,
                allowed_out_dtypes: Sequence[str] = _ALLOWED_OUT,
                ) -> EntryAudit:
    """Graph-level invariants on one traced program."""
    audit = EntryAudit(entry=entry)
    num_eqns = 0
    for j in _iter_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            num_eqns += 1
            name = eqn.primitive.name
            if any(m in name for m in _CALLBACK_MARKERS):
                audit.problems.append(AuditProblem(
                    entry, "callback",
                    f"primitive `{name}` — host round-trip inside the "
                    "compiled program"))
    audit.num_eqns = num_eqns

    const_bytes = 0
    for c in closed.consts:
        arr = np.asarray(c)
        const_bytes += arr.nbytes
    audit.const_bytes = const_bytes
    if const_bytes > const_budget:
        audit.problems.append(AuditProblem(
            entry, "const_budget",
            f"{const_bytes} bytes of baked-in constants exceed the "
            f"{const_budget}-byte budget — a fleet/profile table leaked "
            "into a closure instead of being an argument"))

    out: List[str] = []
    for av in closed.jaxpr.outvars:
        aval = av.aval
        dt = str(getattr(aval, "dtype", ""))
        out.append(dt)
        if getattr(aval, "weak_type", False):
            audit.problems.append(AuditProblem(
                entry, "weak_type",
                f"output aval {aval} is weakly typed — a Python scalar "
                "leaked into the output dtype lattice"))
        if dt and dt not in allowed_out_dtypes:
            audit.problems.append(AuditProblem(
                entry, "dtype",
                f"output dtype {dt} is outside the contract "
                f"{tuple(allowed_out_dtypes)} (float64 island, stable "
                "int32/bool integer leaves)"))
    audit.out_dtypes = tuple(out)
    return audit


# ---------------------------------------------------------------------------
# Pytree contracts
# ---------------------------------------------------------------------------


def check_pytree_contract(name: str, tree: Any) -> List[AuditProblem]:
    """Flattened (path, dtype) pairs must match ``contracts.PYTREE_CONTRACTS``
    exactly — count, order, and dtype."""
    from jax.tree_util import keystr, tree_flatten_with_path

    expected = contracts.PYTREE_CONTRACTS[name]
    leaves, _ = tree_flatten_with_path(tree)
    got = tuple((keystr(path), str(jnp.asarray(leaf).dtype))
                for path, leaf in leaves)
    problems: List[AuditProblem] = []
    if len(got) != len(expected):
        problems.append(AuditProblem(
            name, "pytree",
            f"{len(got)} leaves, contract declares {len(expected)} — "
            "a leaf was added/removed; golden files and scans assume the "
            "declared flattening"))
    for i, ((gp, gd), (ep, ed)) in enumerate(zip(got, expected, strict=False)):
        if gp != ep:
            problems.append(AuditProblem(
                name, "pytree",
                f"leaf {i} is {gp}, contract says {ep} (order/rename drift)"))
        elif gd != ed:
            problems.append(AuditProblem(
                name, "pytree", f"leaf {gp} has dtype {gd}, contract says {ed}"))
    weak = [(keystr(p), leaf) for p, leaf in leaves
            if getattr(jnp.asarray(leaf), "weak_type", False)]
    for path, _ in weak:
        problems.append(AuditProblem(
            name, "pytree", f"leaf {path} is weakly typed"))
    return problems


# ---------------------------------------------------------------------------
# Recompile counting
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts real XLA backend compiles via ``jax.monitoring``.

    jax has no listener-unregister API, so one module-level listener is
    installed on first use and forwards to whichever counters are
    active (re-entrant: nested counters both see the event).

    Usage::

        with CompileCounter() as c:
            plan_many_jit(...)   # first call compiles
            plan_many_jit(...)   # same shapes/statics: cache hit
        assert c.count == 1
    """

    _lock = threading.Lock()
    _installed = False
    _active: List["CompileCounter"] = []

    def __init__(self) -> None:
        self.count = 0

    @classmethod
    def _listener(cls, event: str, duration: float, **kwargs) -> None:
        if "backend_compile" not in event:
            return
        with cls._lock:
            for c in cls._active:
                c.count += 1

    @classmethod
    def _install(cls) -> None:
        with cls._lock:
            if not cls._installed:
                jax.monitoring.register_event_duration_secs_listener(
                    cls._listener)
                cls._installed = True

    def __enter__(self) -> "CompileCounter":
        self._install()
        with self._lock:
            self._active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        with self._lock:
            self._active.remove(self)


# ---------------------------------------------------------------------------
# Entry-point sweep
# ---------------------------------------------------------------------------


def tiny_fleet(n: int = 3):
    """Smallest representative fleet (AlexNet tables, n devices)."""
    from repro.configs.paper_tables import alexnet_fleet

    return alexnet_fleet(jax.random.PRNGKey(0), n)


def _trace_entries(n: int = 3) -> List[Tuple[str, ClosedJaxpr]]:
    """make_jaxpr over the real public entry points at tiny sizes."""
    from repro.core.api import Planner, PlannerConfig, Scenario, stack_scenarios
    from repro.core.ccp import sigma_cantelli
    from repro.core.montecarlo import violation_report
    from repro.core.pccp import _inner_spec
    from repro.core.planner import plan_fixed_partition
    from repro.serve.faults import FaultState
    from repro.solvers.ipm import structured_barrier_solve

    fleet = tiny_fleet(n)
    sc = Scenario(deadline=0.18, eps=0.02, B=10e6).normalized(n)
    planner = Planner(PlannerConfig(policy="robust", multi_start=2))
    key = jax.random.PRNGKey(7)
    m0 = jnp.zeros((n,), jnp.int32)
    faults = FaultState.identity()._replace(
        vm_mean_scale=jnp.asarray(3.0, jnp.float64))

    entries: List[Tuple[str, ClosedJaxpr]] = []

    def add(name, fn, *args, **kwargs):
        entries.append((name, jax.make_jaxpr(fn, **kwargs)(*args)))

    add("Planner.plan", lambda f, s: planner.plan(f, s), fleet, sc)
    scs = stack_scenarios([sc, sc._replace(deadline=sc.deadline * 1.1)], n)
    add("Planner.plan_many", lambda f, s: planner.plan_many(f, s), fleet, scs)
    add("Planner.grid",
        lambda f, d, e: planner.grid(f, d, e, 10e6),
        fleet, jnp.asarray([0.15, 0.18]), jnp.asarray([0.02, 0.05]))
    # a PCCP inner problem — the exact spec the planner hot loop solves
    m1 = 7
    e_tab = jnp.linspace(0.05, 0.9, m1)
    t_tab = jnp.linspace(0.01, 0.12, m1)
    v_tab = jnp.linspace(1e-6, 2e-4, m1)
    x_prev = jnp.full((m1,), 1.0 / m1)
    y_prev = jnp.sqrt(jnp.dot(v_tab, x_prev**2))
    spec, z0 = _inner_spec(e_tab, t_tab, v_tab, sigma_cantelli(jnp.asarray(0.05)),
                           jnp.asarray(0.12), 10.0, x_prev, y_prev)
    # spec is closed over, not passed: its index metadata is trace-time
    # static by construction (the planner builds it inside the jit)
    add("structured_barrier_solve",
        lambda z: structured_barrier_solve(spec, z), z0)
    add("violation_report",
        lambda k, f, m: violation_report(
            k, f, m, plan_fixed_partition(f, m, sc.deadline, sc.eps,
                                          sc.B).alloc,
            sc.deadline, num_samples=8),
        key, fleet, m0)
    add("violation_report+faults",
        lambda k, f, m, st: violation_report(
            k, f, m, plan_fixed_partition(f, m, sc.deadline, sc.eps,
                                          sc.B).alloc,
            sc.deadline, num_samples=8, faults=st),
        key, fleet, m0, faults)
    add("closedloop.step(plan_fixed_partition)",
        lambda f, m, d, e, b: plan_fixed_partition(f, m, d, e, b),
        fleet, m0, sc.deadline, sc.eps, sc.B)
    return entries


def run_audit(n: int = 3) -> Dict[str, Any]:
    """Full Layer-2 sweep; returns a JSON-ready report dict."""
    from repro.core.api import Scenario
    from repro.core.planner import Plan
    from repro.serve.faults import FaultState

    report: Dict[str, Any] = {"entries": {}, "pytrees": {}, "problems": []}
    for name, closed in _trace_entries(n):
        audit = audit_jaxpr(closed, entry=name)
        report["entries"][name] = {
            "ok": audit.ok,
            "num_eqns": audit.num_eqns,
            "const_bytes": audit.const_bytes,
            "out_dtypes": sorted(set(audit.out_dtypes)),
            "problems": [p.render() for p in audit.problems],
        }
        report["problems"] += [p.render() for p in audit.problems]

    fleet = tiny_fleet(n)
    sc = Scenario(deadline=0.18, eps=0.02, B=10e6).normalized(n)
    from repro.core.api import Planner, PlannerConfig

    examples = {
        "Scenario": sc,
        "Plan": Planner(PlannerConfig(policy="robust")).plan(fleet, sc),
        "FaultState": FaultState.identity(),
    }
    examples["Allocation"] = examples["Plan"].alloc
    assert isinstance(examples["Plan"], Plan)
    for name, tree in examples.items():
        probs = check_pytree_contract(name, tree)
        report["pytrees"][name] = {
            "ok": not probs, "problems": [p.render() for p in probs]}
        report["problems"] += [p.render() for p in probs]

    # recompile drill: a 4-scenario sweep reuses one compiled program —
    # the second (value-varied) call must not trigger any backend compile
    from repro.core.api import plan_many_jit, stack_scenarios, _BATCH_STATICS  # noqa: F401
    planner = Planner(PlannerConfig(policy="robust"))
    scs = stack_scenarios([
        sc._replace(deadline=jnp.full_like(sc.deadline, 0.15 + 0.01 * i))
        for i in range(4)], n)
    planner.plan_many(fleet, scs)  # warm the cache
    with CompileCounter() as c:
        varied = stack_scenarios([
            sc._replace(deadline=jnp.full_like(sc.deadline, 0.16 + 0.01 * i))
            for i in range(4)], n)
        jax.block_until_ready(planner.plan_many(fleet, varied).total_energy)
    report["recompile_drill"] = {
        "ok": c.count == 0,
        "backend_compiles_on_value_varied_repeat": c.count,
    }
    if c.count:
        report["problems"].append(
            f"recompile_drill: {c.count} backend compiles on a value-varied "
            "plan_many repeat — a scenario knob became static")

    # group-sharded drill: the decomposed planner compiles one program per
    # distinct (M_g, n_bucket) group shape; a value-varied repeat (new
    # scenario values AND new gains, same group shapes) must compile zero
    # times per group — prices/gains are traced operands, never baked in.
    from repro.configs.paper_tables import mixed_spec

    spec = mixed_spec(8)
    sharded = Planner(PlannerConfig(policy="robust_exact", outer_iters=2))
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    sharded.plan_sharded(spec, Scenario(deadline=0.2, eps=0.04, B=30e6),
                         key=k1)  # warm
    with CompileCounter() as cs:
        varied = sharded.plan_sharded(
            spec, Scenario(deadline=0.21, eps=0.05, B=28e6), key=k2)
        jax.block_until_ready(varied.total_energy)
    report["sharded_recompile_drill"] = {
        "ok": cs.count == 0,
        "backend_compiles_on_value_varied_repeat": cs.count,
    }
    if cs.count:
        report["problems"].append(
            f"sharded_recompile_drill: {cs.count} backend compiles on a "
            "value-varied plan_sharded repeat — a per-group program is "
            "recompiling on scenario/gain values")

    # placement drill: per-node capacity vectors are traced operands of
    # the same compiled program — a value-varied (E,) capacity repeat
    # (same E, different node budgets) must trigger zero backend compiles.
    caps0 = jnp.asarray([0.08, 0.05, 0.03], jnp.float64)
    planner.plan(fleet, sc._replace(edge_capacity_s=caps0))  # warm
    with CompileCounter() as cp:
        shifted = sc._replace(
            edge_capacity_s=jnp.asarray([0.06, 0.07, 0.02], jnp.float64))
        jax.block_until_ready(planner.plan(fleet, shifted).total_energy)
    report["placement_recompile_drill"] = {
        "ok": cp.count == 0,
        "backend_compiles_on_value_varied_repeat": cp.count,
    }
    if cp.count:
        report["problems"].append(
            f"placement_recompile_drill: {cp.count} backend compiles on a "
            "value-varied per-node capacity repeat — the capacity vector "
            "or assignment leaked into a static")

    # replay drill: the trace-driven epoch sampler pads request batches
    # to a static capacity, so a value-varied epoch — different request
    # count/devices, different key, different (E,) fault state — must
    # reuse the one compiled program.
    from repro.serve.faults import brownout, state_at
    from repro.serve.replay import sample_epoch

    rplan = planner.plan(fleet, sc._replace(edge_capacity_s=caps0))
    rsched = brownout(4, start=1, length=2, depth=0.5, node=1, num_nodes=3)
    dev = jnp.asarray([0, 1, 2, 0, 1, 2, 0, 1], jnp.int32)
    valid = jnp.arange(8) < 6
    key = jax.random.PRNGKey(9)
    # the value-varied operands are built eagerly BEFORE the counter —
    # the drill pins the epoch program, not jnp.roll's dispatch cache
    key2 = jax.random.fold_in(key, 1)
    dev2 = jnp.roll(dev, 1)
    valid2 = jnp.arange(8) < 4
    caps2 = caps0 * 0.7
    state0, state1 = state_at(rsched, 0), state_at(rsched, 1)
    sample_epoch(key, fleet, rplan.m_sel, rplan.alloc, sc.deadline, dev,
                 valid, 2.0, edge_capacity_s=caps0, faults=state0,
                 assignment=rplan.assignment)  # warm
    with CompileCounter() as cr:
        out = sample_epoch(
            key2, fleet, rplan.m_sel, rplan.alloc, sc.deadline, dev2,
            valid2, 3.0, edge_capacity_s=caps2, faults=state1,
            assignment=rplan.assignment)
        jax.block_until_ready(out.total_s)
    report["replay_recompile_drill"] = {
        "ok": cr.count == 0,
        "backend_compiles_on_value_varied_repeat": cr.count,
    }
    if cr.count:
        report["problems"].append(
            f"replay_recompile_drill: {cr.count} backend compiles on a "
            "value-varied replay epoch — a trace batch leaf (device_ids/"
            "valid/rounds) or fault state leaked into a static")

    report["ok"] = not report["problems"]
    return report

"""Multi-edge placement (DESIGN.md §placement).

Pins the tentpole contracts of ``core.placement``:

- **E=1 reduction**: a one-node capacity vector is leaf-identical to the
  scalar shared edge, for every planner policy — which is what keeps the
  golden-pinned scalar plans (and PR 4's edge pins) valid under the new
  placement layer;
- **assignment invariants**: every registered strategy places each
  device on exactly one *present* node (0-capacity ⇒ absent),
  deterministically, and the numpy host mirror replays the traced
  strategy bit-for-bit (the contract ``core.decompose`` relies on);
- **capacity enforcement**: planned E>1 plans satisfy the per-node
  occupancy rows at the returned per-node prices, and the duality-gap
  certificate is non-negative;
- **Cantelli edge rows**: ``edge_eps`` reduces exactly to the mean
  occupancy row at zero VM variance and strictly tightens otherwise;
- **Hybrid vs Balanced**: the migration pass never loads the scarcest
  node worse than Balanced (property-tested).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.configs.paper_tables import alexnet_fleet
from repro.core import Planner, PlannerConfig, Scenario, allocate
from repro.core import placement
from repro.core.placement import (
    assign_devices,
    assign_devices_host,
    available_assignments,
    node_loads,
    plan_duality_gap,
)
from repro.core.resource import select_point

D, B, EPS = 0.40, 10e6, 0.02
N = 10

STRATEGIES = available_assignments()


@pytest.fixture(scope="module")
def fleet():
    return alexnet_fleet(jax.random.PRNGKey(0), N)


def occupancy(fleet, m_sel) -> float:
    return float(select_point(fleet, m_sel).t_vm.sum())


@pytest.fixture(scope="module")
def slack_occ(fleet):
    p0 = Planner(PlannerConfig(policy="robust_exact", outer_iters=3)).plan(
        fleet, Scenario(D, EPS, B))
    return occupancy(fleet, p0.m_sel)


def assert_plans_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb, strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------------ E=1 reduction


@pytest.mark.parametrize("policy", ["robust_exact", "robust", "optimal"])
def test_one_node_vector_is_leaf_identical_to_scalar(fleet, slack_occ, policy):
    """(1,) capacity vectors ARE the scalar edge — every policy, every
    leaf (including the all-zeros assignment stamp)."""
    planner = Planner(PlannerConfig(policy=policy, outer_iters=3,
                                    pccp_iters=4))
    cap = 0.6 * slack_occ
    p_scalar = planner.plan(fleet, Scenario(D, EPS, B, cap))
    p_vec = planner.plan(fleet, Scenario(D, EPS, B, jnp.asarray([cap])))
    assert_plans_equal(p_scalar, p_vec)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_one_node_assignment_is_all_zeros(strategy):
    occ = jnp.linspace(0.01, 0.1, 7)
    a = assign_devices(occ, jnp.asarray([0.5]), strategy)
    np.testing.assert_array_equal(np.asarray(a), np.zeros(7, np.int32))


# ----------------------------------------------------- assignment invariants

_RNG = np.random.default_rng(0)
_CASES = [
    (_RNG.uniform(0.01, 0.2, size=9), np.array([0.5, 0.3, 0.2])),
    (_RNG.uniform(0.01, 0.2, size=9), np.array([np.inf, 0.2, 0.1])),
    (_RNG.uniform(0.01, 0.2, size=9), np.array([0.0, 0.4, 0.0, 0.4])),
    (_RNG.uniform(0.01, 0.2, size=12), np.array([np.inf, np.inf])),
    (np.full(6, 0.05), np.array([0.1, 0.0, 1.0])),
]


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("case", range(len(_CASES)))
def test_assignment_invariants(strategy, case):
    """One present node per device, deterministic, host ≡ traced."""
    occ, caps = _CASES[case]
    a = np.asarray(assign_devices(occ, caps, strategy))
    assert a.shape == occ.shape and a.dtype == np.int32
    assert np.all((a >= 0) & (a < caps.shape[0]))
    # 0-capacity nodes are absent: NO strategy may place on them
    assert np.all(caps[a] > 0.0), (strategy, a, caps)
    # deterministic
    np.testing.assert_array_equal(
        a, np.asarray(assign_devices(occ, caps, strategy)))
    # host mirror is bit-identical (the decompose host-loop contract)
    np.testing.assert_array_equal(
        a, assign_devices_host(occ, caps, strategy))


def test_round_robin_cycles_present_nodes_only():
    a = np.asarray(assign_devices(np.full(6, 0.1),
                                  np.array([0.5, 0.0, 0.5]), "round_robin"))
    np.testing.assert_array_equal(a, [0, 2, 0, 2, 0, 2])


def test_unknown_strategy_raises():
    with pytest.raises(ValueError, match="unknown assignment strategy"):
        assign_devices(np.ones(3), np.ones(2), "nope")
    with pytest.raises(ValueError, match="unknown assignment strategy"):
        assign_devices_host(np.ones(3), np.ones(2), "nope")


@given(occ=st.lists(st.floats(0.001, 10.0), min_size=1, max_size=16),
       caps=st.lists(st.floats(0.0, 5.0), min_size=2, max_size=5))
@settings(max_examples=60, deadline=None)
def test_hybrid_never_loads_scarcest_node_worse_than_balanced(occ, caps):
    """The migration pass only ever *removes* load from the scarcest
    present node — so for every input Hybrid fragments it no worse than
    Balanced (the structural guarantee in ``_assign_hybrid``)."""
    occ = np.asarray(occ, np.float64)
    caps = np.asarray(caps, np.float64)
    if not np.any(caps > 0.0):
        caps[0] = 1.0
    ceff = np.where(np.isfinite(caps), caps, placement._CAP_BIG)
    e_star = int(np.argmin(np.where(caps > 0.0, ceff, np.inf)))
    load = lambda strat: float(np.sum(
        occ[assign_devices_host(occ, caps, strat) == e_star]))
    assert load("hybrid") <= load("balanced") + 1e-12


@given(occ=st.lists(st.floats(0.001, 10.0), min_size=1, max_size=16),
       caps=st.lists(st.floats(0.0, 5.0), min_size=2, max_size=5),
       strat=st.sampled_from(list(STRATEGIES)))
@settings(max_examples=60, deadline=None)
def test_host_traced_bit_identity_property(occ, caps, strat):
    occ = np.asarray(occ, np.float64)
    caps = np.asarray(caps, np.float64)
    if not np.any(caps > 0.0):
        caps[0] = 1.0
    np.testing.assert_array_equal(
        np.asarray(assign_devices(occ, caps, strat)),
        assign_devices_host(occ, caps, strat))


@pytest.mark.parametrize("strat", list(STRATEGIES))
def test_host_traced_bit_identity_subnormal_caps(strat):
    """XLA flushes subnormal inputs to zero, so a subnormal capacity is an
    absent node to the traced strategies; the host mirror must agree
    (a hypothesis counter-example: caps=[0, 5e-324])."""
    occ = np.asarray([0.5, 1.0, 2.0], np.float64)
    for caps in ([0.0, 5e-324], [5e-324, 1.0, 0.0], [1.0, 1e-310]):
        caps = np.asarray(caps, np.float64)
        np.testing.assert_array_equal(
            np.asarray(assign_devices(occ, caps, strat)),
            assign_devices_host(occ, caps, strat), err_msg=str(caps))


# ------------------------------------------------------ planned E>1 plans


def test_planned_assignment_respects_per_node_capacity(fleet, slack_occ):
    caps = jnp.asarray([0.5, 0.35, 0.25]) * slack_occ
    p = Planner(PlannerConfig(policy="robust_exact", outer_iters=3)).plan(
        fleet, Scenario(D, EPS, B, caps))
    assert bool(np.asarray(p.feasible).all())
    a = np.asarray(p.assignment)
    assert a.shape == (N,)
    occ_e = np.asarray(node_loads(select_point(fleet, p.m_sel).t_vm,
                                  p.assignment, 3))
    assert np.all(occ_e <= np.asarray(caps) * (1 + 1e-9)), (occ_e, caps)
    # the price is a per-node vector now
    assert np.asarray(p.alloc.mu).shape == (3,)


def test_duality_gap_certificate(fleet, slack_occ):
    caps = jnp.asarray([0.5, 0.35, 0.25]) * slack_occ
    p = Planner(PlannerConfig(policy="robust_exact", outer_iters=3)).plan(
        fleet, Scenario(D, EPS, B, caps))
    gap = float(plan_duality_gap(fleet, p, D, EPS, caps))
    assert np.isfinite(gap)
    assert gap >= -1e-8  # primal ≥ dual lower bound, always
    # the bound is meaningful: within the primal's own scale
    assert gap <= float(p.total_energy)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_policy_assign_strategy_is_selectable(fleet, slack_occ, strategy):
    """Unregistered Policy instances select the allocator family member;
    every member yields a valid (feasible-or-flagged) plan."""
    from repro.core.planner import get_policy
    import dataclasses

    pol = dataclasses.replace(get_policy("robust_exact"), assign=strategy)
    caps = jnp.asarray([0.6, 0.4, 0.3]) * slack_occ
    p = Planner(PlannerConfig(policy=pol, outer_iters=3)).plan(
        fleet, Scenario(D, EPS, B, caps))
    a = np.asarray(p.assignment)
    assert np.all((a >= 0) & (a < 3))
    if bool(np.asarray(p.feasible).all()):
        occ_e = np.asarray(node_loads(select_point(fleet, p.m_sel).t_vm,
                                      p.assignment, 3))
        assert np.all(occ_e <= np.asarray(caps) * (1 + 1e-9))


def test_grid_with_per_node_rows_and_absent_node(fleet, slack_occ):
    """(K, E) capacity rows are a traced grid axis; a 0 entry marks the
    node absent in that row — node-count what-ifs on one program."""
    c = 0.4 * slack_occ
    rows = jnp.asarray([[c, c, c], [1.5 * c, 1.5 * c, 0.0]])
    planner = Planner(PlannerConfig(policy="robust_exact", outer_iters=3))
    grid = planner.grid(fleet, D, EPS, B, edge_capacities=rows)
    assert grid.total_energy.shape == (1, 1, 1, 2)
    a_absent = np.asarray(grid.assignment)[0, 0, 0, 1]
    assert np.all(a_absent != 2), "absent node must never be assigned"
    # each row matches its single-scenario plan leaf-for-leaf
    for k in range(2):
        cell = jax.tree_util.tree_map(lambda x: x[0, 0, 0, k], grid)
        single = planner.plan(fleet, Scenario(D, EPS, B, rows[k]))
        assert_plans_equal(cell, single)


# --------------------------------------------------------- Cantelli rows


def test_cantelli_reduces_to_mean_row_at_zero_variance(fleet, slack_occ):
    """σ_vm = 0 ⇒ the chance-constrained occupancy row IS the mean row —
    every Allocation leaf identical."""
    chain0 = fleet.chain._replace(v_vm=jnp.zeros_like(fleet.chain.v_vm))
    fleet0 = fleet._replace(chain=chain0)
    m = jnp.full((N,), 4, jnp.int32)
    caps = jnp.asarray([0.6, 0.4, 0.3]) * slack_occ
    a = assign_devices(select_point(fleet0, m).t_vm, caps, "hybrid")
    mean = allocate(fleet0, m, D, EPS, B, edge_capacity_s=caps, assignment=a)
    cc = allocate(fleet0, m, D, EPS, B, edge_capacity_s=caps, assignment=a,
                  edge_eps=0.1)
    assert_plans_equal(mean, cc)


def test_cantelli_row_tightens_with_variance(fleet):
    """With real VM variance the Cantelli row charges σ_e·√(Σ v_vm) extra:
    a capacity between the mean and the chance-constrained occupancy is
    feasible under the mean row and rejected under ε_edge."""
    m = jnp.full((N,), 4, jnp.int32)
    sel = select_point(fleet, m)
    occ, var = float(sel.t_vm.sum()), float(sel.v_vm.sum())
    assert var > 0.0
    sig = placement.edge_sigma(0.05)
    cap = occ + 0.5 * sig * np.sqrt(var)  # between mean and Cantelli
    mean = allocate(fleet, m, D, EPS, B, edge_capacity_s=cap)
    cc = allocate(fleet, m, D, EPS, B, edge_capacity_s=cap, edge_eps=0.05)
    assert bool(np.asarray(mean.feasible).all())
    assert not bool(np.asarray(cc.feasible).any())


def test_edge_sigma_validation():
    assert placement.edge_sigma(None) == 0.0
    np.testing.assert_allclose(placement.edge_sigma(0.5), 1.0)
    with pytest.raises(ValueError, match="edge_eps"):
        placement.edge_sigma(1.5)
    with pytest.raises(ValueError, match="edge_eps"):
        PlannerConfig(edge_eps=0.0)

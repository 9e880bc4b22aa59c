"""Multi-edge placement: device→node assignment + price certificates.

DESIGN.md §placement. The shared edge is E heterogeneous nodes with a
per-round VM-time capacity vector C ∈ R^E (``Scenario.edge_capacity_s``
as an ``(E,)`` array); each device must be *placed* on exactly one node,
``a ∈ {0..E−1}^N``. The assignment is the discrete half of a
transport-style subproblem: the continuous half (per-node clearing
prices μ_e, bisected inside the planner's dual loop) certifies and
refines it — ``duality_gap`` reports the certificate.

The assignment strategies are the AccaSim-style allocator family
(Balanced / Weighted / Hybrid, plus round-robin and greedy-load
baselines), registered in ``ASSIGN_FNS`` and selected per policy via
``Policy.assign``. All strategies are **traced** (``lax.scan`` over the
devices, one argmin over the E nodes per step) so they live inside the
planner's compiled program, and each has a numpy **host mirror**
(``assign_devices_host``) with the *identical* float64 op order, so the
group-sharded host loop of ``core.decompose`` replays the same
assignments bit-for-bit (the same contract ``_host_bisect`` keeps with
``solvers.scalar.bisect``). Decision arithmetic deliberately avoids
cross-node sum reductions (order-ambiguous between numpy and XLA);
``max``/elementwise ops only.

Capacity conventions: ∞ ⇒ uncapacitated node; **0 ⇒ absent node** — no
strategy ever places on a zero-capacity node, which is what lets
"3 nodes vs 2" be value-varied (not shape-varied) axes of one compiled
``Planner.grid`` sweep.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ASSIGN_FNS", "assign_devices", "assign_devices_host",
    "available_assignments", "assignment_churn", "migration_energy",
    "node_loads", "duality_gap", "plan_duality_gap", "edge_sigma",
]

#: Stand-in capacity for uncapacitated (∞) nodes inside utilization
#: arithmetic — dominates any real occupancy while keeping ratios finite
#: and ordered.
_CAP_BIG = 1e9
#: Denominator floor: a zero-capacity (absent) node gets utilization
#: ~1e30 per occupancy second, so it is never chosen while any present
#: node exists.
_CAP_TINY = 1e-30
#: Additive penalty for placing a device on a node it does not fit on —
#: larger than any scarcity-weighted load of a fitting node.
_OVERFLOW = 1e12


def edge_sigma(edge_eps) -> float:
    """Cantelli multiplier √((1−ε)/ε) of the chance-constrained occupancy
    row P{Σ t_vm > C_e} ≤ ε_edge (the paper's own CCP treatment applied
    to the shared resource): mean occupancy is charged an extra
    σ_edge·√(Σ v_vm). ``edge_eps`` is a *static* float (or ``None`` ⇒ the
    mean-only row, multiplier 0.0 — the returned value gates the extra
    term out of the trace entirely)."""
    if edge_eps is None:
        return 0.0
    eps = float(edge_eps)
    if not 0.0 < eps < 1.0:
        raise ValueError(f"edge_eps must lie in (0, 1), got {edge_eps!r}")
    return math.sqrt((1.0 - eps) / eps)


def _caps_eff(caps):
    return jnp.where(jnp.isfinite(caps), caps, _CAP_BIG)


def _assign_round_robin(occ, caps):
    """a_n = n mod E over *present* nodes — the interleaving baseline
    (load- and capacity-magnitude-blind, but it never lands a device on
    an absent C_e = 0 node, so node-count what-ifs stay meaningful)."""
    present = caps > 0.0
    count = jnp.maximum(jnp.sum(present.astype(jnp.int32)), 1)
    # present node ids first, in ascending order (stable argsort on ~present)
    order = jnp.argsort(jnp.logical_not(present), stable=True)
    r = jnp.arange(occ.shape[0], dtype=jnp.int32) % count
    return order[r].astype(jnp.int32)


def _assign_greedy_load(occ, caps):
    """Devices in natural order onto the least-loaded node (absolute
    load, capacity-blind apart from skipping absent nodes)."""
    e_count = caps.shape[0]

    def step(load, n):
        score = jnp.where(caps > 0.0, load, jnp.inf)
        e = jnp.argmin(score)
        return load.at[e].add(occ[n]), e.astype(jnp.int32)

    _, a = jax.lax.scan(step, jnp.zeros((e_count,), jnp.float64),
                        jnp.arange(occ.shape[0]))
    return a


def _balanced_scan(occ, caps):
    """Balanced core: heaviest devices first, each onto the node with the
    lowest *post-placement utilization* (load+occ)/C_e. Returns the
    assignment AND the final per-node loads (accumulated in processing
    order — the host mirror replays the identical add sequence)."""
    e_count = caps.shape[0]
    denom = jnp.maximum(_caps_eff(caps), _CAP_TINY)
    order = jnp.argsort(-occ, stable=True)

    def step(load, n):
        util = (load + occ[n]) / denom
        util = jnp.where(caps > 0.0, util, jnp.inf)
        e = jnp.argmin(util)
        return load.at[e].add(occ[n]), e.astype(jnp.int32)

    load, es = jax.lax.scan(step, jnp.zeros((e_count,), jnp.float64), order)
    a = jnp.zeros(occ.shape, jnp.int32).at[order].set(es)
    return a, load


def _assign_balanced(occ, caps):
    return _balanced_scan(occ, caps)[0]


def _assign_weighted(occ, caps):
    """Heaviest first onto the node minimizing the *scarcity-weighted*
    post-load w_e·(load+occ) + load/C_e, w_e = C_max/C_e: scarce nodes
    cost proportionally more, so abundant nodes fill first and scarce
    accelerators are not fragmented by bulk load. Devices that do not
    fit anywhere land on the least-overflowed node."""
    e_count = caps.shape[0]
    ceff = _caps_eff(caps)
    denom = jnp.maximum(ceff, _CAP_TINY)
    w = jnp.max(ceff) / denom  # max, not mean: order-exact on host + device
    order = jnp.argsort(-occ, stable=True)

    def step(load, n):
        post = load + occ[n]
        fits = post <= ceff
        score = jnp.where(fits, w * post + load / denom, _OVERFLOW + w * post)
        score = jnp.where(caps > 0.0, score, jnp.inf)
        e = jnp.argmin(score)
        return load.at[e].add(occ[n]), e.astype(jnp.int32)

    _, es = jax.lax.scan(step, jnp.zeros((e_count,), jnp.float64), order)
    return jnp.zeros(occ.shape, jnp.int32).at[order].set(es)


def _assign_hybrid(occ, caps):
    """Balanced placement + a migration pass off the scarcest node: its
    devices (lightest first) move to the best-fitting other node while
    the move still fits. Migration only ever *removes* load from the
    scarcest node, so Hybrid fragments it no worse than Balanced — by
    construction, for every input (the hypothesis-tested invariant)."""
    a, load = _balanced_scan(occ, caps)
    e_count = caps.shape[0]
    if e_count == 1:
        return a
    ceff = jnp.maximum(_caps_eff(caps), _CAP_TINY)
    # scarcest *present* node class (absent C=0 nodes hold no load)
    e_star = jnp.argmin(jnp.where(caps > 0.0, ceff, jnp.inf)).astype(jnp.int32)
    node_ids = jnp.arange(e_count)
    order = jnp.argsort(occ, stable=True)  # cheapest-to-move first

    def step(carry, n):
        a_arr, load = carry
        on_star = a_arr[n] == e_star
        util = (load + occ[n]) / ceff
        util = jnp.where((node_ids == e_star) | (caps <= 0.0), jnp.inf, util)
        tgt = jnp.argmin(util).astype(jnp.int32)
        move = on_star & (load[tgt] + occ[n] <= ceff[tgt])
        delta = jnp.where(move, occ[n], 0.0)
        load = load.at[e_star].add(-delta).at[tgt].add(delta)
        a_arr = a_arr.at[n].set(jnp.where(move, tgt, a_arr[n]))
        return (a_arr, load), None

    (a, _), _ = jax.lax.scan(step, (a, load), order)
    return a


#: Strategy registry: name → traced ``(occ (N,), caps (E,)) → (N,) int32``.
ASSIGN_FNS = {
    "round_robin": _assign_round_robin,
    "greedy_load": _assign_greedy_load,
    "balanced": _assign_balanced,
    "weighted": _assign_weighted,
    "hybrid": _assign_hybrid,
}


def available_assignments() -> tuple[str, ...]:
    return tuple(ASSIGN_FNS)


def assign_devices(occ, caps, strategy: str = "hybrid") -> jnp.ndarray:
    """Assign every device to exactly one edge node (traced).

    ``occ`` is the per-device edge occupancy at the current partition
    (t̄_vm at the selected point, ``(N,)``), ``caps`` the per-node
    capacity vector ``(E,)`` (∞ ⇒ uncapacitated, 0 ⇒ absent node);
    ``strategy`` is a static key into :data:`ASSIGN_FNS`.
    """
    try:
        fn = ASSIGN_FNS[strategy]
    except KeyError:
        raise ValueError(
            f"unknown assignment strategy {strategy!r}; registered: "
            f"{available_assignments()}") from None
    occ = jnp.asarray(occ, jnp.float64)
    caps = jnp.asarray(caps, jnp.float64)
    if caps.ndim != 1:
        raise ValueError(
            f"assign_devices needs an (E,) capacity vector, got shape {caps.shape}")
    return fn(occ, caps)


# ---------------------------------------------------------------------------
# Host mirrors (numpy, identical float64 op order) — for core.decompose's
# host-level price loop. Pinned bit-identical to the traced strategies in
# tests/test_placement.py.
# ---------------------------------------------------------------------------


def _host_caps_eff(caps):
    return np.where(np.isfinite(caps), caps, _CAP_BIG)


def _host_greedy_load(occ, caps):
    load = np.zeros(caps.shape[0])
    a = np.zeros(occ.shape[0], np.int32)
    for n in range(occ.shape[0]):
        score = np.where(caps > 0.0, load, np.inf)
        e = int(np.argmin(score))
        load[e] += occ[n]
        a[n] = e
    return a


def _host_balanced_scan(occ, caps):
    denom = np.maximum(_host_caps_eff(caps), _CAP_TINY)
    order = np.argsort(-occ, kind="stable")
    load = np.zeros(caps.shape[0])
    a = np.zeros(occ.shape[0], np.int32)
    for n in order:
        util = (load + occ[n]) / denom
        util = np.where(caps > 0.0, util, np.inf)
        e = int(np.argmin(util))
        load[e] += occ[n]
        a[n] = e
    return a, load


def _host_weighted(occ, caps):
    ceff = _host_caps_eff(caps)
    denom = np.maximum(ceff, _CAP_TINY)
    w = np.max(ceff) / denom
    order = np.argsort(-occ, kind="stable")
    load = np.zeros(caps.shape[0])
    a = np.zeros(occ.shape[0], np.int32)
    for n in order:
        post = load + occ[n]
        fits = post <= ceff
        score = np.where(fits, w * post + load / denom, _OVERFLOW + w * post)
        score = np.where(caps > 0.0, score, np.inf)
        e = int(np.argmin(score))
        load[e] += occ[n]
        a[n] = e
    return a


def _host_hybrid(occ, caps):
    a, load = _host_balanced_scan(occ, caps)
    e_count = caps.shape[0]
    if e_count == 1:
        return a
    ceff = np.maximum(_host_caps_eff(caps), _CAP_TINY)
    e_star = int(np.argmin(np.where(caps > 0.0, ceff, np.inf)))
    node_ids = np.arange(e_count)
    order = np.argsort(occ, kind="stable")
    for n in order:
        if a[n] != e_star:
            continue
        util = (load + occ[n]) / ceff
        util = np.where((node_ids == e_star) | (caps <= 0.0), np.inf, util)
        tgt = int(np.argmin(util))
        if load[tgt] + occ[n] <= ceff[tgt]:
            load[e_star] -= occ[n]
            load[tgt] += occ[n]
            a[n] = tgt
    return a


def _host_round_robin(occ, caps):
    present = caps > 0.0
    count = max(int(np.sum(present)), 1)
    order = np.argsort(~present, kind="stable")
    return order[np.arange(occ.shape[0]) % count].astype(np.int32)


_HOST_ASSIGN_FNS = {
    "round_robin": _host_round_robin,
    "greedy_load": _host_greedy_load,
    "balanced": lambda occ, caps: _host_balanced_scan(occ, caps)[0],
    "weighted": _host_weighted,
    "hybrid": _host_hybrid,
}


def assign_devices_host(occ, caps, strategy: str = "hybrid") -> np.ndarray:
    """Numpy mirror of :func:`assign_devices` — same strategies, identical
    float64 op order, bit-identical assignments (pinned in tests)."""
    try:
        fn = _HOST_ASSIGN_FNS[strategy]
    except KeyError:
        raise ValueError(
            f"unknown assignment strategy {strategy!r}; registered: "
            f"{available_assignments()}") from None
    occ = np.asarray(occ, np.float64)  # analyze: ok(TRC002): deliberate host mirror — decompose's host price loop runs on materialized lanes
    caps = np.asarray(caps, np.float64)  # analyze: ok(TRC002): deliberate host mirror — decompose's host price loop runs on materialized lanes
    if caps.ndim != 1:
        raise ValueError(
            f"assign_devices_host needs an (E,) capacity vector, got shape {caps.shape}")
    # XLA reads subnormal inputs as zero, so the traced strategies see a
    # subnormal capacity as an absent node; the mirror must too.
    tiny = np.finfo(np.float64).tiny
    caps = np.where(np.abs(caps) < tiny, 0.0, caps)
    occ = np.where(np.abs(occ) < tiny, 0.0, occ)
    return fn(occ, caps)


def node_loads(occ, assignment, num_nodes: int):
    """Per-node total occupancy Σ_{n: a_n=e} occ_n (traced)."""
    return jax.ops.segment_sum(jnp.asarray(occ, jnp.float64),
                               jnp.asarray(assignment, jnp.int32),
                               num_segments=num_nodes)


# ---------------------------------------------------------------------------
# Migration accounting (workload replay: DESIGN.md §robustness)
# ---------------------------------------------------------------------------


def assignment_churn(a_old, a_new) -> jnp.ndarray:
    """Number of devices whose node changed between two assignments
    (traced, int32 scalar). The replay's ladder charges each such move —
    a migrated device's session state must be re-established on the new
    node before it serves again."""
    a_old = jnp.asarray(a_old, jnp.int32)
    a_new = jnp.asarray(a_new, jnp.int32)
    if a_old.shape != a_new.shape:
        raise ValueError(
            f"assignment shapes differ: {a_old.shape} vs {a_new.shape}")
    return jnp.sum((a_old != a_new).astype(jnp.int32))


def migration_energy(a_old, a_new, e_migrate) -> jnp.ndarray:
    """Total energy of a re-plan's migrations: Σ over moved devices of
    ``e_migrate[n]`` (traced, float64 scalar).

    ``e_migrate`` is the per-device cost of re-establishing its session
    on a new node — the replay uses one extra upload of the offload
    payload, t_off·p_tx at the incumbent partition, so a device with a
    bigger activation payload or a worse channel is costlier to move."""
    a_old = jnp.asarray(a_old, jnp.int32)
    a_new = jnp.asarray(a_new, jnp.int32)
    cost = jnp.asarray(e_migrate, jnp.float64)
    return jnp.sum(jnp.where(a_old != a_new, cost, 0.0))


# ---------------------------------------------------------------------------
# Duality-gap certificate
# ---------------------------------------------------------------------------


def duality_gap(e_table, occ_table, feas, m_sel, mu, caps):
    """Certificate gap between the returned discrete plan and the
    per-node-price dual lower bound.

    The Lagrangian relaxation lets every device pick *any* node, so each
    pays the cheapest price μ_min = min_e μ_e; the dual value at the
    returned prices is

        g(μ) = Σ_n min_{m feasible} (e_nm + μ_min·occ_nm) − Σ_e μ_e·C_e

    and ``gap = primal − g(μ) ≥ 0`` bounds the discrete assignment's
    suboptimality (0 ⇔ the heuristic placement is price-certified
    optimal). Devices with no feasible point contribute their selected
    point to both sides (they price out identically).
    """
    e_table = jnp.asarray(e_table, jnp.float64)
    occ_table = jnp.asarray(occ_table, jnp.float64)
    m_sel = jnp.asarray(m_sel, jnp.int32)
    mu = jnp.atleast_1d(jnp.asarray(mu, jnp.float64))
    caps = jnp.atleast_1d(jnp.asarray(caps, jnp.float64))
    take = lambda a: jnp.take_along_axis(a, m_sel[:, None], -1)[:, 0]
    e_sel, occ_sel = take(e_table), take(occ_table)
    primal = jnp.sum(e_sel)
    mu_min = jnp.min(mu)
    priced = jnp.where(feas, e_table + mu_min * occ_table, jnp.inf)
    best = jnp.min(priced, axis=-1)
    any_feas = jnp.any(feas, axis=-1)
    dev_dual = jnp.where(any_feas, best, e_sel + mu_min * occ_sel)
    # μ_e·C_e with C_e = ∞ only ever pairs with μ_e = 0 (an uncapacitated
    # node never needs a price) — gate the 0·∞ = NaN out explicitly.
    pay = jnp.sum(jnp.where(mu > 0.0, mu * caps, 0.0))
    return primal - (jnp.sum(dev_dual) - pay)


def plan_duality_gap(fleet, plan, deadline, eps, caps, policy="robust_exact",
                     channel_cv: float = 0.0):
    """Duality gap of a returned :class:`~repro.core.planner.Plan` —
    rebuilds the priced point tables at the plan's allocation and scores
    :func:`duality_gap` at the plan's recorded prices ``alloc.mu``."""
    from repro.core import ccp  # deferred: placement must not import planner at module load
    from repro.core.planner import _edge_occ_prep, get_policy, policy_point_tables

    pol = get_policy(policy)
    n = fleet.num_devices
    deadline = jnp.broadcast_to(jnp.asarray(deadline, jnp.float64), (n,))
    eps = jnp.broadcast_to(jnp.asarray(eps, jnp.float64), (n,))
    sigma = ccp.SIGMA_FNS[pol.sigma_model](eps)
    e_table, t_table, var_table = policy_point_tables(
        fleet, plan.alloc.b, plan.alloc.f, pol, channel_cv)
    feas, _, _ = _edge_occ_prep(t_table, var_table, sigma, deadline)
    return duality_gap(e_table, fleet.chain.t_vm, feas, plan.m_sel,
                       plan.alloc.mu, caps)

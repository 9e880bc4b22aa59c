"""Algorithm 2 — alternating robust partitioning + resource allocation.

Policies are **strategy records** in a registry (``Policy`` /
``register_policy``), not string if-chains: each policy bundles its
ambiguity-set σ model, its worst-case time inflation, its partition step
(PCCP vs exact enumeration), and — for baselines that bypass the
alternation entirely, like ``"optimal"`` — a full-plan ``solve`` override.
``_alternation`` dispatches through the record, so a new policy is a
``register_policy`` call, not an edit to the solver. Built-ins:

- ``"robust"``      — the paper: CCP margins (Cantelli σ) + PCCP partitioning.
- ``"robust_exact"``— beyond-paper: CCP margins + *exact per-device
                      enumeration* of the partition point (the decoupling
                      observation in DESIGN.md §2); certifies PCCP.
- ``"gaussian"``    — beyond-paper: Gaussian quantile σ instead of Cantelli
                      (tighter margins when times are near-normal).
- ``"worst_case"``  — §VI baseline: upper-bound times (mean + 3σ), no
                      probabilistic slack (hard deadline).
- ``"optimal"``     — §VI baseline: joint exhaustive search implemented as
                      price-based exact enumeration over (m, b, f)
                      (optimal because the problem decouples at a fixed
                      bandwidth price; see DESIGN.md). Registered with a
                      ``solve`` override, so it batch-dispatches through
                      ``api.Planner.plan_many``/``grid`` like any policy.

The whole planner is ONE compiled XLA program (DESIGN.md §planner): the
outer Algorithm-2 alternation is a ``lax.scan``, the multi-start spread is
a ``vmap`` over initial partition points with a traced
feasibility-then-energy argmin, and all scenario parameters
(deadline, ε, B) are traced — so repeated calls on same-shaped fleets hit
the jit cache, and ``core.api.Planner.plan_many`` can vmap whole zipped
scenario batches over the same trace.

``plan`` below is the deprecated-but-working functional wrapper; new code
should use ``repro.core.api`` (``Scenario`` / ``PlannerConfig`` /
``Planner``).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ccp, channel, energy, placement
from repro.core.blocks import Fleet
from repro.core.pccp import pccp_partition
from repro.core.resource import (
    _EDGE_CAP_RTOL,
    _LOG_PRICE_HI0,
    Allocation,
    _device_best_b_at,
    _device_invariants,
    allocate,
    allocate_with_bracket,
    price_search,
    select_point,
)


#: ``Plan.status`` codes (DESIGN.md §robustness — the solver fail-soft
#: contract). The traced solve stamps OK/DEGRADED; the host-side ladder
#: in ``api.Planner.plan`` overwrites with the fallback codes when it
#: had to re-solve or reuse the incumbent.
PLAN_OK = 0  # healthy solve
PLAN_DEGRADED = 1  # non-finite leaves detected at solve time
PLAN_FALLBACK_DENSE = 2  # re-solved with the dense inner barrier
PLAN_FALLBACK_INCUMBENT = 3  # caller's incumbent plan returned instead

PLAN_STATUS_NAMES = {
    PLAN_OK: "ok",
    PLAN_DEGRADED: "degraded",
    PLAN_FALLBACK_DENSE: "fallback_dense",
    PLAN_FALLBACK_INCUMBENT: "fallback_incumbent",
}


class Plan(NamedTuple):
    m_sel: jnp.ndarray  # (N,) partition points
    alloc: Allocation  # bandwidth / frequency allocation
    total_energy: jnp.ndarray  # scalar objective (9a)
    feasible: jnp.ndarray  # (N,) chance/hard constraint satisfied
    objective_trace: jnp.ndarray  # (outer_iters,) Algorithm-2 trajectory (Fig. 10)
    pccp_iters: jnp.ndarray  # (outer_iters, N) Algorithm-1 iterations (Fig. 9)
    margins: jnp.ndarray  # (N,) deadline margin (≤0 ⇒ guaranteed)
    status: jnp.ndarray = jnp.int32(PLAN_OK)  # scalar PLAN_* code  # analyze: ok(TRC005): tiny scalar NamedTuple default; a concrete int32 stamp is the contract
    #: device→edge-node map a ∈ {0..E−1}^N (DESIGN.md §placement). All
    #: zeros on the scalar-capacity path (one shared edge ⇒ node 0).
    assignment: jnp.ndarray = jnp.int32(0)  # analyze: ok(TRC005): tiny scalar NamedTuple default; traced solves stamp the (N,) map


# ---------------------------------------------------------------------------
# Policy strategy registry
# ---------------------------------------------------------------------------

#: Worst-case baseline upper bound: mean + UB_K·std. Fig. 1/5 show
#: heavy-tailed outliers (spikes ≫ mean); the empirical max of the paper's
#: 500-sample campaigns corresponds to ≈ mean + 8·std for such tails.
WORST_CASE_UB_K = 8.0

#: Masking constants for ragged fleets (DESIGN.md §fleet): padded points
#: get this energy/time in the per-point tables, so no argmin — feasible,
#: least-bad, or PCCP-rounded — can ever select them (real times are
#: ≪ 1e6 s, real energies ≪ 1e6 J), while staying finite so the PCCP
#: inner barrier stays well-conditioned (∞ would poison its residuals).
MASK_ENERGY_J = 1e6
MASK_TIME_S = 1e6

#: One-sided safety factor on a discovered edge clearing price. The
#: occupancy excess is a step function of μ; the bisection's upper
#: endpoint sits within ~1 ulp of a jump, where re-evaluating the priced
#: argmin across an XLA fusion boundary can round to the *other* side of
#: the threshold. Over-pricing by 1e-9 relative is decisively past the
#: jump and is the safe direction (occupancy only shrinks as μ grows).
_MU_SAFETY = 1.0 + 1e-9


@dataclass(frozen=True)
class Policy:
    """Strategy record for one planning policy.

    Instances are hashable statics: they ride through ``jax.jit`` as
    ``static_argnames`` entries, and the registry hands out singletons so
    repeated lookups hit the same jit-cache key.

    ``partition`` runs inside the Algorithm-2 alternation with signature
    ``(m, e_table, t_table, var_table, sigma, deadline, pccp_iters,
    solver, gated) -> (m_new, feasible, iters)`` — for edge-aware policies
    the energy table arrives already μ-priced (``e + μ·t̄_vm``); ``solver``
    / ``gated`` are the inner-barrier statics of DESIGN.md §solver
    (partition steps that do not run the PCCP ignore them). ``solve``,
    when set,
    replaces the whole alternation (signature ``(fleet, deadline, eps, B,
    edge_cap, policy, outer_iters, pccp_iters, channel_cv, edge_eps)
    -> Plan``) — used by ``"optimal"``.
    """

    name: str
    sigma_model: str = "cantelli"  # key into ccp.SIGMA_FNS
    ub_k: float = 0.0  # worst-case time inflation (mean + ub_k·std)
    partition: Optional[Callable] = None
    solve: Optional[Callable] = None
    #: charge the shared-edge clearing price μ·t̄_vm on every candidate
    #: point of the partition subproblem (DESIGN.md §edge). With an
    #: infinite edge capacity μ = 0 and this is a numerical no-op; set
    #: False to register a policy that ignores edge contention when
    #: partitioning (the capacity check still gates feasibility).
    edge_aware: bool = True
    #: device→node assignment strategy under a per-node capacity vector
    #: (key into ``placement.ASSIGN_FNS``; DESIGN.md §placement). Ignored
    #: on the scalar-capacity path.
    assign: str = "hybrid"

    def __post_init__(self):
        if self.sigma_model not in ccp.SIGMA_FNS:
            raise ValueError(
                f"sigma_model must be one of {tuple(ccp.SIGMA_FNS)}, "
                f"got {self.sigma_model!r}")
        if self.partition is None and self.solve is None:
            raise ValueError("a Policy needs a partition step or a solve override")
        if self.assign not in placement.ASSIGN_FNS:
            raise ValueError(
                f"assign must be one of {placement.available_assignments()}, "
                f"got {self.assign!r}")


_REGISTRY: dict[str, Policy] = {}


def register_policy(policy: Policy, *, overwrite: bool = False) -> Policy:
    """Add ``policy`` to the registry (returns it, for assignment)."""
    if policy.name in _REGISTRY and not overwrite:
        raise ValueError(f"policy {policy.name!r} already registered "
                         "(pass overwrite=True to replace)")
    _REGISTRY[policy.name] = policy
    return policy


def get_policy(policy) -> Policy:
    """Resolve a policy name (or pass through a ``Policy`` instance)."""
    if isinstance(policy, Policy):
        return policy
    try:
        return _REGISTRY[policy]
    except KeyError:
        raise ValueError(
            f"unknown policy {policy!r}; registered: {available_policies()}"
        ) from None


def available_policies() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def _point_tables(fleet: Fleet, b, f, channel_cv: float = 0.0):
    """Per-(device, point) energy/time/variance tables at fixed ``(b, f)``
    (the per-device allocation vectors — pass ``alloc.b, alloc.f``).

    For ragged fleets the padded points are masked here — the one place
    every partition step (exact enumeration AND the PCCP barrier) reads
    its tables from — with finite sentinel energy/time and zero variance,
    so downstream selections can never land on padding. An all-valid mask
    is a numerical no-op (pure selects).
    """
    c, plat, link = fleet.chain, fleet.platform, fleet.link
    f = f[:, None]
    b = b[:, None]
    e_loc = energy.expected_local_energy(plat.kappa[:, None], c.w_flops, c.g_eff, f)
    t_loc = energy.mean_local_time(c.w_flops, c.g_eff, f)
    t_off = channel.offload_time(c.d_bits, b, link.p_tx[:, None], link.gain[:, None])
    e_off = link.p_tx[:, None] * t_off
    e_table = e_loc + e_off
    t_table = t_loc + t_off + c.t_vm
    var_table = c.v_loc + c.v_vm
    if channel_cv > 0.0:  # joint channel robustness (paper footnote 2)
        std_off = channel.offload_time_std(
            c.d_bits, b, link.p_tx[:, None], link.gain[:, None], channel_cv)
        var_table = var_table + std_off**2
    if fleet.valid is not None:  # ragged fleet: mask padded points
        e_table = jnp.where(fleet.valid, e_table, MASK_ENERGY_J)
        t_table = jnp.where(fleet.valid, t_table, MASK_TIME_S)
        var_table = jnp.where(fleet.valid, var_table, 0.0)
    return e_table, t_table, var_table


def policy_point_tables(fleet: Fleet, b, f, policy: Policy,
                        channel_cv: float = 0.0):
    """``_point_tables`` with the policy's worst-case time inflation
    applied (mean + ub_k·std, variance dropped — §VI baseline). The ONE
    implementation of the policy-conditioned tables: the alternation, the
    group-sharded decomposition, the straight-line reference port and the
    phase-breakdown bench all read their partition subproblem from here,
    so they cannot drift apart. Takes the raw ``(b, f)`` vectors (not an
    ``Allocation``) so per-group programs can call it on sliced batches.
    """
    e_table, t_table, var_table = _point_tables(fleet, b, f, channel_cv)
    if policy.ub_k > 0.0:  # worst-case inflation: mean + ub_k·std, no variance
        t_table = t_table + policy.ub_k * (
            jnp.sqrt(jnp.maximum(fleet.chain.v_loc, 0.0))
            + jnp.sqrt(jnp.maximum(fleet.chain.v_vm, 0.0))
        )
        var_table = jnp.zeros_like(var_table)
    return e_table, t_table, var_table


@jax.jit
def objective_trace(fleet: Fleet, m, b, f):
    """``Plan.objective_trace`` from the stacked per-step ``(T, N)`` new
    partition points ``m`` and the allocation ``(b, f)`` they were chosen
    at: Σ_n expected device energy per step (true energy, not the
    μ-priced surrogate). The fused planner and ``planner_ref`` both build
    their trace with this one compiled function, so the two round alike
    (XLA may fuse a product into an FMA, which an op-by-op evaluation of
    the same formula does not)."""
    def one(m, b, f):
        sel = select_point(fleet, m)
        e_loc = energy.expected_local_energy(fleet.platform.kappa,
                                             sel.w_flops, sel.g_eff, f)
        e_off = channel.offload_energy(sel.d_bits, b, fleet.link.p_tx,
                                       fleet.link.gain)
        return jnp.sum(e_loc + e_off)

    return jax.vmap(one)(m, b, f)


def _traced_status(alloc: Allocation, total_energy, margins) -> jnp.ndarray:
    """OK/DEGRADED stamp computed inside the trace (no host syncs): a
    healthy plan has finite allocation, energy and margins. Transient
    NaNs inside rejected line-search candidates are fine — this checks
    the *outputs* the caller is about to act on."""
    healthy = (jnp.all(jnp.isfinite(alloc.b)) & jnp.all(jnp.isfinite(alloc.f))
               & jnp.isfinite(total_energy) & jnp.all(jnp.isfinite(margins)))
    return jnp.where(healthy, PLAN_OK, PLAN_DEGRADED).astype(jnp.int32)


def _exact_partition(e_table, t_table, var_table, sigma, deadline):
    """Exact per-device enumeration under the ECR constraint (28)."""
    margin = t_table + sigma[:, None] * jnp.sqrt(jnp.maximum(var_table, 0.0)) - deadline[:, None]
    # Tolerance: allocate() drives f to meet the deadline *exactly*, so the
    # incumbent point sits at margin ≈ +ulp; treat it as feasible.
    feas = margin <= 1e-9
    e_masked = jnp.where(feas, e_table, jnp.inf)
    m_feas = jnp.argmin(e_masked, axis=-1)
    any_feas = jnp.any(feas, axis=-1)
    m_least_bad = jnp.argmin(margin, axis=-1)
    m_sel = jnp.where(any_feas, m_feas, m_least_bad).astype(jnp.int32)
    return m_sel, jnp.take_along_axis(feas, m_sel[:, None], -1)[:, 0]


def _clearing_price(occ_at, edge_cap, prior_log_hi=None):
    """Smallest price μ ≥ 0 with ``occ_at(μ) ≤ edge_cap``; returns
    ``(μ, log_hi)`` where ``log_hi`` is the expanded bracket top (for
    warm-starting the next clearing — value-identical, see
    ``resource.price_search``).

    ``occ_at`` must be a non-increasing step function of μ (a priced
    argmin's selected occupancy). The search is a log-space bisection
    with the adaptively expanded bracket of ``resource``; the *upper*
    bracket endpoint ×``_MU_SAFETY`` is returned so the discovered price
    sits on the feasible side of the step. Complementary slackness:
    μ = 0 when the unpriced selection already fits.
    """
    _, log_mu, need, log_hi = price_search(
        occ_at, lambda occ: occ, edge_cap, hi_start=prior_log_hi,
        endpoint="hi", final=False)
    return jnp.where(need, 10.0**log_mu * _MU_SAFETY, 0.0), log_hi


def _edge_occ_prep(t_table, var_table, sigma, deadline):
    """μ-invariant pieces of the priced partition argmin: per-point
    feasibility, any-feasible flags, least-bad fallback points. Split out
    so the group-sharded path can hoist them out of the μ bisection."""
    margin = (t_table + sigma[:, None] * jnp.sqrt(jnp.maximum(var_table, 0.0))
              - deadline[:, None])
    feas = margin <= 1e-9
    any_feas = jnp.any(feas, axis=-1)
    m_least_bad = jnp.argmin(margin, axis=-1)
    return feas, any_feas, m_least_bad


def _edge_clearing_price(e_table, t_table, var_table, sigma, deadline,
                         occ_table, edge_cap, prior_log_hi=None,
                         occ_var=None, edge_sigma: float = 0.0):
    """Market-clearing price μ of the shared-edge capacity at fixed (b, f)
    — returns ``(μ, log_hi)`` like ``_clearing_price``.

    The partition subproblem decouples per device at a given μ (each
    device argmins its priced table ``e + μ·occ`` over feasible points),
    so the fleet's total occupancy Σ occ(m*(μ)) is a non-increasing step
    function of μ — priced by ``_clearing_price`` over the *tables*
    (no golden sections: ~60 cheap argmins).

    ``edge_sigma`` > 0 (static — from ``placement.edge_sigma(edge_eps)``)
    clears against the Cantelli chance-constrained occupancy
    Σ occ + σ_e·√(Σ var) instead of the mean (``occ_var`` is the per-point
    VM variance table); at 0.0 the trace is untouched.
    """
    feas, any_feas, m_least_bad = _edge_occ_prep(t_table, var_table, sigma,
                                                 deadline)

    def occ_at(mu):
        cost = jnp.where(feas, e_table + mu * occ_table, jnp.inf)
        m = jnp.where(any_feas, jnp.argmin(cost, axis=-1), m_least_bad)
        occ = jnp.sum(jnp.take_along_axis(occ_table, m[:, None], -1)[:, 0])
        if edge_sigma > 0.0:
            var = jnp.sum(jnp.take_along_axis(occ_var, m[:, None], -1)[:, 0])
            occ = occ + edge_sigma * jnp.sqrt(jnp.maximum(var, 0.0))
        return occ

    return _clearing_price(occ_at, edge_cap, prior_log_hi=prior_log_hi)


def _node_clearing_prices(e_table, t_table, var_table, sigma, deadline,
                          occ_table, assignment, caps, prior_log_hi=None,
                          occ_var=None, edge_sigma: float = 0.0):
    """Per-node clearing prices μ ∈ R^E at a fixed assignment — the
    transport subproblem's continuous half (DESIGN.md §placement).

    Each node clears independently: all devices argmin their table priced
    at the node's trial μ, and only the occupancy of the devices *assigned
    to that node* is summed against its capacity C_e — the same
    ``_clearing_price`` log-space bracket arithmetic as the scalar edge,
    vmapped over nodes (so ``plan_sharded``'s host loop can replay each
    node's bisection IEEE-identically). Returns ``(μ_vec, log_hi_vec)``,
    both ``(E,)``; ``prior_log_hi`` warm-starts per node.
    """
    feas, any_feas, m_least_bad = _edge_occ_prep(t_table, var_table, sigma,
                                                 deadline)
    e_count = caps.shape[0]
    masks = assignment[None, :] == jnp.arange(e_count)[:, None]  # (E, N)

    def occ_at_node(mask, mu):
        cost = jnp.where(feas, e_table + mu * occ_table, jnp.inf)
        m = jnp.where(any_feas, jnp.argmin(cost, axis=-1), m_least_bad)
        occ_sel = jnp.take_along_axis(occ_table, m[:, None], -1)[:, 0]
        occ = jnp.sum(jnp.where(mask, occ_sel, 0.0))
        if edge_sigma > 0.0:
            var_sel = jnp.take_along_axis(occ_var, m[:, None], -1)[:, 0]
            occ = occ + edge_sigma * jnp.sqrt(jnp.maximum(
                jnp.sum(jnp.where(mask, var_sel, 0.0)), 0.0))
        return occ

    def one(mask, cap, hi):
        return _clearing_price(lambda mu: occ_at_node(mask, mu), cap,
                               prior_log_hi=hi)

    if prior_log_hi is None:
        prior_log_hi = jnp.full((e_count,), _LOG_PRICE_HI0, jnp.float64)
    return jax.vmap(one)(masks, caps, prior_log_hi)


def exact_partition_step(m, e_table, t_table, var_table, sigma, deadline,
                         pccp_iters, solver="structured", gated=False):
    """Partition strategy: exact per-device enumeration (DESIGN.md §2)."""
    del m, pccp_iters, solver, gated  # no inner barrier to configure
    m_new, feas = _exact_partition(e_table, t_table, var_table, sigma, deadline)
    return m_new, feas, jnp.ones(m_new.shape, jnp.int32)


def pccp_partition_step(m, e_table, t_table, var_table, sigma, deadline,
                        pccp_iters, solver="structured", gated=False):
    """Partition strategy: the paper's penalty CCP (Algorithm 1)."""
    x_init = jax.nn.one_hot(m, e_table.shape[-1], dtype=jnp.float64)
    res = pccp_partition(
        e_table, t_table, var_table, sigma, deadline, x_init,
        num_iters=pccp_iters, solver=solver, gated=gated
    )
    return res.m_sel, res.feasible, res.iters_to_converge


def default_starts(num_points: int) -> list[int]:
    """Multi-start spread of initial partition points (Fig. 10)."""
    m1 = num_points
    return sorted({1, m1 // 2, (3 * m1) // 4, max(m1 - 2, 1), m1 - 1})


def initial_points(fleet: Fleet, init_m, multi_start: bool):
    """Resolve the planner's initial partition points → (m0, use_multi).

    Shared by every planning entry point (``api.Planner``, the legacy
    ``plan``/``plan_grid`` wrappers) so all resolve starts identically
    (the batch contract is ``plan_many(...)[k] == plan(...)``).

    With ``multi_start`` and no explicit ``init_m``: the Fig. 10 spread as
    an (S, N) batch. Otherwise a single (N,) start — ``init_m`` broadcast,
    or full local inference (m = M). The alternation is sensitive to its
    start (paper Fig. 10 uses interior points): m = 0 pins f at f_min
    which makes every local prefix look deadline-infeasible in the
    partitioning step, while full-local allocates a high frequency from
    which all prefixes are reachable.

    On ragged fleets every start is clamped to the device's own chain
    (``m ≤ M_n``); the spread is derived from the padded width, so devices
    with short chains see a denser spread near their terminal point.
    """
    n, m1 = fleet.num_devices, fleet.max_points

    def clamp(m0):
        if fleet.num_points is None:
            return m0
        return jnp.minimum(m0, fleet.num_points - 1)

    if multi_start and init_m is None:
        starts = default_starts(m1)
        m0 = jnp.broadcast_to(
            jnp.asarray(starts, jnp.int32)[:, None], (len(starts), n))
        return clamp(m0), True
    if init_m is None:
        return clamp(jnp.full((n,), m1 - 1, jnp.int32)), False
    if not isinstance(init_m, jax.core.Tracer):  # bounds-check concrete starts
        arr = np.asarray(init_m)  # analyze: ok(TRC002): concrete by the Tracer guard above
        if arr.size and (arr.min() < 0 or arr.max() > m1 - 1):  # analyze: ok(TRC003): host bounds check on a concrete start
            raise ValueError(
                f"init_m must lie in [0, {m1 - 1}] (partition points 0..M for "
                f"a {m1 - 1}-block chain); got {init_m!r}")
    return clamp(jnp.broadcast_to(jnp.asarray(init_m, jnp.int32), (n,))), False


def _plan_tail(fleet: Fleet, m, alloc, deadline, eps, sig_model, feasible,
               traces, pccp_trace, assignment) -> Plan:
    """Shared plan assembly: margins + status at the final (m, alloc).
    Pure function of its inputs — the scalar and vector alternation
    branches (and only they) both end here, so the scalar path's ops are
    unchanged from the pre-placement goldens."""
    sel = select_point(fleet, m)
    t_mean = (
        energy.mean_local_time(sel.w_flops, sel.g_eff, alloc.f)
        + channel.offload_time(sel.d_bits, alloc.b, fleet.link.p_tx, fleet.link.gain)
        + sel.t_vm
    )
    margins = ccp.deterministic_deadline_margin(
        t_mean, sel.v_loc + sel.v_vm, eps, deadline, sig_model
    )
    total_energy = jnp.sum(alloc.energy)
    return Plan(
        m_sel=m,
        alloc=alloc,
        total_energy=total_energy,
        feasible=feasible & alloc.feasible,
        objective_trace=traces,
        pccp_iters=pccp_trace,
        margins=margins,
        status=_traced_status(alloc, total_energy, margins),
        assignment=assignment,
    )


def _alternation(fleet: Fleet, deadline, eps, B, edge_cap, m0, policy: Policy,
                 outer_iters: int, pccp_iters: int, channel_cv: float,
                 solver: str = "structured", pccp_gated: bool = False,
                 edge_eps=None) -> Plan:
    """One Algorithm-2 alternation from initial points ``m0`` — fully traced.

    The outer loop is a ``lax.scan`` carrying the partition decision; each
    step re-allocates (b, f) at the current m and re-partitions at the new
    (b, f). No host syncs, so the whole alternation stays one XLA program.
    Policy behaviour (σ model, time inflation, partition step) comes from
    the ``Policy`` record — no per-policy branches live here.

    ``edge_cap`` is the shared-edge VM-time budget (traced; ∞ ⇒ dedicated
    VMs): each step discovers the clearing price μ on the current tables
    and charges μ·t̄_vm per candidate point, so the partition internalizes
    edge contention; with ∞ capacity μ = 0 and the step is numerically
    identical to the uncoupled planner.

    A **per-node ``(E,)`` capacity vector** (DESIGN.md §placement) routes
    to the placement branch: each step assigns devices to nodes with the
    policy's ``assign`` strategy at the current occupancies, clears a
    per-node price vector μ ∈ R^E (``_node_clearing_prices``, warm-started
    per node through the scan), and charges each device its *own* node's
    price μ_{a_n}·t̄_vm in the partition tables. The capacity's *shape* is
    static, so the scalar path's jaxpr is untouched (E=1 vectors are
    collapsed to scalars by ``Scenario.normalized`` — goldens stay
    leaf-identical). ``edge_eps`` (static) swaps the mean occupancy rows
    for Cantelli chance-constrained rows everywhere the capacity is
    checked or cleared.
    """
    n = fleet.num_devices
    deadline = jnp.broadcast_to(jnp.asarray(deadline, jnp.float64), (n,))
    eps = jnp.broadcast_to(jnp.asarray(eps, jnp.float64), (n,))
    edge_cap = jnp.asarray(edge_cap, jnp.float64)
    sig_model, ub_k = policy.sigma_model, policy.ub_k
    sigma = ccp.SIGMA_FNS[sig_model](eps)
    occ_table = fleet.chain.t_vm  # (N, M+1) edge occupancy per point
    occ_var = fleet.chain.v_vm  # (N, M+1) VM variance (Cantelli row)
    edge_sig = placement.edge_sigma(edge_eps)
    m = jnp.broadcast_to(jnp.asarray(m0, jnp.int32), (n,))
    hi0 = jnp.asarray(_LOG_PRICE_HI0, jnp.float64)

    def last_or(i, repartition, m, mu_hi):
        """``repartition(mu_hi)`` except on the extra last step, which only
        allocates (its partition outputs are dropped below)."""
        out = jax.eval_shape(repartition, mu_hi)
        return jax.lax.cond(
            i < outer_iters, repartition,
            lambda mu_hi: (m, mu_hi, *(jnp.zeros(t.shape, t.dtype)
                                       for t in out[2:])), mu_hi)

    # outer_iters alternation steps plus one that only allocates at the
    # final m: the program holds one copy of the allocation, not two
    if edge_cap.ndim == 0:  # one shared edge (scalar μ — the seed goldens)
        def step(carry, i):
            m, lam_hi, mu_hi = carry
            alloc, lam_hi = allocate_with_bracket(
                fleet, m, deadline, eps, B, sig_model, ub_k, channel_cv,
                edge_capacity_s=edge_cap, prior_log_hi=lam_hi,
                edge_eps=edge_eps)

            def repartition(mu_hi):
                e_table, t_table, var_table = policy_point_tables(
                    fleet, alloc.b, alloc.f, policy, channel_cv)
                if policy.edge_aware:
                    mu, mu_hi = _edge_clearing_price(
                        e_table, t_table, var_table, sigma, deadline,
                        occ_table, edge_cap, prior_log_hi=mu_hi,
                        occ_var=occ_var, edge_sigma=edge_sig)
                else:
                    mu = jnp.asarray(0.0, jnp.float64)
                m_new, feas, pc = policy.partition(
                    m, e_table + mu * occ_table, t_table, var_table, sigma,
                    deadline, pccp_iters, solver, pccp_gated)
                return m_new, mu_hi, feas, pc, mu

            m_new, mu_hi, feas, pc, mu = last_or(i, repartition, m, mu_hi)
            return (m_new, lam_hi, mu_hi), ((m_new, alloc.b, alloc.f), pc,
                                            feas, mu, alloc)

        carry, (steps, pccp_trace, feas_seq, mu_seq, allocs) = jax.lax.scan(
            step, (m, hi0, hi0), jnp.arange(outer_iters + 1))
        assignment = jnp.zeros((n,), jnp.int32)
    else:  # per-node capacities: assignment + per-node prices
        e_count = edge_cap.shape[0]

        def step(carry, i):
            m, lam_hi, mu_hi = carry
            occ_now = jnp.take_along_axis(occ_table, m[:, None], -1)[:, 0]
            assign = placement.assign_devices(occ_now, edge_cap, policy.assign)
            alloc, lam_hi = allocate_with_bracket(
                fleet, m, deadline, eps, B, sig_model, ub_k, channel_cv,
                edge_capacity_s=edge_cap, prior_log_hi=lam_hi,
                assignment=assign, edge_eps=edge_eps)

            def repartition(mu_hi):
                e_table, t_table, var_table = policy_point_tables(
                    fleet, alloc.b, alloc.f, policy, channel_cv)
                if policy.edge_aware:
                    mu_vec, mu_hi = _node_clearing_prices(
                        e_table, t_table, var_table, sigma, deadline,
                        occ_table, assign, edge_cap, prior_log_hi=mu_hi,
                        occ_var=occ_var, edge_sigma=edge_sig)
                else:
                    mu_vec = jnp.zeros((e_count,), jnp.float64)
                mu_dev = mu_vec[assign]  # each device pays its own node's price
                m_new, feas, pc = policy.partition(
                    m, e_table + mu_dev[:, None] * occ_table, t_table,
                    var_table, sigma, deadline, pccp_iters, solver,
                    pccp_gated)
                return m_new, mu_hi, feas, pc, mu_vec

            m_new, mu_hi, feas, pc, mu_vec = last_or(i, repartition, m, mu_hi)
            return (m_new, lam_hi, mu_hi), ((m_new, alloc.b, alloc.f), pc,
                                            feas, mu_vec, (alloc, assign))

        mu_hi0 = jnp.full((e_count,), _LOG_PRICE_HI0, jnp.float64)
        carry, (steps, pccp_trace, feas_seq, mu_seq, (allocs, assigns)) = (
            jax.lax.scan(step, (m, hi0, mu_hi0), jnp.arange(outer_iters + 1)))
        assignment = assigns[-1]

    m = carry[0]
    steps, pccp_trace, feas_seq, mu_seq = jax.tree_util.tree_map(
        lambda x: x[:outer_iters], (steps, pccp_trace, feas_seq, mu_seq))
    # the last step's allocation is the plan's; it records the last price
    alloc = jax.tree_util.tree_map(lambda x: x[-1], allocs)._replace(
        mu=mu_seq[-1])
    traces = objective_trace(fleet, *steps)
    return _plan_tail(fleet, m, alloc, deadline, eps, sig_model, feas_seq[-1],
                      traces, pccp_trace, assignment)


def _select_best(plans: Plan) -> jnp.ndarray:
    """Traced multi-start selection: feasible plans first, then lowest
    energy — the same lexicographic key as the seed's
    ``min(plans, key=(num_infeasible, energy))``, with first-occurrence
    tie-breaking matching Python ``min`` over ascending starts.

    Fail-soft guard: a lane whose energy went non-finite is ranked worse
    than every finite lane (NaNs would otherwise poison the argmin), so a
    single diverged start can never shadow a healthy one. With all lanes
    finite this is bit-identical to the unguarded selection."""
    finite = jnp.isfinite(plans.total_energy)
    n_dev = plans.feasible.shape[-1]
    n_bad = jnp.where(jnp.asarray(finite),
                      jnp.sum(~plans.feasible, axis=-1), n_dev + 1)
    best_bad = jnp.min(n_bad)
    e_masked = jnp.where((n_bad == best_bad) & finite,
                         plans.total_energy, jnp.inf)
    return jnp.argmin(e_masked)


def _multi_start(fleet: Fleet, deadline, eps, B, edge_cap, m0_batch,
                 policy: Policy, outer_iters: int, pccp_iters: int,
                 channel_cv: float, solver: str = "structured",
                 pccp_gated: bool = False, edge_eps=None) -> Plan:
    """vmapped multi-start alternation + traced best-plan selection."""
    plans = jax.vmap(
        lambda m0: _alternation(fleet, deadline, eps, B, edge_cap, m0, policy,
                                outer_iters, pccp_iters, channel_cv, solver,
                                pccp_gated, edge_eps)
    )(m0_batch)
    idx = _select_best(plans)
    return jax.tree_util.tree_map(lambda x: x[idx], plans)


def _solve_entry(fleet: Fleet, deadline, eps, B, edge_cap, policy: Policy,
                 outer_iters: int, pccp_iters: int, channel_cv: float,
                 solver: str = "structured", pccp_gated: bool = False,
                 edge_eps=None) -> Plan:
    """Entry for solve-override policies (no alternation, no starts; the
    inner-barrier statics do not apply to exact solves)."""
    del solver, pccp_gated
    return policy.solve(fleet, deadline, eps, B, edge_cap, policy,
                        outer_iters, pccp_iters, channel_cv, edge_eps)


_STATICS = ("policy", "outer_iters", "pccp_iters", "channel_cv", "solver",
            "pccp_gated", "edge_eps")

#: Jitted entry points. Exposed at module level (not hidden in ``plan``) so
#: tests can assert cache behaviour via ``_cache_size()``. ``policy`` is a
#: static ``Policy`` record; the registry hands out singletons so the cache
#: key is stable across calls.
plan_single_jit = partial(jax.jit, static_argnames=_STATICS)(_alternation)
plan_multi_jit = partial(jax.jit, static_argnames=_STATICS)(_multi_start)
plan_solve_jit = partial(jax.jit, static_argnames=_STATICS)(_solve_entry)


def plan(
    fleet: Fleet,
    deadline: jnp.ndarray,
    eps: jnp.ndarray,
    B: float,
    policy: str = "robust",
    outer_iters: int = 6,
    init_m: Optional[jnp.ndarray] = None,
    pccp_iters: int = 10,
    multi_start: bool = True,
    channel_cv: float = 0.0,
) -> Plan:
    """Run Algorithm 2 (or a baseline policy) and return the plan.

    .. deprecated::
        Thin delegating wrapper over :class:`repro.core.api.Planner` —
        prefer ``Planner(PlannerConfig(...)).plan(fleet, Scenario(...))``,
        which also exposes zipped scenario batches (``plan_many``) and
        grids. This wrapper is kept leaf-identical to the seed goldens
        (``tests/golden/seed_plans.json``).

    ``multi_start`` follows Fig. 10: the alternation converges to a
    stationary point that depends on the initial partition point, so we run
    it from a small spread of starts (vmapped) and keep the best feasible
    plan. The whole call — including the multi-start sweep — is a single
    compiled XLA program; scenario parameters (deadline, ε, B) are traced,
    so only a new fleet *shape* or new static (policy, iteration counts)
    triggers recompilation.
    """
    import warnings

    from repro.core.api import Planner, PlannerConfig, Scenario

    warnings.warn(
        "repro.core.plan is deprecated; use "
        "api.Planner(PlannerConfig(...)).plan(fleet, Scenario(...))",
        DeprecationWarning, stacklevel=2)
    cfg = PlannerConfig(policy=policy, outer_iters=outer_iters,
                        pccp_iters=pccp_iters, multi_start=multi_start,
                        channel_cv=channel_cv)
    return Planner(cfg).plan(fleet, Scenario(deadline, eps, B), init_m=init_m)


def _optimal_prep(fleet: Fleet, deadline, sigma, B):
    """λ-invariant tables of the optimal joint search: per-(device, point)
    deadline budgets and the feasibility bracket of ``_device_invariants``.
    Shared by ``plan_optimal`` and the per-group programs of
    ``core.decompose`` (which runs the same search at native group width)."""
    c, plat, link = fleet.chain, fleet.platform, fleet.link
    budget_all = (
        deadline[:, None]
        - c.t_vm
        - sigma[:, None] * jnp.sqrt(jnp.maximum(c.v_loc + c.v_vm, 0.0))
    )  # (N, M+1)
    if fleet.valid is not None:  # ragged fleet: padded points are never
        # feasible (negative budget ⇒ feas=False ⇒ cost=∞) nor the
        # least-bad fallback (argmax over budgets)
        budget_all = jnp.where(fleet.valid, budget_all, -MASK_TIME_S)
    inv_points = jax.vmap(
        lambda bud, d, w, g, fmax, p, h: _device_invariants(bud, d, w, g, fmax, p, h, B),
        in_axes=(0, 0, 0, 0, None, None, None),
    )
    inv_devices = jax.vmap(inv_points, in_axes=(0, 0, 0, 0, 0, 0, 0))
    b_lo_all, feas0_all = inv_devices(
        budget_all, c.d_bits, c.w_flops, c.g_eff, plat.f_max, link.p_tx, link.gain
    )  # (N, M+1) each
    return budget_all, b_lo_all, feas0_all


def _optimal_point_solve(fleet: Fleet, budget_all, b_lo_all, feas0_all, lam, B):
    """Solve the 1-D convex bandwidth problem for every (device, point) at
    price λ → ``(cost, b, f, e, feas)`` tables, cost ∞ on infeasible points."""
    c, plat, link = fleet.chain, fleet.platform, fleet.link

    def per_point(lam, bud, d, w, g, k, fmin, fmax, p, h, blo, fe):
        b, f, feas = _device_best_b_at(lam, bud, d, w, g, k, fmin, fmax, p, h, B, blo, fe)
        e = energy.expected_local_energy(k, w, g, f) + channel.offload_energy(d, b, p, h)
        cost = jnp.where(feas, e + lam * b, jnp.inf)
        return cost, b, f, e, feas

    vm_points = jax.vmap(
        per_point, in_axes=(None, 0, 0, 0, 0, None, None, None, None, None, 0, 0))
    vm_devices = jax.vmap(vm_points, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0))
    return vm_devices(
        lam, budget_all, c.d_bits, c.w_flops, c.g_eff,
        plat.kappa, plat.f_min, plat.f_max, link.p_tx, link.gain,
        b_lo_all, feas0_all,
    )


def _optimal_select(cost, feas, budget_all, occ_all, mu):
    """Per-device argmin of the (λ, μ)-priced point scores (cost already ∞
    on infeasible points; fallback = largest-budget point)."""
    priced = cost + mu * occ_all
    any_feas = jnp.any(feas, axis=-1)
    m_sel = jnp.where(any_feas, jnp.argmin(priced, -1),
                      jnp.argmax(budget_all, -1))
    return m_sel.astype(jnp.int32), any_feas


def plan_optimal(fleet: Fleet, deadline, eps, B, sigma_model: str = "cantelli",
                 edge_capacity_s=None, assign: str = "hybrid",
                 edge_eps=None) -> Plan:
    """§VI "Optimal policy": joint exact search over (m, b, f).

    At a fixed bandwidth price λ the joint problem separates per device
    *and* per candidate point: solve the 1-D convex bandwidth problem for
    every (n, m), take the per-device argmin over m, then bisect λ until
    Σ b ≤ B. Complexity O(N·M·log) — equivalent to the paper's exhaustive
    baseline (which is exponential only because it enumerates x jointly).
    The λ-invariant feasibility bracket per (n, m) is hoisted out of the
    price bisection (same hoist as ``resource.allocate``).

    ``edge_capacity_s`` turns this into the **two-price dual
    decomposition** over (λ, μ) of DESIGN.md §edge: the per-point score
    gains μ·t̄_vm and the outer search nests — for every λ step the edge
    price μ*(λ) is cleared by a *cheap* inner bisection over the already-
    solved point tables (the per-point (b, f) solutions depend on λ only,
    so no golden sections re-run), and the λ bisection proceeds on
    Σ b(λ, μ*(λ)) − B, which stays monotone because partial maximization
    over μ preserves the dual's concavity. With ∞ capacity μ*(λ) ≡ 0 and
    the search degenerates to the single-price seed path bit-for-bit.

    Fully traced (fixed-iteration bisection), so the ``"optimal"`` policy
    vmaps over zipped scenario batches like any other registry entry.

    A per-node ``(E,)`` capacity vector (DESIGN.md §placement) runs the
    placement variant: at each λ the assignment is built from the
    unpriced selection's occupancies (strategy ``assign``), per-node
    prices μ ∈ R^E are cleared over the same point tables, and the final
    selection is priced per device at its own node's μ_{a_n}. ``edge_eps``
    (static) makes every occupancy row/clearing Cantelli
    chance-constrained.
    """
    n = fleet.num_devices
    deadline = jnp.broadcast_to(jnp.asarray(deadline, jnp.float64), (n,))
    eps = jnp.broadcast_to(jnp.asarray(eps, jnp.float64), (n,))
    edge_cap = jnp.asarray(
        jnp.inf if edge_capacity_s is None else edge_capacity_s, jnp.float64)
    c, plat, link = fleet.chain, fleet.platform, fleet.link
    sigma = ccp.SIGMA_FNS[sigma_model](eps)
    occ_all = c.t_vm  # (N, M+1) shared-edge occupancy of each point
    var_all = c.v_vm  # (N, M+1) VM variance (Cantelli row)
    edge_sig = placement.edge_sigma(edge_eps)

    budget_all, b_lo_all, feas0_all = _optimal_prep(fleet, deadline, sigma, B)

    def select(cost, feas, mu):
        return _optimal_select(cost, feas, budget_all, occ_all, mu)

    def occ_dev(m_sel):
        return jnp.take_along_axis(occ_all, m_sel[:, None], -1)[:, 0]

    def var_dev(m_sel):
        return jnp.take_along_axis(var_all, m_sel[:, None], -1)[:, 0]

    if edge_cap.ndim == 0:  # one shared edge (scalar μ — the seed goldens)
        def occ_of(m_sel):
            occ = jnp.sum(occ_dev(m_sel))
            if edge_sig > 0.0:
                occ = occ + edge_sig * jnp.sqrt(
                    jnp.maximum(jnp.sum(var_dev(m_sel)), 0.0))
            return occ

        def mu_star(cost, feas):
            """Clearing price of the edge capacity at fixed λ — a cheap
            ``_clearing_price`` search over the point tables (no golden
            sections re-run; the per-point (b, f) depend on λ only)."""
            return _clearing_price(
                lambda mu: occ_of(select(cost, feas, mu)[0]), edge_cap)[0]

        def solve_at(lam):
            cost, b, f, e, feas = _optimal_point_solve(
                fleet, budget_all, b_lo_all, feas0_all, lam, B)
            mu = mu_star(cost, feas)
            m_sel, any_feas = select(cost, feas, mu)
            pick = lambda a: jnp.take_along_axis(a, m_sel[:, None], -1)[:, 0]
            return (m_sel, pick(b), pick(f), pick(e), pick(feas) & any_feas,
                    mu, jnp.zeros((n,), jnp.int32))
    else:  # per-node capacities: assignment + per-node prices
        e_count = edge_cap.shape[0]
        node_ids = jnp.arange(e_count)

        def eff_node_occ(m_sel, mask):
            occ = jnp.sum(jnp.where(mask, occ_dev(m_sel), 0.0))
            if edge_sig > 0.0:
                occ = occ + edge_sig * jnp.sqrt(jnp.maximum(
                    jnp.sum(jnp.where(mask, var_dev(m_sel), 0.0)), 0.0))
            return occ

        def solve_at(lam):
            cost, b, f, e, feas = _optimal_point_solve(
                fleet, budget_all, b_lo_all, feas0_all, lam, B)
            m0_sel, _ = select(cost, feas, jnp.asarray(0.0, jnp.float64))
            a = placement.assign_devices(occ_dev(m0_sel), edge_cap, assign)
            masks = a[None, :] == node_ids[:, None]  # (E, N)

            def one(mask, cap):
                return _clearing_price(
                    lambda mu: eff_node_occ(select(cost, feas, mu)[0], mask),
                    cap)[0]

            mu_vec = jax.vmap(one)(masks, edge_cap)
            m_sel, any_feas = select(cost, feas, mu_vec[a][:, None])
            pick = lambda arr: jnp.take_along_axis(arr, m_sel[:, None], -1)[:, 0]
            return (m_sel, pick(b), pick(f), pick(e), pick(feas) & any_feas,
                    mu_vec, a)

    (m_sel, b, f, e, feas, mu, assignment), log_lam, need_price, _ = (
        price_search(solve_at, lambda out: jnp.sum(out[1]), B))
    lam = jnp.where(need_price, 10.0**log_lam, 0.0)
    # primal capacity check at the rounded discrete selection
    if edge_cap.ndim == 0:
        feas = feas & (occ_of(m_sel) <= edge_cap * (1.0 + _EDGE_CAP_RTOL))
    else:
        occ_nodes = jax.vmap(
            lambda mask: eff_node_occ(m_sel, mask)
        )(assignment[None, :] == node_ids[:, None])
        node_ok = occ_nodes <= edge_cap * (1.0 + _EDGE_CAP_RTOL)
        feas = feas & node_ok[assignment]

    sel = select_point(fleet, m_sel)
    e_loc = energy.expected_local_energy(plat.kappa, sel.w_flops, sel.g_eff, f)
    e_off = channel.offload_energy(sel.d_bits, b, link.p_tx, link.gain)
    alloc = Allocation(b=b, f=f, e_loc=e_loc, e_off=e_off, feasible=feas,
                       lam=lam, mu=mu)
    t_mean = (
        energy.mean_local_time(sel.w_flops, sel.g_eff, f)
        + channel.offload_time(sel.d_bits, b, link.p_tx, link.gain)
        + sel.t_vm
    )
    margins = ccp.deterministic_deadline_margin(
        t_mean, sel.v_loc + sel.v_vm, eps, deadline, sigma_model
    )
    total_energy = jnp.sum(alloc.energy)
    return Plan(
        m_sel=m_sel,
        alloc=alloc,
        total_energy=total_energy,
        feasible=feas,
        objective_trace=total_energy[None],
        pccp_iters=jnp.ones((1, fleet.num_devices), jnp.int32),
        margins=margins,
        status=_traced_status(alloc, total_energy, margins),
        assignment=assignment,
    )


def _optimal_solve(fleet, deadline, eps, B, edge_cap, policy: Policy,
                   outer_iters, pccp_iters, channel_cv, edge_eps=None) -> Plan:
    """Registry ``solve`` adapter for the optimal baseline (iteration
    counts and channel_cv do not apply to the exact search)."""
    del outer_iters, pccp_iters, channel_cv
    return plan_optimal(fleet, deadline, eps, B, sigma_model=policy.sigma_model,
                        edge_capacity_s=edge_cap, assign=policy.assign,
                        edge_eps=edge_eps)


@partial(jax.jit, static_argnames=("sigma_model", "assign", "edge_eps"))
def plan_fixed_partition(fleet: Fleet, m_sel, deadline, eps, B,
                         edge_capacity_s=None,
                         sigma_model: str = "cantelli",
                         assign: str = "hybrid", edge_eps=None) -> Plan:
    """A full :class:`Plan` at a *forced* partition: allocate (b, f) by
    the dual decomposition at the given ``m_sel`` and score it — no
    partitioning loop, no PCCP.

    This is the cheap "λ/μ price-step" rung of the degradation ladder
    (DESIGN.md §robustness): re-clear the bandwidth/edge prices against
    re-fit moments while keeping the incumbent split, at the cost of one
    allocation solve. It is also how the precomputed contingency plans
    (local-only m = M_n, full-offload m = 0) are built at plan time.

    ``m_sel`` is broadcast to ``(N,)`` and clamped to each device's own
    chain on ragged fleets.

    A per-node ``(E,)`` ``edge_capacity_s`` vector computes the
    device→node assignment at the forced partition with the ``assign``
    strategy (DESIGN.md §placement) and checks per-node occupancy;
    ``edge_eps`` makes the rows Cantelli chance-constrained.
    """
    n = fleet.num_devices
    deadline = jnp.broadcast_to(jnp.asarray(deadline, jnp.float64), (n,))
    eps = jnp.broadcast_to(jnp.asarray(eps, jnp.float64), (n,))
    edge_cap = jnp.asarray(
        jnp.inf if edge_capacity_s is None else edge_capacity_s, jnp.float64)
    m = jnp.broadcast_to(jnp.asarray(m_sel, jnp.int32), (n,))
    m = jnp.minimum(m, fleet.points_per_device - 1)
    if edge_cap.ndim == 0:
        assignment = jnp.zeros((n,), jnp.int32)
        alloc = allocate(fleet, m, deadline, eps, B, sigma_model,
                         edge_capacity_s=edge_cap, edge_eps=edge_eps)
    else:
        assignment = placement.assign_devices(
            select_point(fleet, m).t_vm, edge_cap, assign)
        alloc = allocate(fleet, m, deadline, eps, B, sigma_model,
                         edge_capacity_s=edge_cap, assignment=assignment,
                         edge_price=jnp.zeros(edge_cap.shape, jnp.float64),
                         edge_eps=edge_eps)
    sel = select_point(fleet, m)
    t_mean = (
        energy.mean_local_time(sel.w_flops, sel.g_eff, alloc.f)
        + channel.offload_time(sel.d_bits, alloc.b, fleet.link.p_tx,
                               fleet.link.gain)
        + sel.t_vm
    )
    margins = ccp.deterministic_deadline_margin(
        t_mean, sel.v_loc + sel.v_vm, eps, deadline, sigma_model)
    total_energy = jnp.sum(alloc.energy)
    return Plan(
        m_sel=m,
        alloc=alloc,
        total_energy=total_energy,
        feasible=alloc.feasible & (margins <= 1e-9),
        objective_trace=total_energy[None],
        pccp_iters=jnp.ones((1, n), jnp.int32),
        margins=margins,
        status=_traced_status(alloc, total_energy, margins),
        assignment=assignment,
    )


def plan_health(plan: Plan, pccp_iter_cap: Optional[int] = None):  # analyze: ok(TRC001,TRC002,TRC003): host-side verdict; the fail-soft caller skips it under tracing
    """Host-side health verdict on a single (unbatched) plan.

    Returns ``(ok, reason)``. Unhealthy when any actionable leaf
    (energy, allocation, margins) is non-finite, when the traced solve
    stamped ``PLAN_DEGRADED``, or — with ``pccp_iter_cap`` given — when
    the PCCP is *stuck*: every device burned the full iteration budget in
    the final outer step yet the plan is still infeasible (θ_err never
    met the stopping rule). Fallback statuses count as healthy: they are
    deliberate, usable plans.
    """
    e = np.asarray(plan.total_energy)
    if e.ndim != 0:
        raise ValueError(
            "plan_health scores a single plan; index batched plans with "
            "scenario_at/plan_at first")
    for name, leaf in (("total_energy", plan.total_energy),
                       ("alloc.b", plan.alloc.b), ("alloc.f", plan.alloc.f),
                       ("margins", plan.margins)):
        if not np.all(np.isfinite(np.asarray(leaf))):
            return False, f"non-finite {name}"
    status = int(np.asarray(plan.status))
    if status == PLAN_DEGRADED:
        return False, "solver stamped PLAN_DEGRADED"
    if pccp_iter_cap is not None:
        iters = np.asarray(plan.pccp_iters)
        if (iters.size and np.all(iters[-1] >= pccp_iter_cap)
                and not np.any(np.asarray(plan.feasible))):
            return False, (f"PCCP stuck at the {pccp_iter_cap}-iteration cap "
                           "with no feasible device")
    return True, PLAN_STATUS_NAMES.get(status, f"status={status}")


ROBUST = register_policy(Policy("robust", partition=pccp_partition_step))
ROBUST_EXACT = register_policy(Policy("robust_exact", partition=exact_partition_step))
GAUSSIAN = register_policy(
    Policy("gaussian", sigma_model="gaussian", partition=exact_partition_step))
WORST_CASE = register_policy(
    Policy("worst_case", sigma_model="hard", ub_k=WORST_CASE_UB_K,
           partition=exact_partition_step))
OPTIMAL = register_policy(Policy("optimal", solve=_optimal_solve))

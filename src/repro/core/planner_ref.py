"""Straight-line port of the seed Algorithm-2 planner loop.

This is the *unfused* reference: the outer alternation is a Python loop
with per-iteration jit dispatches, the multi-start spread is sequential
with ``float(...)`` host syncs in the scoring — exactly the structure the
seed ``plan()`` had before the scan/vmap fusion (DESIGN.md §planner).

It exists for two reasons:

1. **Golden pinning** — ``tests/test_plan_golden.py`` asserts the fused
   planner reproduces this loop's ``m_sel`` exactly and its energies to
   1e-8 rtol across policies and paper-table configs.
2. **Speedup accounting** — ``benchmarks/bench_runtime.py`` times it
   against the fused path so the dispatch-overhead win is tracked across
   PRs (Fig. 11 runtime claim).

It shares every numerical building block (``allocate``, ``pccp_partition``,
``policy_point_tables``, ``_exact_partition``) with the fused planner, so
any divergence isolates the fusion restructuring itself.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import ccp, channel, energy
from repro.core.blocks import Fleet
from repro.core.pccp import pccp_partition
from repro.core.planner import (
    Plan,
    _exact_partition,
    _traced_status,
    default_starts,
    get_policy,
    objective_trace,
    policy_point_tables,
)
from repro.core.resource import allocate, select_point


def plan_reference(
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
    pccp_schedule: tuple | None = None,
    solver: str = "structured",
) -> Plan:
    """Seed-loop Algorithm 2: Python outer loop, sequential multi-start.

    ``pccp_schedule`` overrides the inner barrier schedule — pass
    ``pccp.SEED_SCHEDULE`` to reproduce the seed's full inner-solver cost
    (the default shares the tuned schedule with the fused planner so
    golden comparisons are bit-exact). ``solver`` picks the inner barrier
    path; pass ``"dense"`` (with the seed schedule) to reproduce the
    seed's generic autodiff solver for speedup accounting.
    """
    if multi_start and init_m is None:
        plans = [
            plan_reference(fleet, deadline, eps, B, policy, outer_iters,
                           jnp.int32(s), pccp_iters, multi_start=False,
                           channel_cv=channel_cv, pccp_schedule=pccp_schedule,
                           solver=solver)
            for s in default_starts(fleet.max_points)
        ]

        def score(p: Plan):
            # feasible plans first, then lowest energy
            return (float(jnp.sum(~p.feasible)), float(p.total_energy))

        return min(plans, key=score)

    n, m1 = fleet.num_devices, fleet.max_points
    deadline = jnp.broadcast_to(jnp.asarray(deadline, jnp.float64), (n,))
    eps = jnp.broadcast_to(jnp.asarray(eps, jnp.float64), (n,))
    pol = get_policy(policy)
    sig_model, ub_k = pol.sigma_model, pol.ub_k
    sigma = ccp.SIGMA_FNS[sig_model](eps)

    m = (
        jnp.full((n,), m1 - 1, jnp.int32)
        if init_m is None
        else jnp.broadcast_to(jnp.asarray(init_m, jnp.int32), (n,))
    )
    if fleet.num_points is not None:  # ragged fleet: clamp starts to M_n
        m = jnp.minimum(m, fleet.num_points - 1)

    steps, pccp_trace = [], []
    feasible = jnp.ones((n,), bool)
    alloc = None
    for _ in range(outer_iters):
        alloc = allocate(fleet, m, deadline, eps, B, sig_model, ub_k, channel_cv)
        e_table, t_table, var_table = policy_point_tables(
            fleet, alloc.b, alloc.f, pol, channel_cv)
        if policy == "robust":
            x_init = jax.nn.one_hot(m, m1, dtype=jnp.float64)
            pccp_kw = {} if pccp_schedule is None else {"schedule": pccp_schedule}
            res = pccp_partition(
                e_table, t_table, var_table, sigma, deadline, x_init,
                num_iters=pccp_iters, solver=solver, **pccp_kw
            )
            m, feasible = res.m_sel, res.feasible
            pccp_trace.append(res.iters_to_converge)
        else:  # robust_exact / gaussian / worst_case → exact enumeration
            m, feasible = _exact_partition(e_table, t_table, var_table, sigma, deadline)
            pccp_trace.append(jnp.ones((n,), jnp.int32))
        steps.append((m, alloc.b, alloc.f))

    alloc = allocate(fleet, m, deadline, eps, B, sig_model, ub_k, channel_cv)
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
        objective_trace=objective_trace(
            fleet, *(jnp.stack(x) for x in zip(*steps, strict=True))),
        pccp_iters=jnp.stack(pccp_trace),
        margins=margins,
        status=_traced_status(alloc, total_energy, margins),
    )

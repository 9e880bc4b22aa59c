"""Resource-allocation subproblem (paper §V-B, problems (16) → (23)).

Given a partition decision m_n per device, jointly allocate uplink
bandwidth b_n (Σ b_n ≤ B) and DVFS frequency f_n ∈ [f_min, f_max] to
minimize expected energy under the ECR-deterministic deadline (22).

Two solvers:

- ``allocate`` (primary): Lagrangian dual on the single coupling
  constraint Σ b_n ≤ B. For a bandwidth price λ the problem separates per
  device; the inner 1-D problem over b is convex (partial minimization
  over f is closed-form), solved by golden section; λ is found by
  bisection on Σ b*(λ) − B. Strong duality holds (convex + Slater), so
  this matches the paper's interior-point optimum.
- ``allocate_ipm`` (cross-check): the paper-faithful joint interior-point
  solve of (23) in scaled variables, used in tests to certify ``allocate``.

Shared-edge capacity (DESIGN.md §edge): beyond the paper's dedicated-VM
assumption (§III-B), the edge accelerator may be a *shared* resource with
a per-round VM-time budget  Σ_n occ_n(m_n) ≤ C_edge, where
occ_n = t̄_vm at device n's selected point. At a fixed partition the
occupancies are constants, so ``allocate`` only *checks* the capacity
(feasibility flags) and records the operative edge price μ; the price
itself is discovered where the partition is chosen — the (λ, μ) two-price
search in ``planner.plan_optimal`` and the per-step clearing price of the
Algorithm-2 alternation — both built on this module's price-bracket
helpers.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ccp, channel, energy, placement
from repro.core.blocks import Fleet
from repro.solvers.scalar import bisect, golden_section
from repro.solvers.ipm import BarrierSpec, barrier_solve

_BIG = 1e9
_TINY_B = 1e-3  # Hz floor for allocated bandwidth

#: Dual-price searches run in log10 space. The seed bracket top (λ = 10²)
#: is right for paper-scale scenarios; when the market-clearing price is
#: higher (extreme bandwidth/capacity starvation) the bracket is expanded
#: adaptively up to 10¹⁸ — beyond that the constraint cannot be priced
#: out (the λ-invariant feasibility floors alone overrun the budget) and
#: the caller flags infeasibility instead of silently rescaling.
_LOG_PRICE_LO = -16.0
_LOG_PRICE_HI0 = 2.0
_LOG_PRICE_HI_MAX = 18.0
_LOG_PRICE_STEP = 4.0
#: relative tolerance of the Σ occ ≤ C_edge capacity check
_EDGE_CAP_RTOL = 1e-9


#: phases of ``price_search``'s loop, each naming what its next
#: evaluation is for
(_PH_ZERO, _PH_START, _PH_PROBE, _PH_EXPAND, _PH_BISECT_LO, _PH_BISECT,
 _PH_FINAL, _PH_DONE) = range(8)


def price_search(solve_at, total, cap, hi_start=None, iters: int = 60,
                 endpoint: str = "mid", final: bool = True):
    """Smallest dual price ``p ≥ 0`` with ``total(solve_at(p)) ≤ cap``, for
    a ``total`` non-increasing in the price. Returns
    ``(out, log_p, need, log_hi)``.

    The search runs in log10 space, one evaluation per step of a single
    ``lax.while_loop`` — so the compiled program holds one copy of
    ``solve_at``, however many stages the search has:

    1. ``need = total(solve_at(0)) > cap`` (complementary slackness: the
       price is 0 when the unpriced solve already fits);
    2. the bracket top ``log_hi`` rises from ``_LOG_PRICE_HI0`` in
       ``_LOG_PRICE_STEP`` steps until the excess
       ``total(solve_at(10**x)) - cap`` is ≤ 0 (or ``_LOG_PRICE_HI_MAX``
       is reached: then even the max price cannot clear, and the caller
       sees an infeasible solve). ``hi_start`` (traced, optional)
       warm-starts it from a prior bracket top: snapped to the grid
       ``HI0 + k·STEP`` (the only values a cold expansion can produce, all
       exact in float64), then *contracted* while the next-lower grid
       point still clears and expanded as usual. The excess is monotone,
       so both directions stop at the grid point a cold expansion finds:
       the warm path is value-identical, it only spends its evaluations
       near the answer;
    3. ``iters`` bisection steps on ``[_LOG_PRICE_LO, log_hi]``; ``log_p``
       is the final bracket's midpoint, or its upper end with
       ``endpoint="hi"`` (for a step-function excess the upper end stays
       on the ``excess ≤ 0`` side);
    4. with ``final``, ``out = solve_at(where(need, 10**log_p, 0))`` (else
       ``out`` is None).
    """
    if endpoint not in ("mid", "hi"):
        raise ValueError(f"endpoint must be 'mid' or 'hi', got {endpoint!r}")
    hi0 = jnp.asarray(_LOG_PRICE_HI0, jnp.float64)
    if hi_start is None:
        start = hi0
    else:
        k = jnp.round((jnp.asarray(hi_start, jnp.float64) - hi0)
                      / _LOG_PRICE_STEP)
        k_max = (_LOG_PRICE_HI_MAX - _LOG_PRICE_HI0) // _LOG_PRICE_STEP
        start = hi0 + jnp.clip(k, 0.0, k_max) * _LOG_PRICE_STEP
    zero = jnp.asarray(0.0, jnp.float64)
    lo0 = jnp.asarray(_LOG_PRICE_LO, jnp.float64)
    out0 = jax.tree_util.tree_map(
        lambda t: jnp.zeros(t.shape, t.dtype), jax.eval_shape(solve_at, zero))
    state = dict(phase=jnp.asarray(_PH_ZERO, jnp.int32),
                 k=jnp.asarray(0, jnp.int32), x=zero, hi=start, f_hi=zero,
                 lo=lo0, b_hi=start, f_lo=zero, need=jnp.asarray(False),
                 out=out0)
    bisect_end = ((lambda lo, hi: hi) if endpoint == "hi"
                  else (lambda lo, hi: 0.5 * (lo + hi)))

    def expand_or_bisect(st):
        """The bracket top's excess is known: step the top up, or start
        the bisection at its lower end."""
        go = (st["f_hi"] > 0.0) & (st["hi"] < _LOG_PRICE_HI_MAX - 1e-9)
        hi = jnp.where(go, st["hi"] + _LOG_PRICE_STEP, st["hi"])
        return dict(st, hi=hi, b_hi=hi, lo=lo0,
                    phase=jnp.where(go, _PH_EXPAND, _PH_BISECT_LO),
                    x=jnp.where(go, hi, lo0))

    def body(st):
        ph, x = st["phase"], st["x"]
        price = jnp.where(ph == _PH_FINAL,
                          jnp.where(st["need"], 10.0**x, 0.0),
                          jnp.where(ph == _PH_ZERO, 0.0, 10.0**x))
        out = solve_at(price)
        tot = total(out)
        f = tot - cap
        nxt = {_PH_ZERO: dict(st, need=tot > cap, phase=_PH_START, x=start)}
        started = dict(st, hi=start, f_hi=f)
        nxt[_PH_START] = (
            expand_or_bisect(started) if hi_start is None else
            dict(started, phase=_PH_PROBE, x=start - _LOG_PRICE_STEP))
        # contraction: f is the excess one grid step below the top
        above = st["hi"] > hi0 + 1e-9
        f_dn = jnp.where(above, f, 1.0)
        hi_dn = st["hi"] - _LOG_PRICE_STEP
        nxt[_PH_PROBE] = jax.tree_util.tree_map(
            partial(jnp.where, above & (f_dn <= 0.0)),
            dict(st, hi=hi_dn, f_hi=f_dn, phase=_PH_PROBE,
                 x=hi_dn - _LOG_PRICE_STEP),
            expand_or_bisect(st))
        nxt[_PH_EXPAND] = expand_or_bisect(dict(st, f_hi=f))
        nxt[_PH_BISECT_LO] = dict(st, f_lo=f, phase=_PH_BISECT, k=0,
                                  x=0.5 * (st["lo"] + st["b_hi"]))
        right = jnp.sign(f) == jnp.sign(st["f_lo"])
        lo = jnp.where(right, x, st["lo"])
        b_hi = jnp.where(right, st["b_hi"], x)
        more = st["k"] + 1 < iters
        nxt[_PH_BISECT] = dict(
            st, lo=lo, b_hi=b_hi, k=st["k"] + 1,
            f_lo=jnp.where(right, f, st["f_lo"]),
            phase=jnp.where(more, _PH_BISECT,
                            _PH_FINAL if final else _PH_DONE),
            x=jnp.where(more, 0.5 * (lo + b_hi), bisect_end(lo, b_hi)))
        nxt[_PH_FINAL] = dict(st, out=out, phase=_PH_DONE)

        def pick(*leaves):  # the next state of the phase that ran
            chosen = leaves[-1]
            for i in range(len(leaves) - 2, -1, -1):
                chosen = jnp.where(ph == i, leaves[i], chosen)
            return chosen

        return jax.tree_util.tree_map(pick, *(
            jax.tree_util.tree_map(lambda a, ref: jnp.asarray(a, ref.dtype),
                                   nxt[i], st)
            for i in range(_PH_DONE)))

    st = jax.lax.while_loop(lambda st: st["phase"] != _PH_DONE, body, state)
    out = st["out"] if final else None  # analyze: ok(TRC003): ``final`` is a static Python flag
    return out, st["x"], st["need"], st["hi"]


class Selected(NamedTuple):
    """Per-device chain quantities at the chosen partition point."""

    d_bits: jnp.ndarray
    w_flops: jnp.ndarray
    g_eff: jnp.ndarray
    v_loc: jnp.ndarray
    t_vm: jnp.ndarray
    v_vm: jnp.ndarray


class Allocation(NamedTuple):
    b: jnp.ndarray  # (N,) Hz
    f: jnp.ndarray  # (N,) Hz
    e_loc: jnp.ndarray  # (N,) J (expected)
    e_off: jnp.ndarray  # (N,) J
    feasible: jnp.ndarray  # (N,) bool
    lam: jnp.ndarray  # scalar dual price of bandwidth
    mu: jnp.ndarray = 0.0  # scalar dual price of shared-edge VM capacity

    @property
    def energy(self):
        return self.e_loc + self.e_off


def select_point(fleet: Fleet, m_sel: jnp.ndarray) -> Selected:
    """Gather chain columns at per-device partition points (N,).

    On ragged fleets the gather index is clamped to each device's own
    chain (``m ≤ M_n``), so a padded point can never be selected — every
    consumer of a partition decision (``allocate``, the final plan
    summary, ``montecarlo.violation_report``) inherits the guarantee.
    """
    c = fleet.chain
    if fleet.num_points is not None:
        m_sel = jnp.minimum(m_sel, fleet.num_points - 1)
    take = lambda a: jnp.take_along_axis(a, m_sel[:, None], axis=-1)[:, 0]
    return Selected(
        d_bits=take(c.d_bits),
        w_flops=take(c.w_flops),
        g_eff=take(c.g_eff),
        v_loc=take(c.v_loc),
        t_vm=take(c.t_vm),
        v_vm=take(c.v_vm),
    )


def deadline_budget(sel: Selected, deadline, eps, sigma_model="cantelli", ub_k=0.0):
    """D' = D − t̄_vm − σ(ε)·√(v_loc+v_vm) − ub_k·(√v_loc+√v_vm).

    The local+offload time must fit inside D'. ``ub_k`` > 0 implements the
    worst-case baseline (§VI: "upper bound of t_loc and t_vm"): means are
    replaced by mean + ub_k·std and no probabilistic slack is taken.
    """
    sig = ccp.SIGMA_FNS[sigma_model](eps)
    return (
        deadline
        - sel.t_vm
        - sig * jnp.sqrt(jnp.maximum(sel.v_loc + sel.v_vm, 0.0))
        - ub_k * (jnp.sqrt(jnp.maximum(sel.v_loc, 0.0)) + jnp.sqrt(jnp.maximum(sel.v_vm, 0.0)))
    )


def _budget_eff(b, budget, d, p_tx, gain, sigma, v_base, channel_cv):
    """Effective ECR budget at bandwidth b (paper footnote 2).

    With channel uncertainty (``channel_cv`` > 0) the offload time is
    random too: Var[T] = v_base + v_off(b) and the budget shrinks by
    σ·(√(v_base+v_off(b)) − √v_base). ``channel_cv`` is a static Python
    float, so the branch resolves at trace time.
    """
    if channel_cv <= 0.0:
        return budget
    std_off = channel.offload_time_std(d, b, p_tx, gain, channel_cv)
    return budget - sigma * (
        jnp.sqrt(jnp.maximum(v_base + std_off**2, 0.0))
        - jnp.sqrt(jnp.maximum(v_base, 0.0))
    )


def _device_invariants(budget, d, w, g, f_max, p_tx, gain, B):
    """λ-invariant per-device quantities of the dual inner problem.

    The feasible-bandwidth bracket and the feasibility flag depend only on
    (budget, chain, link) — not on the bandwidth price λ — so they are
    computed once per ``allocate`` call and reused across all ~60 dual
    bisection steps (the λ search then only re-runs the golden section).
    """
    # Smallest feasible b: R(b) ≥ d / (budget − w/(g·f_max)).
    slack_at_fmax = budget - w / (jnp.maximum(g, 1e-30) * f_max)
    need_rate = d / jnp.maximum(slack_at_fmax, 1e-12)
    rate_fn = lambda b: channel.uplink_rate(b, p_tx, gain) - need_rate
    b_feas = bisect(rate_fn, _TINY_B, B)
    feasible = (slack_at_fmax > 0.0) & (channel.uplink_rate(B, p_tx, gain) >= need_rate)
    b_lo = jnp.where(feasible, jnp.minimum(b_feas * (1.0 + 1e-9) + _TINY_B, B), B * 0.5)
    return b_lo, feasible


def _device_best_b_at(lam, budget, d, w, g, kappa, f_min, f_max, p_tx, gain, B,
                      b_lo, feas0, sigma=0.0, v_base=0.0, channel_cv=0.0):
    """Optimal (b, f, feasible) for one device at bandwidth price λ, given
    the precomputed λ-invariants from ``_device_invariants``.

    For fixed b: t_off = d/R(b); the deadline forces
    f ≥ f_req(b) = w / (g·(budget_eff(b) − t_off)); energy rises with f, so
    f*(b) = clip(f_req, f_min, f_max). The remaining 1-D problem in b is
    convex (1/R is convex); we restrict to the feasible interval
    [b_lo, B]. The golden search handles the (quasi-convex) extra term
    that channel uncertainty adds to budget_eff.
    """
    beff = lambda b: _budget_eff(b, budget, d, p_tx, gain, sigma, v_base, channel_cv)

    def cost_fn(b):
        t_off = channel.offload_time(d, b, p_tx, gain)
        local_slack = jnp.maximum(beff(b) - t_off, 1e-12)
        f_req = w / (jnp.maximum(g, 1e-30) * local_slack)
        f = jnp.clip(f_req, f_min, f_max)
        e = energy.expected_local_energy(kappa, w, g, f) + channel.offload_energy(
            d, b, p_tx, gain
        )
        return e + lam * b

    b_star = golden_section(cost_fn, b_lo, B)
    t_off = channel.offload_time(d, b_star, p_tx, gain)
    local_slack = jnp.maximum(beff(b_star) - t_off, 1e-12)
    f_req = w / (jnp.maximum(g, 1e-30) * local_slack)
    f_star = jnp.clip(f_req, f_min, f_max)
    t_loc = energy.mean_local_time(w, g, f_star)
    feasible = feas0 & (t_loc + t_off <= beff(b_star) + 1e-9)
    return b_star, f_star, feasible


class AllocPrep(NamedTuple):
    """λ-invariant per-device state of the dual inner problem — everything
    downstream of the partition gather that does not depend on the price.

    Self-contained on purpose (platform/link columns ride along): the
    per-λ solve and the finalize step read *only* this record, so the
    group-sharded path (``core.decompose``) can concatenate per-group
    preps into fleet order and run the identical global finalize without
    ever materializing a cross-group padded ``Fleet``.
    """

    sel: Selected  # (N,) chain columns at the partition point
    budget: jnp.ndarray  # (N,) deadline budget D'
    sigma: jnp.ndarray  # (N,) σ(ε) of the ambiguity model
    v_base: jnp.ndarray  # (N,) inference-time variance at the point
    b_lo: jnp.ndarray  # (N,) feasibility floor on b
    feas0: jnp.ndarray  # (N,) λ-invariant feasibility
    kappa: jnp.ndarray  # (N,) platform/link columns
    f_min: jnp.ndarray
    f_max: jnp.ndarray
    p_tx: jnp.ndarray
    gain: jnp.ndarray


def _alloc_prep(fleet: Fleet, m_sel, deadline, eps, B,
                sigma_model: str = "cantelli", ub_k: float = 0.0,
                channel_cv: float = 0.0) -> AllocPrep:
    """λ-invariant work (point gather, deadline budget, b_feas bisection,
    feasibility flags) — once per allocation, not once per dual-bisection
    step."""
    del channel_cv  # prep is channel-model independent (budget_eff is per-λ)
    sel = select_point(fleet, m_sel)
    budget = deadline_budget(sel, deadline, eps, sigma_model, ub_k)
    sigma = ccp.SIGMA_FNS[sigma_model](jnp.broadcast_to(
        jnp.asarray(eps, jnp.float64), (fleet.num_devices,)))
    v_base = jnp.maximum(sel.v_loc + sel.v_vm, 0.0)
    plat, link = fleet.platform, fleet.link
    b_lo, feas0 = jax.vmap(
        lambda bud, d, w, g, fmax, p, h: _device_invariants(bud, d, w, g, fmax, p, h, B)
    )(budget, sel.d_bits, sel.w_flops, sel.g_eff, plat.f_max, link.p_tx, link.gain)
    return AllocPrep(sel=sel, budget=budget, sigma=sigma, v_base=v_base,
                     b_lo=b_lo, feas0=feas0, kappa=plat.kappa,
                     f_min=plat.f_min, f_max=plat.f_max, p_tx=link.p_tx,
                     gain=link.gain)


def _alloc_solve_at(prep: AllocPrep, B, lam, channel_cv: float = 0.0):
    """Per-device optimal ``(b, f, feasible)`` at bandwidth price λ."""
    per_device = jax.vmap(
        lambda lam_, bud, d, w, g, k, fmin, fmax, p, h, blo, fe, sg, vb: _device_best_b_at(
            lam_, bud, d, w, g, k, fmin, fmax, p, h, B, blo, fe,
            sigma=sg, v_base=vb, channel_cv=channel_cv,
        ),
        in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0),
    )
    sel = prep.sel
    return per_device(
        lam,
        prep.budget,
        sel.d_bits,
        sel.w_flops,
        sel.g_eff,
        prep.kappa,
        prep.f_min,
        prep.f_max,
        prep.p_tx,
        prep.gain,
        prep.b_lo,
        prep.feas0,
        prep.sigma,
        prep.v_base,
    )


def _alloc_finalize(prep: AllocPrep, b, f, feas, B, lam, need_price,
                    channel_cv: float = 0.0, edge_capacity_s=None,
                    edge_price=None, assignment=None,
                    edge_eps=None) -> Allocation:
    """Global post-solve: floor-respecting rescale to Σb ≤ B, deadline
    recheck, edge-capacity check, energies. Shared verbatim by the
    monolithic ``allocate`` and the group-sharded path (which calls it on
    fleet-order concatenations of per-group solves)."""
    sel = prep.sel
    # If the price was active, rescale residual slack to exactly meet B
    # (bisection leaves O(1e-14 B) slack; harmless but keep Σb ≤ B exact).
    # The rescale must not push a device below its λ-invariant feasibility
    # floor b_lo: clamp to the floor and redistribute the shortfall to the
    # unclamped devices (the final _deadline_ok recheck stays the
    # authority on ``feasible``).
    total = jnp.sum(b)
    b = jnp.where(need_price & (total > B),
                  _rescale_with_floor(b, prep.b_lo, B), b)
    # The rescale shrinks b, which lengthens t_off — recheck the deadline
    # at the final (b, f) so ``feasible`` reflects what is returned.
    feas = feas & _deadline_ok(
        b, f, sel, prep.budget, prep.p_tx, prep.gain, prep.sigma,
        prep.v_base, channel_cv)

    # Shared-edge capacity: Σ occupancy at the (fixed) selected points.
    # ``edge_eps`` (static float, DESIGN.md §placement) turns the mean row
    # into the Cantelli chance-constrained row  Σ t̄ + σ_e·√(Σ v_vm) ≤ C
    # with σ_e = √((1−ε)/ε); at ``None`` the trace is untouched.
    if edge_capacity_s is not None:
        cap = jnp.asarray(edge_capacity_s, jnp.float64)
        sig_edge = placement.edge_sigma(edge_eps)
        if cap.ndim == 0:  # one shared edge (scalar path — the PR 4 goldens)
            occ = jnp.sum(sel.t_vm)
            if sig_edge > 0.0:
                occ = occ + sig_edge * jnp.sqrt(
                    jnp.maximum(jnp.sum(sel.v_vm), 0.0))
            feas = feas & (occ <= cap * (1.0 + _EDGE_CAP_RTOL))
        else:  # per-node capacity rows Σ_{n: a_n=e} t̄_vm,n ≤ C_e
            if assignment is None:
                raise ValueError(
                    "a per-node edge_capacity_s vector needs the device→node "
                    "assignment (core.placement.assign_devices)")
            e_count = cap.shape[0]
            occ_e = placement.node_loads(sel.t_vm, assignment, e_count)
            if sig_edge > 0.0:
                var_e = placement.node_loads(sel.v_vm, assignment, e_count)
                occ_e = occ_e + sig_edge * jnp.sqrt(jnp.maximum(var_e, 0.0))
            node_ok = occ_e <= cap * (1.0 + _EDGE_CAP_RTOL)
            feas = feas & node_ok[assignment]
    mu = jnp.asarray(0.0 if edge_price is None else edge_price, jnp.float64)

    e_loc = energy.expected_local_energy(prep.kappa, sel.w_flops, sel.g_eff, f)
    e_off = channel.offload_energy(sel.d_bits, b, prep.p_tx, prep.gain)
    return Allocation(b=b, f=f, e_loc=e_loc, e_off=e_off, feasible=feas,
                      lam=lam, mu=mu)


def _allocate_impl(fleet, m_sel, deadline, eps, B, sigma_model, ub_k,
                   channel_cv, edge_capacity_s, edge_price, prior_log_hi,
                   assignment=None, edge_eps=None):
    prep = _alloc_prep(fleet, m_sel, deadline, eps, B, sigma_model, ub_k,
                       channel_cv)

    (b, f, feas), log_lam, need_price, log_hi = price_search(
        lambda lam: _alloc_solve_at(prep, B, lam, channel_cv),
        lambda out: jnp.sum(out[0]), B, hi_start=prior_log_hi)
    lam = jnp.where(need_price, 10.0**log_lam, 0.0)
    alloc = _alloc_finalize(prep, b, f, feas, B, lam, need_price, channel_cv,
                            edge_capacity_s, edge_price, assignment, edge_eps)
    return alloc, log_hi


@partial(jax.jit, static_argnames=("sigma_model", "channel_cv", "edge_eps"))
def allocate(
    fleet: Fleet,
    m_sel: jnp.ndarray,
    deadline: jnp.ndarray,
    eps: jnp.ndarray,
    B: float,
    sigma_model: str = "cantelli",
    ub_k: float = 0.0,
    channel_cv: float = 0.0,
    edge_capacity_s=None,
    edge_price=None,
    prior_log_hi=None,
    assignment=None,
    edge_eps=None,
) -> Allocation:
    """Solve problem (23) by dual decomposition over Σ b_n ≤ B.

    ``channel_cv`` > 0 enables the joint inference-time + channel-state
    robustness extension (paper footnote 2).

    ``edge_capacity_s`` (traced scalar; ``None``/∞ ⇒ dedicated VMs) adds
    the shared-edge capacity check Σ_n t̄_vm(m_n) ≤ C_edge to the
    feasibility flags. At a *fixed* partition the occupancies are
    constants, so there is nothing to optimize here — the edge price μ
    that shaped the partition decision is passed in as ``edge_price``
    and recorded on the returned :class:`Allocation` next to λ.

    ``prior_log_hi`` (traced scalar, optional) warm-starts the λ-bracket
    expansion from a prior solve's bracket top — value-identical to a
    cold start (see ``price_search``). Use ``allocate_with_bracket``
    to also get the bracket top back for threading.

    ``edge_capacity_s`` may also be a per-node ``(E,)`` capacity vector
    (DESIGN.md §placement), in which case the traced ``assignment``
    (device→node, ``(N,)`` int32) selects which row each device's
    occupancy lands on and ``mu`` records the per-node price vector.
    ``edge_eps`` (static float) swaps the mean occupancy row for the
    Cantelli chance-constrained row (see ``placement.edge_sigma``).
    """
    return _allocate_impl(fleet, m_sel, deadline, eps, B, sigma_model, ub_k,
                          channel_cv, edge_capacity_s, edge_price,
                          prior_log_hi, assignment, edge_eps)[0]


@partial(jax.jit, static_argnames=("sigma_model", "channel_cv", "edge_eps"))
def allocate_with_bracket(
    fleet: Fleet,
    m_sel: jnp.ndarray,
    deadline: jnp.ndarray,
    eps: jnp.ndarray,
    B: float,
    sigma_model: str = "cantelli",
    ub_k: float = 0.0,
    channel_cv: float = 0.0,
    edge_capacity_s=None,
    edge_price=None,
    prior_log_hi=None,
    assignment=None,
    edge_eps=None,
):
    """``allocate`` that also returns the expanded λ-bracket top (log10),
    for threading across repeated solves (the Algorithm-2 alternation
    carries it through its scan so step k+1 starts at step k's bracket).
    The bracket is returned *next to* the :class:`Allocation` — not on it —
    because ``Allocation``'s flattening is a pinned pytree contract
    (``analysis.contracts.ALLOCATION_LEAVES``)."""
    return _allocate_impl(fleet, m_sel, deadline, eps, B, sigma_model, ub_k,
                          channel_cv, edge_capacity_s, edge_price,
                          prior_log_hi, assignment, edge_eps)


def _rescale_with_floor(b, b_lo, B):
    """Scale Σb down to B without crossing the feasibility floors.

    A plain ``b · (B/Σb)`` can push devices below their λ-invariant floor
    ``b_lo`` (and in principle below ``_TINY_B``). Devices that would dip
    are clamped to their floor and the remaining budget is redistributed
    pro-rata over the unclamped ones (two fixed rounds + a final scale
    recompute so Σb = Σ floors + leftover budget exactly). When no device
    dips — every healthy scenario, since the bisection leaves only
    O(1e-14·B) excess — this reduces bit-exactly to the plain rescale.
    """
    plain = b * (B / jnp.sum(b))
    floor = jnp.maximum(jnp.minimum(b_lo, b), _TINY_B)
    low = plain < floor
    for _ in range(2):
        avail = jnp.maximum(B - jnp.sum(jnp.where(low, floor, 0.0)), 0.0)
        denom = jnp.sum(jnp.where(low, 0.0, b))
        low = low | (b * (avail / jnp.maximum(denom, _TINY_B)) < floor)
    avail = jnp.maximum(B - jnp.sum(jnp.where(low, floor, 0.0)), 0.0)
    denom = jnp.sum(jnp.where(low, 0.0, b))
    out = jnp.where(low, floor, b * (avail / jnp.maximum(denom, _TINY_B)))
    # The floors themselves may overrun B (over-subscribed scenario: not
    # every device can meet its deadline at once). Σb ≤ B is the hard
    # physical constraint, so fall back to the plain proportional rescale
    # and let the deadline recheck flag the casualties.
    floors_fit = jnp.sum(jnp.where(low, floor, 0.0)) <= B
    return jnp.where(floors_fit, out, plain)


def _deadline_ok(b, f, sel: Selected, budget, p_tx, gain, sigma, v_base,
                 channel_cv=0.0, tol=1e-9):
    """ECR deadline check t_loc(f) + t_off(b) ≤ budget_eff(b) at given (b, f)."""
    t_off = channel.offload_time(sel.d_bits, b, p_tx, gain)
    t_loc = energy.mean_local_time(sel.w_flops, sel.g_eff, f)
    beff = _budget_eff(b, budget, sel.d_bits, p_tx, gain, sigma, v_base, channel_cv)
    return t_loc + t_off <= beff + tol


def allocate_ipm(  # analyze: ok(TRC001,TRC002,TRC003): host cross-check utility (barrier reference path), never jitted
    fleet: Fleet,
    m_sel: jnp.ndarray,
    deadline: jnp.ndarray,
    eps: jnp.ndarray,
    B: float,
    sigma_model: str = "cantelli",
    init: Allocation | None = None,
    edge_capacity_s=None,
    assignment=None,
    edge_eps: float | None = None,
) -> Allocation:
    """Paper-faithful joint interior-point solve of (23) (for cross-checks).

    Variables are scaled: β = b/B ∈ (0,1], φ = f/f_max ∈ [f_min/f_max, 1].

    This rides the *dense* autodiff barrier on purpose: unlike the PCCP
    inner problem (36), problem (23) is not of the structured family
    ``fi = C z + c0 + q(z)`` — its deadline rows contain t_off = d/R(b)
    with the log-rate R, non-affine and non-quadratic in b — so the
    closed-form path of ``solvers/ipm.py`` does not apply. It still gets
    the shared solver improvements: scale-aware Tikhonov regularization
    and the Newton-decrement early exit (``gate_tol``), which cuts the
    12×20 fixed Newton-step budget down to the steps that actually move
    the iterate.

    ``edge_capacity_s`` (concrete host float or per-node array — this is a
    test/cross-check utility) appends the shared-edge capacity row
    Σ t̄_vm(m_n) − C ≤ 0 — one row per finite node when a capacity vector
    and its ``assignment`` are given, with the Cantelli variance term
    σ_edge·√(Σ v_vm) added under ``edge_eps``. At fixed m each row is a
    constant: strictly satisfied it is inert in the barrier (certifying
    that the capacity does not distort the (b, f) optimum); violated it
    poisons the barrier, so it is validated here and raised as an error
    instead.
    """
    sel = select_point(fleet, m_sel)
    budget = deadline_budget(sel, deadline, eps, sigma_model)
    plat, link = fleet.platform, fleet.link
    n = fleet.num_devices
    sig_edge = placement.edge_sigma(edge_eps)

    def _eff_occ(occ_sum, var_sum):
        return occ_sum + sig_edge * np.sqrt(max(var_sum, 0.0))

    cap = None  # scalar capacity row
    cap_vec = a_host = None  # per-node capacity rows
    occ_host = np.asarray(sel.t_vm, np.float64)
    var_host = np.asarray(sel.v_vm, np.float64)
    if edge_capacity_s is not None:
        cap_arr = np.asarray(edge_capacity_s, np.float64)
        if cap_arr.ndim == 0:
            if np.isfinite(cap_arr):
                cap = float(cap_arr)
                occ_total = _eff_occ(float(np.sum(occ_host)),
                                     float(np.sum(var_host)))
                if occ_total > cap * (1.0 + _EDGE_CAP_RTOL):
                    raise ValueError(
                        f"allocate_ipm: partition occupies {occ_total:.6g} s of the "
                        f"shared edge but edge_capacity_s={cap:.6g} s — the capacity "
                        "constraint is violated at this fixed m_sel (the occupancy "
                        "row would poison the barrier); re-plan with the edge price "
                        "before cross-checking")
        else:
            if assignment is None:
                raise ValueError(
                    "allocate_ipm: a per-node edge_capacity_s vector needs "
                    "the device→node assignment (pass plan.assignment)")
            cap_vec = cap_arr
            a_host = np.asarray(assignment, np.int64)
            for e in range(cap_vec.shape[0]):
                if not np.isfinite(cap_vec[e]):
                    continue
                mask = a_host == e
                occ_e = _eff_occ(float(np.sum(occ_host[mask])),
                                 float(np.sum(var_host[mask])))
                if occ_e > cap_vec[e] * (1.0 + _EDGE_CAP_RTOL):
                    raise ValueError(
                        f"allocate_ipm: node {e} occupies {occ_e:.6g} s but its "
                        f"edge capacity is {cap_vec[e]:.6g} s — the capacity "
                        "constraint is violated at this fixed (m_sel, assignment); "
                        "re-plan with the per-node prices before cross-checking")

    if init is None:
        init = allocate(fleet, m_sel, deadline, eps, B, sigma_model,
                        edge_capacity_s=edge_capacity_s,
                        assignment=assignment, edge_eps=edge_eps)

    def unpack(z):
        return z[:n] * B, z[n:] * plat.f_max  # b, f

    def objective(z):
        b, f = unpack(z)
        e_loc = energy.expected_local_energy(plat.kappa, sel.w_flops, sel.g_eff, f)
        e_off = channel.offload_energy(sel.d_bits, b, link.p_tx, link.gain)
        return jnp.sum(e_loc + e_off)

    def inequalities(z):
        b, f = unpack(z)
        t_loc = energy.mean_local_time(sel.w_flops, sel.g_eff, f)
        t_off = channel.offload_time(sel.d_bits, b, link.p_tx, link.gain)
        ddl = t_loc + t_off - budget  # ≤ 0
        rows = [
            ddl,
            (jnp.sum(b) - B)[None],
            _TINY_B - b,
            plat.f_min - f,
            f - plat.f_max,
        ]
        if cap is not None:
            # Shared-edge capacity row: constant at fixed m, hence inert
            # in the barrier. The barrier needs it STRICTLY negative, but
            # the validation above tolerates occ up to cap·(1+rtol) (the
            # same tolerance the planner's primal check uses), so the row
            # is written against cap·(1+2·rtol): any occupancy that
            # passed the guard sits strictly inside it.
            cap_eff = cap * (1.0 + 2.0 * _EDGE_CAP_RTOL)
            occ_row = jnp.sum(sel.t_vm)
            if sig_edge > 0.0:
                occ_row = occ_row + sig_edge * jnp.sqrt(
                    jnp.maximum(jnp.sum(sel.v_vm), 0.0))
            rows.append((occ_row - cap_eff)[None])
        if cap_vec is not None:
            # One constant row per finite node (same 2·rtol headroom).
            for e in range(cap_vec.shape[0]):
                if not np.isfinite(cap_vec[e]):
                    continue
                mask = jnp.asarray(a_host == e)
                occ_row = jnp.sum(jnp.where(mask, sel.t_vm, 0.0))
                if sig_edge > 0.0:
                    occ_row = occ_row + sig_edge * jnp.sqrt(jnp.maximum(
                        jnp.sum(jnp.where(mask, sel.v_vm, 0.0)), 0.0))
                cap_eff = cap_vec[e] * (1.0 + 2.0 * _EDGE_CAP_RTOL)
                rows.append((occ_row - cap_eff)[None])
        return jnp.concatenate(rows)

    # Strictly feasible start: nudge the dual solution into the interior.
    b0 = jnp.clip(init.b, _TINY_B * 2, B)
    b0 = b0 * jnp.minimum(1.0, 0.999 * B / jnp.sum(b0))
    f0 = jnp.clip(init.f * 1.02, plat.f_min * 1.0001, plat.f_max * 0.9999)
    z0 = jnp.concatenate([b0 / B, f0 / plat.f_max])

    res = barrier_solve(
        BarrierSpec(objective=objective, inequalities=inequalities),
        z0,
        t0=1e2,
        mu=10.0,
        outer_iters=12,
        newton_iters=20,
        gate_tol=1e-13,
    )
    b, f = unpack(res.z)
    e_loc = energy.expected_local_energy(plat.kappa, sel.w_flops, sel.g_eff, f)
    e_off = channel.offload_energy(sel.d_bits, b, link.p_tx, link.gain)
    return Allocation(b=b, f=f, e_loc=e_loc, e_off=e_off,
                      feasible=init.feasible, lam=init.lam, mu=init.mu)

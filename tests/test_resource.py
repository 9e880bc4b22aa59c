"""Resource-allocation subproblem: dual solver vs paper-faithful IPM."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_tables import alexnet_fleet, resnet152_fleet
from repro.core import allocate, allocate_ipm
from repro.core.resource import deadline_budget, select_point
from repro.core import channel, energy


@pytest.fixture(scope="module")
def fleet():
    return alexnet_fleet(jax.random.PRNGKey(0), 6)


def test_bandwidth_budget_respected(fleet):
    m = jnp.full((6,), 7, jnp.int32)
    a = allocate(fleet, m, 0.2, 0.02, 10e6)
    assert float(jnp.sum(a.b)) <= 10e6 * (1 + 1e-9)
    assert bool(jnp.all(a.b > 0))
    assert bool(jnp.all((a.f >= fleet.platform.f_min - 1) & (a.f <= fleet.platform.f_max + 1)))


def test_deadline_met_in_expectation_with_margin(fleet):
    m = jnp.full((6,), 7, jnp.int32)
    a = allocate(fleet, m, 0.2, 0.02, 10e6)
    sel = select_point(fleet, m)
    t = (
        energy.mean_local_time(sel.w_flops, sel.g_eff, a.f)
        + channel.offload_time(sel.d_bits, a.b, fleet.link.p_tx, fleet.link.gain)
    )
    budget = deadline_budget(sel, jnp.full((6,), 0.2), jnp.full((6,), 0.02))
    assert bool(jnp.all(t <= budget + 1e-9))


def test_dual_matches_interior_point(fleet):
    """Strong duality: the dual-decomposition optimum equals the paper's
    joint IPM optimum (within solver tolerance)."""
    m = jnp.full((6,), 7, jnp.int32)
    a = allocate(fleet, m, 0.2, 0.02, 10e6)
    b = allocate_ipm(fleet, m, jnp.full((6,), 0.2), jnp.full((6,), 0.02), 10e6)
    ea, eb = float(jnp.sum(a.energy)), float(jnp.sum(b.energy))
    assert abs(ea - eb) / max(ea, 1e-12) < 5e-3, (ea, eb)
    # IPM can only be >= (dual gives the true optimum; IPM feasible)
    assert eb >= ea - 1e-6


def test_energy_monotone_in_deadline(fleet):
    m = jnp.full((6,), 7, jnp.int32)
    es = []
    for d in (0.16, 0.2, 0.26):
        a = allocate(fleet, m, d, 0.02, 10e6)
        es.append(float(jnp.sum(a.energy)))
    assert es[0] >= es[1] >= es[2]


def test_infeasible_point_flagged():
    fleet = resnet152_fleet(jax.random.PRNGKey(1), 4)
    m = jnp.full((4,), 9, jnp.int32)  # full local
    a = allocate(fleet, m, 0.001, 0.02, 30e6)  # 1 ms deadline: impossible
    assert not bool(jnp.any(a.feasible))


def test_feasible_flag_consistent_with_returned_bandwidth(fleet):
    """Regression: the final Σb ≤ B rescale shrinks b (lengthening t_off);
    ``feasible`` must be rechecked against the *returned* (b, f), not the
    pre-rescale solution. Tight B makes the price active so the rescale
    actually fires."""
    m = jnp.full((6,), 7, jnp.int32)
    for B in (2e6, 5e6, 10e6):
        a = allocate(fleet, m, 0.2, 0.02, B)
        sel = select_point(fleet, m)
        t = (
            energy.mean_local_time(sel.w_flops, sel.g_eff, a.f)
            + channel.offload_time(sel.d_bits, a.b, fleet.link.p_tx, fleet.link.gain)
        )
        budget = deadline_budget(sel, jnp.full((6,), 0.2), jnp.full((6,), 0.02))
        ok = np.asarray(t <= budget + 1e-9)
        assert np.array_equal(np.asarray(a.feasible), np.asarray(a.feasible) & ok)


def test_dual_bracket_expands_beyond_seed_range(fleet):
    """Regression (ISSUE 4): the seed's hard-coded bisection bracket
    pinned λ at 10² on extreme bandwidth-starved scenarios and silently
    masked the unmet budget behind the rescale. With a huge deadline and
    a few-dozen-Hz budget the true market-clearing price is ≫ 10²: the
    expanded bracket must find it, clear Σb ≤ B by *pricing* (not by
    rescaling), and still match the joint IPM optimum."""
    m = jnp.full((6,), 7, jnp.int32)
    D, B = 2000.0, 36.0
    a = allocate(fleet, m, D, 0.02, B)
    assert float(a.lam) > 100.0  # beyond the seed bracket top
    assert float(jnp.sum(a.b)) <= B * (1 + 1e-9)
    assert bool(a.feasible.all())
    ai = allocate_ipm(fleet, m, jnp.full((6,), D), jnp.full((6,), 0.02), B)
    ea, eb = float(jnp.sum(a.energy)), float(jnp.sum(ai.energy))
    assert abs(ea - eb) / max(ea, 1e-12) < 5e-3, (ea, eb)


def test_rescale_respects_feasibility_floor():
    """Unit contract of the post-bisection rescale: devices are never
    pushed below their λ-invariant floor while the floors fit in B (the
    shortfall moves to unclamped devices), and Σb comes out ≤ B."""
    from repro.core.resource import _rescale_with_floor

    b = jnp.asarray([10.0, 10.0, 2.0])
    b_lo = jnp.asarray([1.0, 1.0, 1.9])
    out = np.asarray(_rescale_with_floor(b, b_lo, 11.0))
    assert out[2] == 1.9  # clamped at its floor, not at 2*(11/22)=1.0
    np.testing.assert_allclose(out.sum(), 11.0, rtol=1e-12)
    assert out[0] == out[1] and out[0] < 10.0 * (11.0 / 22.0) + 1e-12

    # no device dips below its floor -> bit-exactly the plain rescale
    b = jnp.asarray([8.0, 4.0])
    b_lo = jnp.asarray([1.0, 1.0])
    out = np.asarray(_rescale_with_floor(b, b_lo, 6.0))
    np.testing.assert_array_equal(out, np.asarray(b * (6.0 / jnp.sum(b))))

    # floors that overrun B fall back to the plain rescale (Σb <= B is the
    # hard constraint; the deadline recheck flags the casualties)
    b = jnp.asarray([5.0, 5.0])
    b_lo = jnp.asarray([4.0, 4.0])
    out = np.asarray(_rescale_with_floor(b, b_lo, 6.0))
    np.testing.assert_array_equal(out, np.asarray(b * (6.0 / jnp.sum(b))))


def test_deadline_recheck_flags_shrunken_bandwidth(fleet):
    """Unit check of the recheck predicate: halving an exactly-binding b
    must flip the deadline check to False."""
    from repro.core.resource import _deadline_ok
    m = jnp.full((6,), 7, jnp.int32)
    a = allocate(fleet, m, 0.2, 0.02, 10e6)
    sel = select_point(fleet, m)
    budget = deadline_budget(sel, jnp.full((6,), 0.2), jnp.full((6,), 0.02))
    sigma = jnp.zeros((6,))
    v_base = jnp.zeros((6,))
    ok_full = _deadline_ok(a.b, a.f, sel, budget, fleet.link.p_tx,
                           fleet.link.gain, sigma, v_base)
    assert bool(jnp.all(ok_full == a.feasible)) or bool(jnp.all(ok_full))
    ok_half = _deadline_ok(0.5 * a.b, a.f, sel, budget, fleet.link.p_tx,
                           fleet.link.gain, sigma, v_base)
    # the allocator drives (b, f) onto the deadline, so halving b must
    # violate it wherever the constraint was active
    assert not bool(jnp.all(ok_half))


def test_bracket_warm_start_value_identical(fleet):
    """``allocate_with_bracket`` threads the λ-bracket top across repeated
    solves (the Algorithm-2 alternation and the group-sharded planner's
    price loop both carry it). Reuse must be value-IDENTICAL to a cold
    start — not merely close — because the warm expansion snaps to the
    same log-price grid the cold walk uses and contracts to the same
    canonical top, whether the prior bracket is far too high, spot-on,
    or far too low for the new scenario."""
    from repro.core.resource import allocate_with_bracket

    m = jnp.full((6,), 7, jnp.int32)
    # a bandwidth-starved scenario whose clearing price sits far up the
    # grid (λ > 100: beyond the pre-expansion seed bracket)
    starved, hi_starved = allocate_with_bracket(fleet, m, 2000.0, 0.02, 36.0)
    assert float(starved.lam) > 100.0
    cold, hi_cold = allocate_with_bracket(fleet, m, 0.2, 0.02, 10e6)
    assert float(hi_starved) > float(hi_cold)

    def assert_identical(a, b):
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    # over-wide prior (starved bracket) on the easy scenario: contracts
    # back to the cold top, bit-identical allocation
    warm, hi_warm = allocate_with_bracket(fleet, m, 0.2, 0.02, 10e6,
                                          prior_log_hi=hi_starved)
    assert float(hi_warm) == float(hi_cold)
    assert_identical(warm, cold)
    # under-wide prior (easy bracket) on the starved scenario: re-expands
    # to the starved top, bit-identical allocation
    warm2, hi_warm2 = allocate_with_bracket(fleet, m, 2000.0, 0.02, 36.0,
                                            prior_log_hi=hi_cold)
    assert float(hi_warm2) == float(hi_starved)
    assert_identical(warm2, starved)


def _staged_price_search(total_at, cap, hi_start=None, iters=60,
                         endpoint="mid"):
    """Reference for ``price_search``: its stages written out one after
    another in numpy (need, warm snap + contraction, expansion,
    bisection), one evaluation of ``total_at`` per step."""
    from repro.core.resource import (_LOG_PRICE_HI0, _LOG_PRICE_HI_MAX,
                                     _LOG_PRICE_LO, _LOG_PRICE_STEP)

    excess = lambda x: total_at(10.0**x) - cap
    need = total_at(0.0) > cap
    hi0 = _LOG_PRICE_HI0
    if hi_start is None:
        hi = hi0
        f_hi = excess(hi)
    else:
        k = np.round((hi_start - hi0) / _LOG_PRICE_STEP)
        k_max = (_LOG_PRICE_HI_MAX - _LOG_PRICE_HI0) // _LOG_PRICE_STEP
        hi = hi0 + np.clip(k, 0.0, k_max) * _LOG_PRICE_STEP
        f_hi = excess(hi)
        while hi > hi0 + 1e-9:
            f_dn = excess(hi - _LOG_PRICE_STEP)
            if f_dn > 0.0:
                break
            hi, f_hi = hi - _LOG_PRICE_STEP, f_dn
    while f_hi > 0.0 and hi < _LOG_PRICE_HI_MAX - 1e-9:
        hi += _LOG_PRICE_STEP
        f_hi = excess(hi)
    lo, b_hi = _LOG_PRICE_LO, hi
    f_lo = excess(lo)
    for _ in range(iters):
        mid = 0.5 * (lo + b_hi)
        f_mid = excess(mid)
        if np.sign(f_mid) == np.sign(f_lo):
            lo, f_lo = mid, f_mid
        else:
            b_hi = mid
    log_p = b_hi if endpoint == "hi" else 0.5 * (lo + b_hi)
    return log_p, need, hi


@pytest.mark.parametrize("endpoint", ["mid", "hi"])
@pytest.mark.parametrize("hi_start", [None, 2.0, 9.0, 18.0])
@pytest.mark.parametrize("cap", [0.5, 3.0, 1e-9, 50.0])
def test_price_search_matches_staged_reference(cap, hi_start, endpoint):
    """``price_search`` runs need → bracket → bisection → final solve in
    one loop with one evaluation site; it must land where the stages
    written out one by one land (a priced demand 1 + 1e6/(1 + p) falls
    with the price p; cap 1e-9 cannot be cleared even at the top price,
    cap 50 needs no price)."""
    from repro.core.resource import price_search

    demand = lambda p: 1.0 + 1e6 / (1.0 + p)
    out, log_p, need, log_hi = price_search(
        lambda p: (demand(p), 2.0 * p), lambda o: o[0], cap,
        hi_start=hi_start, endpoint=endpoint)
    want_p, want_need, want_hi = _staged_price_search(
        demand, cap, hi_start=hi_start, endpoint=endpoint)
    assert bool(need) == bool(want_need)
    assert float(log_hi) == want_hi
    np.testing.assert_allclose(float(log_p), want_p, rtol=1e-12)
    price = 10.0**float(log_p) if want_need else 0.0
    np.testing.assert_allclose(np.asarray(out[1]), 2.0 * price, rtol=1e-12)
    _, _, _, cold_hi = price_search(
        lambda p: (demand(p), p), lambda o: o[0], cap, endpoint=endpoint)
    assert float(log_hi) == float(cold_hi)  # warm start is value-identical


def test_price_search_without_final_solve():
    from repro.core.resource import price_search

    out, log_p, need, _ = price_search(lambda p: 4.0 / (1.0 + p),
                                       lambda o: o, 1.0, endpoint="hi",
                                       final=False)
    assert out is None and bool(need)
    assert 4.0 / (1.0 + 10.0**float(log_p)) <= 1.0  # upper end clears


def _pairwise_golden(fn, lo, hi, iters=72):
    """Plain reference: the golden section that evaluates ``fn`` at both
    interior points of every bracket (no point carried between steps)."""
    from repro.solvers.scalar import _INV_PHI, _INV_PHI2

    def body(_, ab):
        a, b = ab
        h = b - a
        c, d = a + _INV_PHI2 * h, a + _INV_PHI * h
        keep_left = fn(c) < fn(d)
        return jnp.where(keep_left, a, c), jnp.where(keep_left, d, b)

    a, b = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return 0.5 * (a + b)


def _priced_cost(b, lam, budget, d, w, g, kappa, f_min, f_max, p_tx, gain):
    """e(b) + λ·b of one device: the least clock that meets the budget at
    b, its local energy, the offload energy and the bandwidth's price."""
    t_off = channel.offload_time(d, b, p_tx, gain)
    f = jnp.clip(w / (jnp.maximum(g, 1e-30)
                      * jnp.maximum(budget - t_off, 1e-12)), f_min, f_max)
    return (energy.expected_local_energy(kappa, w, g, f)
            + channel.offload_energy(d, b, p_tx, gain) + lam * b)


def test_best_bandwidth_no_costlier_than_pairwise_golden():
    """At fleet scale and at every price, each device's b* costs no more
    (to 1e-12) than the b* of the pairwise golden section; b itself is
    resolved only to ~√eps, so it is not pinned."""
    from repro.core.resource import _alloc_prep, _alloc_solve_at

    n, B = 512, 10e6 / 12 * 512
    fleet = alexnet_fleet(jax.random.PRNGKey(3), n)
    m = jax.random.randint(jax.random.PRNGKey(4), (n,), 0, 9)
    deadline, eps = jnp.full((n,), 0.18), jnp.full((n,), 0.02)
    prep = _alloc_prep(fleet, m, deadline, eps, B)
    sel = prep.sel
    cols = (prep.budget, sel.d_bits, sel.w_flops, sel.g_eff, prep.kappa,
            prep.f_min, prep.f_max, prep.p_tx, prep.gain)

    @jax.jit
    def costs(lam):
        b_new = _alloc_solve_at(prep, B, lam)[0]
        cost = jax.vmap(lambda b, *c: _priced_cost(b, lam, *c))
        b_old = jax.vmap(lambda lo, *c: _pairwise_golden(
            lambda b: _priced_cost(b, lam, *c), lo, B))(prep.b_lo, *cols)
        return cost(b_new, *cols), cost(b_old, *cols)

    lam_star = float(allocate(fleet, m, deadline, eps, B).lam)
    assert lam_star > 0.0  # the budget binds, so the prices below matter
    for lam in (0.0, 0.1 * lam_star, lam_star, 10.0 * lam_star):
        c_new, c_old = (np.asarray(c) for c in costs(jnp.float64(lam)))
        assert np.all(c_new <= c_old + 1e-12 * np.abs(c_old)), lam

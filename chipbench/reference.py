"""Plain reference planner, in numpy: the paper's Algorithm 2 with exact
partition steps, written from the paper (§III-V) and nothing of the
program under test.

The problem: choose per device a partition point m, a clock f and a
bandwidth b to

    minimise   Σ_n  κ_n (w/g) f_n²  +  p_n d / R_n(b_n)
    subject to w/(g f) + d/R(b) + t̄_vm + σ(ε) √(v_loc + v_vm) ≤ D  per device
               Σ_n b_n ≤ B,   f_min ≤ f_n ≤ f_max

with R(b) = b log2(1 + p h / (b N0)) and σ(ε) = √((1-ε)/ε) (Cantelli).
Algorithm 2 alternates two steps from a start point, ``outer_iters``
times, then allocates once more at the final points:

- allocation at fixed points: at a bandwidth price λ each device's
  energy + λ b is convex in b, with the least clock meeting the deadline
  in closed form, so b*(λ) is the root of a monotone derivative
  (bisection); λ is the smallest price with Σ b*(λ) ≤ B (bisection on
  log10 λ), 0 where the unpriced solve fits. Where no price can fit B
  (a start that leaves devices with no feasible allocation), the
  bandwidths are scaled down to B;
- partition at fixed (b, f): each device takes its least-energy point
  among those whose deadline margin is at most ``MARGIN_TOL_S`` (the
  allocation drives the incumbent's margin to 0), or its least-bad
  point where none is feasible.

With several starts (the multi-start spread of Fig. 10), the plan with
the fewest infeasible devices, then the least energy, is kept.
Everything runs in ``dtype``: float64 for the reference, float32 for the
control of ``chipbench.control``. Per-point arrays are (devices, points);
many fleets (R), scenarios (K) and starts (S) are planned at once, as
leading axes (R, K, S, devices) of one set of arrays.
"""
from __future__ import annotations

import multiprocessing
from concurrent.futures import ProcessPoolExecutor
from typing import NamedTuple

import numpy as np

MB_TO_BITS = 8.0e6
MS2_TO_S2 = 1.0e-6
LOG_PRICE_LO, LOG_PRICE_HI = -16.0, 18.0
PRICE_ITERS = 52
B_ITERS = 52
#: bisection steps per price probe, inside the bracket the probes so far
#: leave (b*(λ) falls as λ rises)
PROBE_ITERS = 16
MARGIN_TOL_S = 1e-9
#: allocation slack of the deadline check of a point's feasibility
ALLOC_TOL_S = 1e-9


class Deployment(NamedTuple):
    d: np.ndarray  # (N, P) bits uplinked at each point
    w: np.ndarray  # (N, P) local FLOPs of the prefix
    g: np.ndarray  # (N, P) FLOPs per cycle of the prefix
    v: np.ndarray  # (N, P) variance of local + edge time, s²
    t_vm: np.ndarray  # (N, P) mean edge time of the suffix, s
    valid: np.ndarray  # (N, P) a real point of the device's chain
    kappa: np.ndarray  # (N,)
    f_min: np.ndarray  # (N,)
    f_max: np.ndarray  # (N,)
    p_tx: np.ndarray  # (N,)
    snr_b: np.ndarray  # (R, N) p h / N0 of each request's fleet, Hz


class RefPlan(NamedTuple):
    """Plans of R fleets × K scenarios."""

    m: np.ndarray  # (R, K, N) chosen points
    b: np.ndarray  # (R, K, N) Hz
    f: np.ndarray  # (R, K, N) Hz
    energy: np.ndarray  # (R, K, N) J
    feasible: np.ndarray  # (R, K, N) bool
    total_energy: np.ndarray  # (R, K) J


def chain_columns(chain: dict, dtype=np.float64):
    """One chain's per-point columns from its config entry: the paper's
    table, and the edge-side times synthesised from the remaining work."""
    d = np.asarray(chain["d_mb"], dtype) * dtype(MB_TO_BITS)
    w = np.asarray(chain["w_gflops"], dtype) * dtype(1e9)
    g = np.asarray(chain["g_eff"], dtype)
    v_loc = np.asarray(chain["v_loc_ms2"], dtype) * dtype(MS2_TO_S2)
    t_vm = dtype(chain["vm_full_s"]) * (w[-1] - w) / max(w[-1], dtype(1.0))
    v_vm = (dtype(chain["vm_cv"]) * t_vm) ** 2
    return d, w, g, v_loc + v_vm, t_vm


def deployment(config: dict, gains, dtype=np.float64) -> Deployment:
    """The deployment of ``config`` with per-device link ``gains`` ((R, N):
    one row per request's fleet), devices in group order, chains padded
    to the widest with invalid points."""
    groups = config["groups"]
    width = max(len(gr["chain"]["d_mb"]) for gr in groups)
    cols = {k: [] for k in Deployment._fields if k != "snr_b"}
    for gr in groups:
        n, plat = int(gr["count"]), gr["platform"]
        d, w, g, v, t_vm = chain_columns(gr["chain"], dtype)
        k = len(d)
        pad = lambda a: np.concatenate([a, np.repeat(a[-1:], width - k)])
        for name, a in (("d", d), ("w", w), ("g", g), ("v", v), ("t_vm", t_vm)):
            cols[name].append(np.broadcast_to(pad(a), (n, width)))
        cols["valid"].append(np.broadcast_to(np.arange(width) < k, (n, width)))
        for name, key in (("kappa", "kappa"), ("f_min", "f_min_hz"),
                          ("f_max", "f_max_hz"), ("p_tx", "p_tx_w")):
            cols[name].append(np.full(n, plat[key], dtype))
    dep = {k: np.concatenate(v, axis=0) for k, v in cols.items()}
    n0 = dtype(10.0 ** ((config["channel"]["noise_dbm_per_hz"] - 30.0) / 10.0))
    gains = np.atleast_2d(np.asarray(gains, np.float64)).astype(dtype)
    if gains.shape[1:] != dep["kappa"].shape:
        raise ValueError(f"{gains.shape[1]} gains for {dep['kappa'].shape[0]} devices")
    return Deployment(snr_b=dep["p_tx"] * gains / n0, **dep)


def rate(b, snr_b):
    """R(b) = b log2(1 + p h / (b N0)), bit/s."""
    return b * np.log2(1 + snr_b / b)


def _bisect(fn, lo, hi, iters):
    """Per-element bisection in log space of a non-decreasing ``fn`` for
    its sign change on [lo, hi]; returns the final bracket (lo, hi)."""
    for _ in range(iters):
        mid = np.sqrt(lo * hi)
        up = fn(mid) >= 0
        hi = np.where(up, mid, hi)
        lo = np.where(up, lo, mid)
    return lo, hi


def sigma(eps):
    return np.sqrt((1 - eps) / eps)


def rescale(b, floor, B):
    """Σb scaled down to B without taking a device below its ``floor``:
    devices that would dip stay at their floor and the rest of the
    budget is shared pro rata (two rounds); where the floors alone
    overrun B, the plain proportional scale."""
    dt = b.dtype.type
    plain = b * (B / b.sum(axis=-1, keepdims=True))
    floor = np.maximum(np.minimum(floor, b), dt(1e-3))
    low = plain < floor

    def shares(low):
        avail = np.maximum(B - np.where(low, floor, 0).sum(-1, keepdims=True), 0)
        denom = np.maximum(np.where(low, 0, b).sum(-1, keepdims=True), dt(1e-3))
        return b * (avail / denom)

    for _ in range(2):
        low = low | (shares(low) < floor)
    out = np.where(low, floor, shares(low))
    fits = np.where(low, floor, 0).sum(-1, keepdims=True) <= B
    return np.where(fits, out, plain)


def _gather(a, m):
    """Per-point columns (N, P) at points ``m`` (..., N)."""
    return np.take_along_axis(np.broadcast_to(a, m.shape + a.shape[-1:]),
                              m[..., None], -1)[..., 0]


def allocate(dep: Deployment, m, deadline, eps, B):
    """Least-energy (b, f) at fixed points ``m`` ((R, K, S, N): fleets,
    scenarios, starts, devices; the scenario's ``deadline`` and ``eps``
    are (1, K, 1, 1) or per device (1, K, 1, N), ``B`` (1, K, 1, 1)). A
    device that cannot meet its deadline at its point even with all of B
    and f_max is held to [B/2, B] at f_max, and flagged. Returns
    ``(b, f, energy, feasible)``."""
    dt = dep.d.dtype.type
    d, w, g, v, t_vm = (_gather(a, m) for a in (dep.d, dep.w, dep.g, dep.v,
                                                 dep.t_vm))
    snr = dep.snr_b[:, None, None, :]
    budget = deadline - t_vm - sigma(eps) * np.sqrt(v)
    full = np.broadcast_to(B, m.shape).astype(dt)
    slack = budget - w / (g * dep.f_max)
    need = d / np.where(slack > 0, slack, dt(1))
    feas0 = (slack > 0) & (rate(full, snr) >= need)
    _, b_min = _bisect(lambda b: rate(b, snr) - need,
                       np.full(m.shape, dt(1e-3)), full, B_ITERS)
    # a feasible device's floor sits a hair above its feasibility edge
    b_lo = np.where(feas0, np.minimum(b_min * dt(1 + 1e-9) + dt(1e-3), full),
                    full / 2)

    ln2 = np.log(dt(2))
    c_loc = dt(2) * dep.kappa * w**3 / g**3  # d(local energy)/dt_left · t_left³

    def clock(b):  # the least clock that meets the deadline, unclipped
        t_left = np.maximum(budget - d / rate(b, snr), dt(1e-12))
        return w / (g * t_left), t_left

    def dcost(b, lam):  # d/db of energy + λ b: non-decreasing (convex)
        x = snr / b
        log2 = np.log1p(x) / ln2
        r = b * log2
        t_left = np.maximum(budget - d / r, dt(1e-12))
        f_req = w / (g * t_left)
        dedt = dep.p_tx + np.where((f_req > dep.f_min) & (f_req < dep.f_max),
                                   c_loc / t_left**3, dt(0))
        slope = log2 - x / ((1 + x) * ln2)  # dR/db
        return lam - d * slope / r**2 * dedt

    def solve(lam, lower, upper, iters):  # lam (R, K, S, 1)
        """b*(λ) within [lower, upper], and the bracket left around it."""
        lo, hi = _bisect(lambda b: dcost(b, lam), lower, upper, iters)
        return np.where(dcost(lower, lam) >= 0, lower, hi), lo, hi

    # λ: the smallest price whose solve fits Σ b ≤ B, per fleet, scenario
    # and start
    total = lambda b: b.sum(axis=-1, keepdims=True, dtype=dt)
    zero = np.zeros(m.shape[:-1] + (1,), dt)
    upper = solve(zero, b_lo, full, B_ITERS)[0]  # b*(0) bounds every b*(λ)
    need_price = total(upper) > B
    lower = b_lo
    lo, hi = zero + dt(LOG_PRICE_LO), zero + dt(LOG_PRICE_HI)
    for _ in range(PRICE_ITERS):
        mid = dt(0.5) * (lo + hi)
        b, b_low, b_high = solve(dt(10) ** mid, lower, upper, PROBE_ITERS)
        fits = total(b) <= B
        hi, lower = np.where(fits, mid, hi), np.where(fits, b_low, lower)
        lo, upper = np.where(fits, lo, mid), np.where(fits, upper, b_high)
    b = solve(np.where(need_price, dt(10) ** hi, dt(0)), b_lo, full, B_ITERS)[0]
    f = np.clip(clock(b)[0], dep.f_min, dep.f_max)
    b = np.where(need_price & (total(b) > B), rescale(b, b_lo, B), b)
    t_off = d / rate(b, snr)
    feasible = feas0 & (w / (g * f) + t_off <= budget + dt(ALLOC_TOL_S))
    energy = dep.kappa * (w / g) * f**2 + dep.p_tx * t_off
    return b, f, energy, feasible


def partition(dep: Deployment, b, f, deadline, eps):
    """Each device's least-energy feasible point at fixed (b, f)
    ((R, K, S, N)), or its least-bad point. Returns ``(m, feasible)``."""
    dt = dep.d.dtype.type
    b, f = b[..., None], f[..., None]
    t_off = dep.d / rate(b, dep.snr_b[:, None, None, :, None])
    energy = dep.kappa[:, None] * (dep.w / dep.g) * f**2 + dep.p_tx[:, None] * t_off
    t = dep.w / (dep.g * f) + t_off + dep.t_vm
    margin = np.where(
        dep.valid, t + sigma(eps[..., None]) * np.sqrt(dep.v) - deadline[..., None],
        dt(np.inf))
    ok = margin <= dt(MARGIN_TOL_S)
    best = np.argmin(np.where(ok, energy, np.inf), axis=-1)
    m = np.where(ok.any(axis=-1), best, np.argmin(margin, axis=-1))
    return m, np.take_along_axis(ok, m[..., None], -1)[..., 0]


def starts(dep: Deployment, multi_start: bool):
    """(S, N) start points: the spread {1, P/2, 3P/4, P-2, P-1} of the
    padded width P (or full local inference alone), clamped to each
    device's own chain."""
    p = dep.valid.shape[1]
    spread = sorted({1, p // 2, (3 * p) // 4, max(p - 2, 1), p - 1}
                    if multi_start else {p - 1})
    last = dep.valid.sum(axis=1) - 1
    return np.minimum(np.asarray(spread)[:, None], last[None, :])


def alternate(dep: Deployment, m, deadline, eps, B, outer_iters: int):
    """Algorithm 2 from the start points ``m`` ((R, K, S, N)): ``outer_iters``
    rounds of allocation and partition, then the allocation at the final
    points. Every start runs on its own; returns ``(m, b, f, energy,
    feasible)``, each (R, K, S, N)."""
    feas_part = np.ones(m.shape, bool)
    for _ in range(outer_iters):
        b, f, _, _ = allocate(dep, m, deadline, eps, B)
        m, feas_part = partition(dep, b, f, deadline, eps)
    b, f, energy, feas_alloc = allocate(dep, m, deadline, eps, B)
    return m, b, f, energy, feas_part & feas_alloc


def plan(dep: Deployment, deadlines, epss, Bs, outer_iters: int = 3,
         multi_start: bool = True) -> RefPlan:
    """The reference plans of every fleet of ``dep`` for the K scenarios:
    ``deadlines`` and ``epss`` (K,) or per device (K, N), ``Bs`` (K,).

    With several starts, each start runs ``alternate`` in a process of its
    own, spawned, and the plans are the same bits as ``alternate`` over
    every start in one process: a start's arithmetic does not depend on
    the others'. A spawned process imports the caller's ``__main__``
    again, so the caller's main module must be importable: a script file
    or a ``python -m`` module (``chipbench.run`` is), not a script read
    from standard input."""
    dt = dep.d.dtype.type

    def col(x):  # (1, K, 1, 1) or per device (1, K, 1, N)
        a = np.asarray(x, dt)
        return a.reshape(1, a.shape[0], 1, -1)

    deadline, eps, B = col(deadlines), col(epss), col(Bs)
    r, k = dep.snr_b.shape[0], deadline.shape[1]
    s0 = starts(dep, multi_start)
    m = np.broadcast_to(s0, (r, k) + s0.shape)
    if len(s0) > 1:
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(len(s0), mp_context=ctx) as pool:
            parts = list(pool.map(
                alternate, *zip(*[(dep, m[:, :, j:j + 1], deadline, eps, B,
                                   outer_iters) for j in range(len(s0))])))
        m, b, f, energy, feasible = (np.concatenate(a, axis=2)
                                     for a in zip(*parts))
    else:
        m, b, f, energy, feasible = alternate(dep, m, deadline, eps, B,
                                              outer_iters)
    total = energy.sum(axis=-1, dtype=dt)  # (R, K, S)
    bad = (~feasible).sum(axis=-1)
    best = np.argmin(np.where(bad == bad.min(axis=-1, keepdims=True), total,
                              np.inf), axis=-1)[..., None]
    pick = lambda a: np.take_along_axis(a, best[..., None], 2)[:, :, 0]
    return RefPlan(m=pick(m), b=pick(b), f=pick(f), energy=pick(energy),
                   feasible=pick(feasible),
                   total_energy=np.take_along_axis(total, best, 2)[..., 0])

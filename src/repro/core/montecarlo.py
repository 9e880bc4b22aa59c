"""Monte-Carlo validation of the probabilistic deadline guarantee.

The planner only uses (mean, variance). The guarantee must therefore hold
for *any* distribution with those moments. We validate empirically against
three plausible families (gamma, lognormal, truncated normal), matching
moments, and report the deadline-violation rate per device (Fig. 13c/14c).

``var_scale`` < 1 emulates the paper's observation that the max-over-
frequency variance (eq. 11) is conservative w.r.t. the actual operating
frequency's variance.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import channel, energy
from repro.core.blocks import Fleet
from repro.core.resource import Allocation, select_point


class ViolationReport(NamedTuple):
    rate: jnp.ndarray  # (N,) empirical P{T > D}
    mean_time: jnp.ndarray  # (N,) empirical E[T]
    p95_time: jnp.ndarray  # (N,)
    #: per-tier observed means — what a partitioned stack measures on
    #: each tier separately (device-side compute vs server-side VM time,
    #: §IV online measurement); the closed-loop moment re-fit needs them
    #: to *attribute* a latency shift to a tier instead of guessing from
    #: totals (straggler/congestion extra lands in ``mean_vm``)
    mean_local: jnp.ndarray = jnp.nan  # (N,) empirical E[t_loc]
    mean_vm: jnp.ndarray = jnp.nan  # (N,) empirical E[t_vm + extras]


def _weibull_shape_from_cv2(cv2, iters: int = 60):
    """Solve Γ(1+2/k)/Γ(1+1/k)² = 1+cv² for the Weibull shape k by
    bisection (the left side is strictly decreasing in k)."""
    target = jnp.log1p(cv2)

    def excess(k):
        return (jax.scipy.special.gammaln(1.0 + 2.0 / k)
                - 2.0 * jax.scipy.special.gammaln(1.0 + 1.0 / k) - target)

    lo = jnp.full_like(target, 0.05)
    hi = jnp.full_like(target, 50.0)

    def body(_, state):
        lo, hi = state
        mid = 0.5 * (lo + hi)
        high = excess(mid) > 0  # cv too large at mid ⇒ true k is larger
        return jnp.where(high, mid, lo), jnp.where(high, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return 0.5 * (lo + hi)


def _sample_matched(key, dist: str, mean, var, shape):
    """Sample ``shape`` values with the given mean/variance (per element).

    ``"pareto"`` / ``"weibull"`` are the heavy-tailed families used by the
    robustness layer's straggler injection (DESIGN.md §robustness): both
    are moment-matched, Pareto with tail index α = 1 + √(1 + mean²/var)
    (always > 2, so the matched variance exists), Weibull with the shape
    solved from the cv by bisection on the log-Γ moment identity.
    """
    mean = jnp.maximum(mean, 1e-12)
    var = jnp.maximum(var, 1e-18)
    if dist == "gamma":
        k = mean**2 / var
        theta = var / mean
        return jax.random.gamma(key, k, shape=shape) * theta
    if dist == "lognormal":
        s2 = jnp.log1p(var / mean**2)
        mu = jnp.log(mean) - 0.5 * s2
        return jnp.exp(mu + jnp.sqrt(s2) * jax.random.normal(key, shape))
    if dist == "truncnorm":
        x = mean + jnp.sqrt(var) * jax.random.normal(key, shape)
        return jnp.maximum(x, 0.0)
    if dist == "pareto":
        alpha = 1.0 + jnp.sqrt(1.0 + mean**2 / var)
        xm = mean * (alpha - 1.0) / alpha
        u = jax.random.uniform(key, shape, minval=1e-12)
        return xm * u ** (-1.0 / alpha)
    if dist == "weibull":
        k = _weibull_shape_from_cv2(var / mean**2)
        lam = mean * jnp.exp(-jax.scipy.special.gammaln(1.0 + 1.0 / k))
        u = jax.random.uniform(key, shape, minval=1e-12)
        return lam * (-jnp.log(u)) ** (1.0 / k)
    raise ValueError(f"unknown dist {dist!r}")


def sample_local_and_vm(key_loc, key_vm, dist: str, mean_loc, var_loc,
                        mean_vm, var_vm, shape):
    """``_sample_matched`` for the local and the VM tier, each from its
    own key: one vmapped call over the pair, so the compiled program holds
    one copy of the sampler (the gamma sampler's rejection loop is most of
    a validation program's code). Each tier's samples are those its own
    ``_sample_matched(key, ...)`` call draws."""
    return jax.vmap(lambda k, m, v: _sample_matched(k, dist, m, v, shape))(
        jnp.stack([key_loc, key_vm]), jnp.stack([mean_loc, mean_vm]),
        jnp.stack([var_loc, var_vm]))


@partial(jax.jit, static_argnames=("dist", "num_samples", "channel_cv"))
def violation_report(
    key,
    fleet: Fleet,
    m_sel: jnp.ndarray,
    alloc: Allocation,
    deadline: jnp.ndarray,
    dist: str = "gamma",
    num_samples: int = 20000,
    var_scale: float = 0.8,
    channel_cv: float = 0.0,
    edge_capacity_s=None,
    faults=None,
    assignment=None,
) -> ViolationReport:
    """Empirical per-device P{T > D} under moment-matched sampling.

    ``faults`` (optional) is a ``serve.faults.FaultState``-shaped pytree
    (duck-typed — this module never imports ``serve``) injecting the
    robustness layer's fault taxonomy into the ground truth: moment
    drift scales the sampled local/VM moments, a channel fade scales the
    gain, a brownout scales the shared-edge capacity, and straggler
    bursts add a Bernoulli(``straggler_prob``) × moment-matched-Pareto
    extra to each VM execution. ``faults=None`` (the default) is gated at
    trace time, so the no-fault program is bit-identical to the
    pre-robustness one (golden-pinned).

    Ragged fleets validate per device: the mask/``num_points`` leaves ride
    in through ``fleet`` (traced, not static), ``select_point`` clamps
    ``m_sel`` to each device's own chain so padded points are never
    sampled, and ``deadline`` may be per-device ``(N,)`` so mixed
    populations score against their own SLOs.

    ``edge_capacity_s`` (traced scalar; ``None``/∞ ⇒ dedicated VMs)
    enables the shared-edge ground-truth model (DESIGN.md §edge): the
    edge is a processor-sharing accelerator with a VM-time budget C per
    round, so when the plan's total occupancy Σ t̄_vm exceeds C every
    VM time stretches by the congestion factor max(1, Σ t̄_vm / C). A
    plan that keeps Σ t̄_vm ≤ C is validated unchanged — this is what
    lets the capacity-priced planner be scored against plans made under
    the dedicated or statically-scaled assumptions on equal terms.

    A per-node ``(E,)`` capacity vector congests per node (DESIGN.md
    §placement): pass the plan's device→node map via ``assignment``
    (traced ``(N,)`` int32, e.g. ``plan.assignment``) and each node e
    processor-shares among its own devices — slow_e = max(1, occ_e/C_e)
    applied to the devices assigned there.
    """
    sel = select_point(fleet, m_sel)
    gain = fleet.link.gain
    if faults is not None:
        sel = sel._replace(
            t_vm=sel.t_vm * faults.vm_mean_scale,
            v_vm=sel.v_vm * faults.vm_var_scale,
            g_eff=sel.g_eff / jnp.maximum(faults.loc_mean_scale, 1e-12),
            v_loc=sel.v_loc * faults.loc_var_scale,
        )
        gain = gain * faults.gain_scale
    if edge_capacity_s is not None:
        cap = jnp.asarray(edge_capacity_s, jnp.float64)
        if faults is not None:
            cap = cap * faults.cap_scale
        if cap.ndim == 0:
            slow = jnp.maximum(1.0, jnp.sum(sel.t_vm) / cap)
        else:
            if assignment is None:
                raise ValueError(
                    "a per-node edge_capacity_s vector needs the plan's "
                    "device→node assignment (pass assignment=plan.assignment)")
            a = jnp.asarray(assignment, jnp.int32)
            occ_e = jax.ops.segment_sum(sel.t_vm, a, num_segments=cap.shape[0])
            slow_e = jnp.maximum(1.0, occ_e / jnp.maximum(cap, 1e-30))
            slow = slow_e[a]
        sel = sel._replace(t_vm=sel.t_vm * slow, v_vm=sel.v_vm * slow**2)
    n = m_sel.shape[0]
    mean_loc = energy.mean_local_time(sel.w_flops, sel.g_eff, alloc.f)

    k_loc, k_vm, k_ch = jax.random.split(key, 3)
    if channel_cv > 0.0:
        # lognormal channel gain with the given cv (paper footnote 2)
        s2 = jnp.log1p(channel_cv**2)
        gains = gain[None, :] * jnp.exp(
            jnp.sqrt(s2) * jax.random.normal(k_ch, (num_samples, n)) - 0.5 * s2)
        t_off = channel.offload_time(sel.d_bits[None, :], alloc.b[None, :],
                                     fleet.link.p_tx[None, :], gains)
    else:
        t_off = channel.offload_time(sel.d_bits, alloc.b, fleet.link.p_tx,
                                     gain)[None, :]
    shape = (num_samples, n)
    s_loc, s_vm = sample_local_and_vm(k_loc, k_vm, dist, mean_loc,
                                      var_scale * sel.v_loc, sel.t_vm,
                                      var_scale * sel.v_vm, shape)
    t_loc = jnp.where(sel.w_flops[None, :] > 0, s_loc, 0.0)
    t_vm = jnp.where(sel.t_vm[None, :] > 0, s_vm, 0.0)
    if faults is not None:
        # Straggler bursts: keys derived by fold_in so the 3-way split
        # above (and hence the no-fault sample stream) stays unchanged.
        k_hit, k_extra = jax.random.split(jax.random.fold_in(key, 0x57), 2)
        p_straggle = jnp.clip(faults.straggler_prob, 0.0, 1.0)
        hit = jax.random.bernoulli(k_hit, p_straggle, shape)
        extra_mean = jnp.maximum(faults.straggler_extra_s, 1e-9)
        extra_var = (jnp.maximum(faults.straggler_cv, 1e-3) * extra_mean) ** 2
        extra = _sample_matched(k_extra, "pareto", extra_mean, extra_var, shape)
        t_vm = t_vm + jnp.where(hit & (sel.t_vm[None, :] > 0), extra, 0.0)
    total = t_loc + t_off + t_vm
    deadline = jnp.broadcast_to(jnp.asarray(deadline, jnp.float64), (n,))
    return ViolationReport(
        # dtype pinned: jnp.mean over bool otherwise lands on float32
        # even inside the x64 island (analysis contract: float64 outputs)
        rate=jnp.mean(total > deadline[None, :], axis=0, dtype=jnp.float64),
        mean_time=jnp.mean(total, axis=0),
        p95_time=jnp.percentile(total, 95.0, axis=0),
        mean_local=jnp.mean(t_loc, axis=0),
        mean_vm=jnp.mean(t_vm, axis=0),
    )

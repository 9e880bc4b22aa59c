"""Trace-driven workload replay (DESIGN.md §robustness).

The step-driven closed loop (``serve.closedloop``) validates the ladder
against hand-picked incidents; this module replays *traffic*. A seeded
:class:`Trace` — Poisson, diurnal, or bursty arrivals over a fleet, with
per-population job mixes — is served epoch by epoch through the same
controller stack: every request's ground-truth latency is sampled from
the faulted moment model (request-granular mirror of
``montecarlo.violation_report``), completions stream into
:class:`~repro.serve.engine.EngineStats`, the binomial-tail sentinel
watches the per-epoch windows, and on a trip the degradation ladder
escalates exactly as in the step harness (price step → warm re-plan →
contingency).

What the replay adds over the step harness:

- **event-driven load** — per-epoch request counts follow the arrival
  process, so shared-edge congestion tracks *demand*, not one
  request/device/round: a burst congests, a lull relaxes;
- **per-node faults + migration** — on a multi-node edge the
  observable-only per-node capacity re-fit
  (``closedloop._refit_node_scales``) shrinks a degraded node's
  estimated budget, so the ladder's re-plan re-runs the ``hybrid``
  allocator and *migrates* that node's devices; churn and the energy of
  each migration (one extra upload of the offload payload,
  t_off·p_tx) are metered;
- **regret vs oracle** — :func:`replay` with ``oracle=True`` re-plans
  each epoch against the *true* faulted fleet and capacity (it reads
  the schedule the controller never sees); :func:`regret_curves` turns
  a paired (actual, oracle) run into cumulative energy/violation regret
  per epoch;
- **engine-backed mode** — :func:`replay_engine` drives the *real*
  :class:`~repro.serve.engine.ServingEngine` through a trace, window
  per epoch, and re-profiles the edge-tier chain from observed decode
  completions via ``partitioned.measured_chain`` (the §IV online path),
  which is exactly the measurement the EWMA re-fit consumes.

Queueing is out of scope: a request's latency is its *service* time
under the epoch's fault state and congestion level, scored against the
scenario SLO — the same contract the planner's guarantee covers.

One compiled program serves the whole trace: per-epoch request batches
are padded to the trace's static ``capacity`` (power-of-two bucket of
the max per-epoch arrivals) with a traced ``valid`` mask and traced
``device_ids``, so value-varied epochs — different counts, different
devices, different fault states — never recompile
(``replay_recompile_drill`` in ``make analyze`` pins this).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel, energy
from repro.core.api import Planner, Scenario
from repro.core.blocks import Fleet
from repro.core.montecarlo import _sample_matched, sample_local_and_vm
from repro.core.placement import assignment_churn, migration_energy
from repro.core.planner import plan_fixed_partition
from repro.core.resource import Allocation, select_point
from repro.serve.closedloop import (
    GuardConfig,
    RUNG_NONE,
    RUNG_PRICE,
    RUNG_REPLAN,
    _predicted_components,
    _refit_node_scales,
    _refit_scales,
    _refit_state,
)
from repro.serve.engine import EngineStats, Request, ServingEngine
from repro.serve.faults import (
    FaultSchedule,
    apply_faults,
    faulted_capacity,
    state_at,
)
from repro.serve.guard import ViolationSentinel, contingency_plans, pick_contingency
from repro.serve.partitioned import measured_chain

__all__ = [
    "Trace", "poisson_trace", "diurnal_trace", "bursty_trace",
    "population_mix", "EpochSample", "sample_epoch", "ReplayResult",
    "replay", "regret_curves", "replay_engine",
]


# ---------------------------------------------------------------------------
# Traces: seeded arrival processes (host-side numpy — trace *construction*
# is data prep, not compiled work; the replay consumes it in static-shape
# padded slices)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Trace:
    """A reproducible request trace over a fleet.

    ``arrival_s`` is sorted; ``device_id[r]`` is the device request ``r``
    lands on (the job mix — per-population weights — is folded in at
    construction). ``nominal_per_epoch`` is the *design-rate* mean
    arrivals per epoch: the congestion normalizer, so an epoch at
    nominal load congests the shared edge exactly as one
    request/device/round does in ``violation_report``.
    """

    kind: str
    epoch_s: float
    epochs: int
    nominal_per_epoch: float
    arrival_s: np.ndarray  # (R,) float64, sorted
    device_id: np.ndarray  # (R,) int32

    @property
    def num_requests(self) -> int:
        return int(self.arrival_s.shape[0])

    def epoch_bounds(self) -> np.ndarray:
        """(epochs+1,) request-index offsets of each epoch's slice."""
        edges = np.arange(self.epochs + 1) * self.epoch_s
        return np.searchsorted(self.arrival_s, edges, side="left")

    @property
    def max_per_epoch(self) -> int:
        b = self.epoch_bounds()
        return int(np.max(b[1:] - b[:-1])) if self.epochs else 0

    @property
    def capacity(self) -> int:
        """Static padded batch width: the power-of-two bucket of the max
        per-epoch arrival count — ONE compiled epoch program per trace."""
        return 1 << max(self.max_per_epoch - 1, 0).bit_length()


def population_mix(pop_counts, pop_weights) -> np.ndarray:
    """Per-device sampling probabilities from a per-population job mix.

    ``pop_weights[g]`` is the share of *traffic* population ``g``
    receives (normalized here); inside a population the load spreads
    uniformly over its ``pop_counts[g]`` devices. Device order follows
    the fleet-builder convention: populations concatenated in order.
    """
    probs = []
    for c, w in zip(pop_counts, pop_weights, strict=True):
        if c <= 0:
            raise ValueError(f"population counts must be positive, got {c}")
        if w < 0:
            raise ValueError(f"mix weights must be >= 0, got {w}")
        probs += [w / c] * c
    p = np.asarray(probs, float)
    total = p.sum()
    if total <= 0:
        raise ValueError("job mix needs at least one positive weight")
    return p / total


def _materialize(kind: str, lam: np.ndarray, epoch_s: float,
                 num_devices: int, rng, device_weights,
                 nominal: float) -> Trace:
    counts = rng.poisson(np.maximum(lam, 0.0))
    chunks, devs = [], []
    for t, c in enumerate(counts):
        if c == 0:
            continue
        chunks.append(t * epoch_s + np.sort(rng.uniform(0.0, epoch_s, int(c))))
        devs.append(rng.choice(num_devices, size=int(c), p=device_weights))
    arrival = np.concatenate(chunks) if chunks else np.zeros((0,))
    device = (np.concatenate(devs) if devs else np.zeros((0,))).astype(np.int32)
    return Trace(kind=kind, epoch_s=float(epoch_s), epochs=len(counts),
                 nominal_per_epoch=float(nominal),
                 arrival_s=arrival, device_id=device)


def poisson_trace(*, rate_per_epoch: float, epochs: int, epoch_s: float,
                  num_devices: int, seed: int,
                  device_weights=None) -> Trace:
    """Homogeneous Poisson arrivals: ``rate_per_epoch`` mean requests per
    epoch across the fleet, deterministic given ``seed``."""
    rng = np.random.default_rng(seed)
    lam = np.full(epochs, float(rate_per_epoch))
    return _materialize("poisson", lam, epoch_s, num_devices, rng,
                        device_weights, rate_per_epoch)


def diurnal_trace(*, rate_per_epoch: float, epochs: int, epoch_s: float,
                  num_devices: int, seed: int, swing: float = 0.6,
                  period_epochs: Optional[int] = None,
                  device_weights=None) -> Trace:
    """Sinusoidally modulated Poisson arrivals: λ_t = λ·(1 + swing·
    sin(2πt/period)) — the day/night cycle, one period over the horizon
    by default. ``nominal_per_epoch`` stays the mean λ."""
    if not 0.0 <= swing <= 1.0:
        raise ValueError(f"swing must lie in [0, 1], got {swing}")
    rng = np.random.default_rng(seed)
    period = epochs if period_epochs is None else period_epochs
    t = np.arange(epochs, dtype=float)
    lam = rate_per_epoch * (1.0 + swing * np.sin(2.0 * np.pi * t / max(period, 1)))
    return _materialize("diurnal", lam, epoch_s, num_devices, rng,
                        device_weights, rate_per_epoch)


def bursty_trace(*, rate_per_epoch: float, epochs: int, epoch_s: float,
                 num_devices: int, seed: int, burst_factor: float = 4.0,
                 p_enter: float = 0.1, p_exit: float = 0.4,
                 device_weights=None) -> Trace:
    """Markov-modulated Poisson arrivals: a 2-state chain (calm/burst)
    flips with ``p_enter``/``p_exit`` per epoch; the burst state
    multiplies the rate by ``burst_factor``. ``nominal_per_epoch`` stays
    the *calm* rate, so a burst genuinely congests the shared edge."""
    rng = np.random.default_rng(seed)
    lam = np.empty(epochs)
    burst = False
    for t in range(epochs):
        burst = (rng.random() < p_enter) if not burst \
            else not (rng.random() < p_exit)
        lam[t] = rate_per_epoch * (burst_factor if burst else 1.0)
    return _materialize("bursty", lam, epoch_s, num_devices, rng,
                        device_weights, rate_per_epoch)


# ---------------------------------------------------------------------------
# The compiled epoch: request-granular faulted ground truth
# ---------------------------------------------------------------------------


class EpochSample(NamedTuple):
    """One epoch's sampled ground truth (padded to the trace capacity)."""

    total_s: jnp.ndarray  # (R,) per-request end-to-end latency
    met: jnp.ndarray      # (R,) bool — deadline met (padded slots: don't read)
    energy_j: jnp.ndarray  # scalar — Σ planned per-request energy served
    obs_local: jnp.ndarray  # (N,) Σ sampled local time per device
    obs_vm: jnp.ndarray     # (N,) Σ sampled VM time (incl. extras) per device
    count: jnp.ndarray      # (N,) requests served per device


@partial(jax.jit, static_argnames=("dist",))
def sample_epoch(
    key,
    fleet: Fleet,
    m_sel: jnp.ndarray,
    alloc: Allocation,
    deadline: jnp.ndarray,
    device_ids: jnp.ndarray,
    valid: jnp.ndarray,
    rounds,
    dist: str = "gamma",
    var_scale: float = 0.8,
    edge_capacity_s=None,
    faults=None,
    assignment=None,
) -> EpochSample:
    """Sample one epoch of request latencies from the faulted ground
    truth — the request-granular mirror of ``violation_report``.

    ``device_ids``/``valid`` are the epoch's padded request batch
    (traced, static ``(R,)`` capacity — value-varied epochs share one
    program). Per-device moments are faulted exactly as the MC
    validator faults them; shared-edge congestion is **demand-driven**:
    node e's occupancy is Σ over this epoch's requests of t̄_vm,
    normalized by ``rounds`` (the design-rate requests/device/epoch), so
    nominal load reproduces ``violation_report``'s slow factor and a
    burst stretches it. Per-device observed tier sums come back for the
    EWMA re-fit — the same observables a partitioned stack measures.
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
    n = m_sel.shape[0]
    dev = jnp.asarray(device_ids, jnp.int32)
    v = jnp.asarray(valid)
    vf = v.astype(jnp.float64)
    count = jax.ops.segment_sum(vf, dev, num_segments=n)

    if edge_capacity_s is not None:
        cap = jnp.asarray(edge_capacity_s, jnp.float64)
        if faults is not None:
            cap = cap * faults.cap_scale
        demand = count * sel.t_vm / jnp.maximum(rounds, 1e-9)
        if cap.ndim == 0:
            slow = jnp.maximum(1.0, jnp.sum(demand) / jnp.maximum(cap, 1e-30))
        else:
            if assignment is None:
                raise ValueError(
                    "a per-node edge_capacity_s vector needs the plan's "
                    "device→node assignment (pass assignment=plan.assignment)")
            a = jnp.asarray(assignment, jnp.int32)
            occ_e = jax.ops.segment_sum(demand, a, num_segments=cap.shape[0])
            slow_e = jnp.maximum(1.0, occ_e / jnp.maximum(cap, 1e-30))
            slow = slow_e[a]
        sel = sel._replace(t_vm=sel.t_vm * slow, v_vm=sel.v_vm * slow**2)

    mean_loc = energy.mean_local_time(sel.w_flops, sel.g_eff, alloc.f)
    t_off = channel.offload_time(sel.d_bits, alloc.b, fleet.link.p_tx, gain)
    shape = dev.shape
    k_loc, k_vm = jax.random.split(key, 2)
    s_loc, s_vm = sample_local_and_vm(
        k_loc, k_vm, dist, mean_loc[dev], var_scale * sel.v_loc[dev],
        sel.t_vm[dev], var_scale * sel.v_vm[dev], shape)
    t_loc_r = jnp.where(sel.w_flops[dev] > 0, s_loc, 0.0)
    t_vm_r = jnp.where(sel.t_vm[dev] > 0, s_vm, 0.0)
    if faults is not None:
        # Straggler bursts, keyed exactly as violation_report keys them
        # (fold_in 0x57) so the fault taxonomy stays one seeded family.
        k_hit, k_extra = jax.random.split(jax.random.fold_in(key, 0x57), 2)
        p_straggle = jnp.clip(faults.straggler_prob, 0.0, 1.0)
        hit = jax.random.bernoulli(k_hit, p_straggle, shape)
        extra_mean = jnp.maximum(faults.straggler_extra_s, 1e-9)
        extra_var = (jnp.maximum(faults.straggler_cv, 1e-3) * extra_mean) ** 2
        extra = _sample_matched(k_extra, "pareto", extra_mean, extra_var, shape)
        t_vm_r = t_vm_r + jnp.where(hit & (sel.t_vm[dev] > 0), extra, 0.0)

    total = t_loc_r + t_off[dev] + t_vm_r
    deadline = jnp.broadcast_to(jnp.asarray(deadline, jnp.float64), (n,))
    e_req = alloc.e_loc + alloc.e_off
    return EpochSample(
        total_s=total,
        met=total <= deadline[dev],
        energy_j=jnp.sum(vf * e_req[dev]),
        obs_local=jax.ops.segment_sum(t_loc_r * vf, dev, num_segments=n),
        obs_vm=jax.ops.segment_sum(t_vm_r * vf, dev, num_segments=n),
        count=count,
    )


# ---------------------------------------------------------------------------
# The replay loop
# ---------------------------------------------------------------------------


@dataclass
class ReplayResult:
    """Per-epoch telemetry plus the ladder/migration headline scalars."""

    epoch_rate: np.ndarray  # (T,) epoch violation rate (NaN when idle)
    window_rate: np.ndarray  # (T,) sentinel sliding-window rate
    tripped: np.ndarray  # (T,) bool
    rung: np.ndarray  # (T,) ladder rung after the epoch
    energy_j: np.ndarray  # (T,) serving energy actually spent per epoch
    overhead_j: np.ndarray  # (T,) migration energy charged per epoch
    epoch_violations: np.ndarray  # (T,) int
    epoch_requests: np.ndarray  # (T,) int
    replans: int
    churn: int  # Σ hamming(m_sel) over installations
    migrations: int  # Σ devices whose node changed over installations
    migration_energy_j: float
    stats: EngineStats = field(default_factory=EngineStats)

    @property
    def final_window_rate(self) -> float:
        w = self.window_rate[~np.isnan(self.window_rate)]
        return float(w[-1]) if w.size else float("nan")

    @property
    def total_energy_j(self) -> float:
        """Serving + migration energy over the whole trace."""
        return float(self.energy_j.sum() + self.overhead_j.sum())

    @property
    def total_violations(self) -> int:
        return int(self.epoch_violations.sum())


def _record_epoch(stats: EngineStats, uid0: int, totals, met):  # analyze: ok(TRC001): host telemetry append; operands are materialized np slices
    """Bulk-append one epoch's completions to the engine-shaped outcome
    stream (same invariants as ``record_completion``; the met flags were
    already scored in-trace against the per-device SLO)."""
    stats.request_uids.extend(range(uid0, uid0 + len(totals)))
    stats.completion_times.extend(float(x) for x in totals)
    stats.deadline_flags.extend(bool(m) for m in met)


def _padded_batch(trace: Trace, bounds, t: int, capacity: int):  # analyze: ok(TRC001,TRC002): host trace slicing — the padded batch is built on host, consumed traced
    lo, hi = int(bounds[t]), int(bounds[t + 1])
    dev = np.zeros(capacity, np.int32)
    dev[: hi - lo] = trace.device_id[lo:hi]
    valid = np.zeros(capacity, bool)
    valid[: hi - lo] = True
    return dev, valid, hi - lo


def replay(  # analyze: ok(TRC001,TRC002,TRC003): host serving loop; the jit boundary is sample_epoch/plan_fixed_partition inside
    fleet: Fleet,
    scenario: Scenario,
    schedule: FaultSchedule,
    planner: Planner,
    trace: Trace,
    key,
    *,
    guarded: bool = True,
    guard: Optional[GuardConfig] = None,
    dist: str = "gamma",
    oracle: bool = False,
) -> ReplayResult:
    """Serve ``trace`` epoch by epoch under ``schedule``; see module doc.

    ``guarded=False`` freezes the initial plan (the A/B baseline);
    ``oracle=True`` replaces the sentinel+ladder with schedule-aware
    re-planning — each time the fault state changes, the oracle plans
    against the *true* faulted fleet and capacity (``apply_faults`` +
    ``faulted_capacity``), paying the same migration costs. An oracle
    run shares the trace and sample keys with the actual run, so
    :func:`regret_curves` is a paired comparison.
    """
    if guard is None:
        guard = GuardConfig()
    sc = Scenario(*scenario).normalized(fleet.num_devices)
    n = fleet.num_devices
    eps_scalar = float(np.asarray(sc.eps).mean())
    cap_np = np.asarray(sc.edge_capacity_s)
    multi_node = cap_np.ndim == 1
    cap_arg = None if np.all(np.isinf(cap_np)) else sc.edge_capacity_s
    rounds = max(trace.nominal_per_epoch / max(n, 1), 1e-9)
    capacity = trace.capacity
    bounds = trace.epoch_bounds()

    plan = planner.plan(fleet, sc)
    contingencies = contingency_plans(
        fleet, sc.deadline, sc.eps, sc.B, cap_arg,
        sigma_inflation=guard.sigma_inflation) if guarded and not oracle else {}
    sentinel = ViolationSentinel(eps_scalar, guard.sentinel)
    stats = EngineStats()

    loc_hat = vm_hat = 1.0
    cap_hat = np.ones(cap_np.shape[0]) if multi_node else None
    rung = RUNG_NONE
    last_action = -(10**9)
    replans = churn = migrations = 0
    mig_energy = 0.0
    last_oracle_state = None

    T = trace.epochs
    epoch_rate = np.full(T, np.nan)
    window_rate = np.full(T, np.nan)
    tripped_log = np.zeros(T, bool)
    rung_log = np.zeros(T, np.int32)
    energy_log = np.zeros(T)
    overhead_log = np.zeros(T)
    viol_log = np.zeros(T, np.int64)
    req_log = np.zeros(T, np.int64)

    def _install(new, t):
        nonlocal plan, replans, churn, migrations, mig_energy
        churn += int(np.sum(np.asarray(new.m_sel) != np.asarray(plan.m_sel)))
        if multi_node:
            moved = int(assignment_churn(plan.assignment, new.assignment))
            migrations += moved
            if moved:
                # re-establishing a migrated session re-uploads the
                # offload payload once at the incumbent partition
                _tl, t_off, _tv = _predicted_components(fleet, plan)
                e_mig = t_off * np.asarray(fleet.link.p_tx, float)
                delta = float(migration_energy(
                    plan.assignment, new.assignment, e_mig))
                mig_energy += delta
                overhead_log[t] += delta
        replans += 1
        plan = new

    for t in range(T):
        state = state_at(schedule, t)
        if oracle:
            # schedule-aware: re-plan whenever the true fault state moves
            leaves = [np.asarray(x) for x in state]
            if last_oracle_state is None or not all(
                    np.array_equal(a, b)
                    for a, b in zip(leaves, last_oracle_state, strict=True)):
                fleet_t = apply_faults(fleet, state)
                cap_t = faulted_capacity(sc.edge_capacity_s, state)
                new = planner.plan(fleet_t, sc._replace(edge_capacity_s=cap_t))
                _install(new, t)
                last_oracle_state = leaves

        dev, valid, served = _padded_batch(trace, bounds, t, capacity)
        stats.mark_window()
        if served:
            ep = sample_epoch(
                jax.random.fold_in(key, t), fleet, plan.m_sel, plan.alloc,
                sc.deadline, jnp.asarray(dev), jnp.asarray(valid),
                rounds, dist=dist, edge_capacity_s=cap_arg, faults=state,
                assignment=plan.assignment if multi_node else None)
            tot = np.asarray(ep.total_s)[:served]
            met = np.asarray(ep.met)[:served]
            _record_epoch(stats, int(bounds[t]), tot, met)
            energy_log[t] = float(ep.energy_j)
            viol_log[t] = int(served - met.sum())
            req_log[t] = served
            epoch_rate[t] = float(viol_log[t]) / served

            k, nn = stats.window_counts()
            sentinel.observe(k, nn)

            # observable-only re-fit: predicted tier sums weighted by the
            # epoch's per-device demand, so idle devices don't bias it
            t_loc_p, _t_off_p, t_vm_p = _predicted_components(fleet, plan)
            cnt = np.asarray(ep.count, float)
            loc_hat, vm_hat = _refit_scales(
                loc_hat, vm_hat, cnt * t_loc_p, cnt * t_vm_p,
                np.asarray(ep.obs_local, float), np.asarray(ep.obs_vm, float),
                guard.ewma)
            if multi_node:
                cap_hat = _refit_node_scales(
                    cap_hat, cnt * t_vm_p, np.asarray(ep.obs_vm, float),
                    np.asarray(plan.assignment), cap_np.shape[0], guard.ewma)

        trip = sentinel.tripped()
        window_rate[t] = sentinel.rate()
        tripped_log[t] = trip

        if guarded and not oracle and trip \
                and t - last_action >= guard.cooldown:
            last_action = t
            rung = min(rung + 1, guard.max_rung)
            fleet_hat = apply_faults(fleet, _refit_state(loc_hat, vm_hat))
            if multi_node:
                cap_fit = sc.edge_capacity_s * jnp.asarray(cap_hat)
                sc_fit = sc._replace(edge_capacity_s=cap_fit)
            else:
                cap_fit, sc_fit = cap_arg, sc
            if rung == RUNG_PRICE:
                new = plan_fixed_partition(
                    fleet_hat, plan.m_sel, sc.deadline, sc.eps, sc.B, cap_fit)
            elif rung == RUNG_REPLAN:
                new = planner.plan(fleet_hat, sc_fit, init_m=plan.m_sel,
                                   incumbent=plan)
            else:
                new = pick_contingency(contingencies, fleet_hat, sc.deadline,
                                       sc.eps, incumbent=plan)
            _install(new, t)
            sentinel.reset()
        elif rung > RUNG_NONE and not trip and \
                sentinel.counts[1] >= guard.sentinel.min_count:
            rung = RUNG_NONE

        rung_log[t] = rung

    return ReplayResult(
        epoch_rate=epoch_rate, window_rate=window_rate, tripped=tripped_log,
        rung=rung_log, energy_j=energy_log, overhead_j=overhead_log,
        epoch_violations=viol_log, epoch_requests=req_log,
        replans=replans, churn=churn, migrations=migrations,
        migration_energy_j=mig_energy, stats=stats)


def regret_curves(actual: ReplayResult, oracle: ReplayResult) -> dict:  # analyze: ok(TRC002): post-hoc accounting over materialized per-epoch logs
    """Cumulative regret of the controller against a schedule-aware
    oracle, per epoch: energy (serving + migration overhead, J) and
    deadline violations. Positive regret = the controller paid more /
    violated more than a clairvoyant re-planner on the *same* trace and
    sample stream; the violation curve is what the ladder's reaction
    time costs, the energy curve what its caution costs."""
    if actual.energy_j.shape != oracle.energy_j.shape:
        raise ValueError(
            f"paired runs must share a horizon: {actual.energy_j.shape} "
            f"!= {oracle.energy_j.shape}")
    de = (actual.energy_j + actual.overhead_j) \
        - (oracle.energy_j + oracle.overhead_j)
    dv = actual.epoch_violations - oracle.epoch_violations
    return {
        "energy_j": np.cumsum(de),
        "violations": np.cumsum(dv),
        "final_energy_j": float(np.sum(de)),
        "final_violations": int(np.sum(dv)),
    }


# ---------------------------------------------------------------------------
# Engine-backed replay (the real ServingEngine, smoke scale)
# ---------------------------------------------------------------------------


def replay_engine(  # analyze: ok(TRC001,TRC002,TRC003): host serving loop around the real engine; jit lives inside ServingEngine
    engine: ServingEngine,
    trace: Trace,
    *,
    seed: int = 0,
    deadline_s: float = 1.0,
    prompt_tokens: int = 8,
    max_new_tokens: int = 4,
    eps: float = 0.05,
    sentinel: Optional[ViolationSentinel] = None,
    chain=None,
):
    """Drive the *real* :class:`ServingEngine` through ``trace``.

    Each epoch's arrivals become :class:`Request` objects (arrival time
    stamped — the FIFO tie-break in ``schedule`` sees it), served with
    ``engine.run``; ``EngineStats`` windows are marked per epoch and fed
    to the sentinel as deadline outcomes. When ``chain`` (a
    ``BlockChain``) is given and the engine has observed at least one
    warm decode step, the measured decode moments are folded back via
    ``measured_chain`` — the §IV online re-profiling that the EWMA
    re-fit consumes on the next plan.

    Returns ``(summary, sentinel, refit_chain)`` — ``refit_chain`` is
    ``None`` until enough completions have been observed.
    """
    rng = np.random.default_rng(seed)
    if sentinel is None:
        sentinel = ViolationSentinel(eps)
    bounds = trace.epoch_bounds()
    vocab = int(engine.cfg.vocab_size)
    for t in range(trace.epochs):
        lo, hi = int(bounds[t]), int(bounds[t + 1])
        if hi == lo:
            continue
        queue = [
            Request(
                uid=r,
                prompt=rng.integers(0, vocab, prompt_tokens).astype(np.int32),
                max_new_tokens=max_new_tokens,
                deadline_s=deadline_s,
                arrival_s=float(trace.arrival_s[r]),
            )
            for r in range(lo, hi)
        ]
        engine.stats.mark_window()
        engine.run(queue)
        sentinel.observe(*engine.stats.window_counts())
    summary = engine.stats.summary()
    refit = None
    if chain is not None and summary["decode_samples"] >= 1:
        refit = measured_chain(chain, summary)
    return summary, sentinel, refit

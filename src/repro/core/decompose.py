"""Group-sharded dual decomposition (DESIGN.md §scale).

``Planner.plan`` compiles ONE padded program over the whole fleet: every
device carries ``max_points`` columns, so a mixed fleet of 8-block and
64-block populations pays 65-wide tables on every device, and a new
population mix is a new (N, M+1) shape → a fresh XLA compile of the whole
planner. That is fine at paper scale (N ≤ 50) and wrong at serving scale
(10⁵–10⁶ devices).

This module re-derives Algorithm 2 as a **global-price / local-enforcer
split**. Problem P2 couples devices through exactly two scalars — the
bandwidth price λ (Σ b_n ≤ B) and the shared-edge price μ
(Σ t̄_vm(m_n) ≤ C_edge). At fixed prices the problem separates per
device, hence per *homogeneous population*: each ``FleetSpec`` group gets
its own compiled program at its **native** shape ``(n_g, M_g + 1)`` (no
cross-group padding), and the groups are coordinated only by a cheap
host-level outer bisection whose excess functions are sums of per-group
excess at the same price:

    excess(λ)  =  Σ_g  [ Σ_{n ∈ g} b_n*(λ) ]  −  B
    occ(μ)     =  Σ_g  [ Σ_{n ∈ g} t̄_vm(m_n*(μ)) ]  −  C_edge

Both are monotone in the price, so the host loop replays the *exact*
bisection/bracket-expansion semantics of ``resource`` / ``solvers.scalar``
in numpy float64 (IEEE-identical arithmetic), with the per-group partial
sums evaluated on device. All price exponentiation (``10**log_price``)
happens **inside** the compiled programs via ``jnp.where(need, 10**lp, 0)``
— the same XLA pow the monolithic trace uses — so the two paths cannot
diverge by a host/device pow ulp.

Parity: leaf-wise agreement with ``Planner.plan`` at rtol ≤ 1e-6 is pinned
by ``tests/test_decompose.py`` for the exact-enumeration policies. The two
paths differ only in reduction *grouping* (per-group partials summed on
the host vs one (N,)-reduction), which perturbs the bisected prices by
O(ulp); everything downstream is price-Lipschitz. The PCCP policy also
runs through here, but its inner barrier sees native-width (M_g+1)
variables instead of padded (max_points+1) ones, so its iterates are not
bit-comparable — that width cut is precisely the perf win.

Compile model: one XLA program per distinct ``(M_g, n_bucket)`` group
shape per statics tuple — NOT per group and NOT per fleet. Group device
counts are bucketed (≤ 16 exact, then power-of-two quanta with ≤ ~12.5 %
lane waste, padded lanes weighted out of every sum by a 0/1 mask), so a
group growing 1000 → 1001 devices reuses the 1024-lane program. Device
batches within a group are sharded over the 1-D ``("devices",)`` mesh of
``parallel.sharding.planner_mesh`` via ``shard_map`` (the λ-solve path —
the ~60-probe hot loop — with per-shard partial sums psummed); groups are
processed one at a time, so peak *table* memory is O(largest group), not
O(fleet).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import lru_cache, partial
from math import gcd
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import ccp, channel, energy
from repro.core.blocks import Fleet
from repro.core.fleet import FleetSpec
from repro.core.placement import assign_devices_host
from repro.core.planner import (
    _MU_SAFETY,
    Plan,
    Policy,
    _edge_occ_prep,
    _optimal_point_solve,
    _optimal_prep,
    _optimal_select,
    _select_best,
    _traced_status,
    default_starts,
    get_policy,
    policy_point_tables,
)
from repro.core.resource import (
    _EDGE_CAP_RTOL,
    _LOG_PRICE_HI0,
    _LOG_PRICE_HI_MAX,
    _LOG_PRICE_LO,
    _LOG_PRICE_STEP,
    Allocation,
    _alloc_finalize,
    _alloc_prep,
    _alloc_solve_at,
    _rescale_with_floor,
    select_point,
)
from repro.parallel.sharding import planner_mesh

__all__ = ["ShardedGroup", "build_groups", "bucket_size", "plan_sharded",
           "program_cache_sizes"]

#: Host spans of the sharded planner (``jax.profiler.TraceAnnotation``):
#: a profiler trace holds them on the device's clock, and with no profiler
#: session each costs about a microsecond. A probe is one host round trip
#: of a price clearing; its wait is the host blocked on the device's
#: partial sum and its copy back.
_SPAN_PLAN = "repro.plan_sharded"
_SPAN_GROUPS = "repro.build_groups"
_SPAN_STEP = "repro.step"
_SPAN_LAM = "repro.price.lam"
_SPAN_MU = "repro.price.mu"
_SPAN_PROBE = "repro.price.probe"
_SPAN_WAIT = "repro.price.wait"


# ---------------------------------------------------------------------------
# Group construction: native-width fleets + lane bucketing
# ---------------------------------------------------------------------------

#: below this count a group compiles at its exact width (small groups are
#: cheap to compile and waste-sensitive); above it, counts are rounded up
#: to a power-of-two quantum ~n/16 so the worst-case lane waste is ~12.5 %
#: and a slowly growing population keeps hitting the same compiled shape.
_EXACT_BUCKET_MAX = 16


def bucket_size(n: int, multiple_of: int = 1) -> int:  # analyze: ok(TRC003): lane bucketing on concrete host ints (group counts, mesh size)
    """Padded lane count for a group of ``n`` devices (see module doc),
    additionally rounded to a multiple of ``multiple_of`` (the mesh size,
    so ``shard_map`` shards evenly)."""
    if n <= _EXACT_BUCKET_MAX:
        q = 1
    else:
        q = 1 << max((n - 1).bit_length() - 4, 0)
    q = q * multiple_of // gcd(q, multiple_of)
    return -(-n // q) * q


@dataclass(frozen=True)
class ShardedGroup:
    """One homogeneous population, materialized at native table width.

    ``fleet`` is a single-group ``FleetSpec`` build of ``n_pad`` lanes
    (bucketed count): its tables are ``(n_pad, M_g + 1)`` with an all-valid
    mask, real devices in lanes ``[:n]`` carrying the fleet-order gains
    slice, pad lanes repeating the last real device (finite, physically
    plausible — they run the full solve and are weighted out of every
    cross-device sum by ``w`` and sliced away on the host).
    """

    fleet: Fleet
    n: int  # real device count
    n_pad: int  # bucketed lane count (== fleet.num_devices)
    start: int  # fleet-order slice [start, stop) of the real lanes
    stop: int
    name: str
    w: jnp.ndarray  # (n_pad,) lane mask: 1.0 real, 0.0 pad


def build_groups(spec: FleetSpec, gains, mesh) -> list:  # analyze: ok(TRC002): gains are concretized once at group-build time (host-side spec surgery)
    """Materialize per-group native-width fleets from a ``FleetSpec`` and
    the fleet-order ``(N,)`` gains vector (``FleetSpec.sample_gains`` —
    the same sequence ``spec.build(key)`` would bake into the monolithic
    fleet, which is what makes the two paths comparable at a key)."""
    with TraceAnnotation(_SPAN_GROUPS):
        gains = np.asarray(jnp.asarray(gains, jnp.float64))
        if gains.shape != (spec.num_devices,):
            raise ValueError(
                f"gains must be ({spec.num_devices},) for this spec, "
                f"got shape {gains.shape}")
        mesh_size = int(mesh.devices.size)
        groups = []
        for g, (start, stop) in zip(spec.groups, spec.group_slices(),
                                    strict=True):
            n = g.count
            n_pad = bucket_size(n, mesh_size)
            gg = np.concatenate([gains[start:stop],
                                 np.repeat(gains[stop - 1:stop], n_pad - n)])
            sub = FleetSpec((replace(g, count=n_pad),), area_m=spec.area_m,
                            min_dist_m=spec.min_dist_m)
            w = np.zeros(n_pad)
            w[:n] = 1.0
            groups.append(ShardedGroup(
                fleet=sub.build(gains=jnp.asarray(gg)), n=n, n_pad=n_pad,
                start=start, stop=stop, name=g.name, w=jnp.asarray(w)))
        return groups


def _pad_lanes(a: np.ndarray, n_pad: int) -> np.ndarray:  # analyze: ok(TRC002): host-side numpy padding of concrete scenario slices
    """Edge-repeat a (n,) host vector to (n_pad,)."""
    return np.concatenate([a, np.repeat(a[-1:], n_pad - a.shape[0])])


def _repad(x: jnp.ndarray, n_pad: int) -> jnp.ndarray:  # analyze: ok(TRC003): pad width is concrete host shape arithmetic
    """Edge-repeat the lane axis of a (S, n) device array back to (S, n_pad)
    after a global step touched only the real lanes."""
    k = n_pad - x.shape[1]
    if k == 0:
        return x
    return jnp.concatenate([x, jnp.repeat(x[:, -1:], k, axis=1)], axis=1)


def _cat_real(parts, groups):
    """Concatenate per-group (S, n_pad) leaves into fleet order (S, N)."""
    return jnp.concatenate(
        [x[:, :g.n] for x, g in zip(parts, groups, strict=True)], axis=1)


# ---------------------------------------------------------------------------
# Compiled per-group programs
# ---------------------------------------------------------------------------

#: every jitted program ever built, for cache introspection in the
#: recompile drill: (name, jitted fn) — ``program_cache_sizes`` sums
#: ``_cache_size()`` per name so tests can pin "one compile per distinct
#: group shape" without scraping compiler logs.
_PROGRAM_REGISTRY: list = []


def _register(name: str, fn):
    _PROGRAM_REGISTRY.append((name, fn))
    return fn


def program_cache_sizes() -> dict:
    """{program name: total jit-cache entries} across all program sets."""
    out: dict = {}
    for name, fn in _PROGRAM_REGISTRY:
        out[name] = out.get(name, 0) + fn._cache_size()
    return out


def _lane_specs(tree):
    """Lane-sharded PartitionSpecs for a pytree of per-device leaves
    (axis 0 = device lane, trailing axes replicated)."""
    return jax.tree_util.tree_map(
        lambda x: P("devices", *([None] * (x.ndim - 1))), tree)


class GroupPrograms(NamedTuple):
    """The compiled per-group programs of one statics tuple (see factory)."""

    prep: object  # (fleet, m (S,n), deadline, eps, B) -> AllocPrep (S,n)
    bsum: object  # (prep, w, B, log_lam (S,), need (S,)) -> (S,) Σ w·b
    solve: object  # (prep, B, log_lam, need) -> (b, f, feas) (S,n)
    edge_state: object  # (fleet, b, f, deadline, eps) -> μ-invariant tables
    occ_sum: object  # (occ, state…, w, log_mu, need) -> (S,) Σ w·occ[m*]
    partition: object  # (fleet, m, b, f, log_mu, mu_need, dl, eps, w) -> step
    occ_sum_node: object  # (occ, mask (S,n), state…, w, log_mu, need) -> (S,)
    partition_nodes: object  # per-device (S,n) μ variant of ``partition``


@lru_cache(maxsize=None)
def _group_programs(mesh, policy: Policy, pccp_iters: int, solver: str,
                    pccp_gated: bool, channel_cv: float) -> GroupPrograms:
    """Build (once per mesh + statics) the jitted per-group programs.

    The lru_cache keeps the *function objects* stable across
    ``plan_sharded`` calls, so jax's jit cache keys on (shape, dtype) only
    — one XLA compile per distinct ``(M_g, n_bucket)`` group shape, zero
    on value-varied repeats. ``shard_map`` wrappers are constructed inside
    the jitted trace (specs depend on leaf ranks), which costs nothing at
    steady state.

    Prices enter every program as ``(log_price, need)`` and are
    exponentiated in-trace — ``jnp.where(need, 10.0**log_price, 0.0)``,
    with the final μ additionally scaled by ``_MU_SAFETY`` exactly where
    the monolithic path does — so the sharded path shares the monolithic
    trace's pow/rounding behaviour bit-for-bit.
    """
    sig_model, ub_k = policy.sigma_model, policy.ub_k
    svec = P(None, "devices")  # (S, n) start-vectorized per-lane leaves

    # ---- λ path (the hot loop): lane-sharded over the planner mesh ----

    def prep_raw(fleet, m, deadline, eps, B):
        return jax.vmap(
            lambda mm: _alloc_prep(fleet, mm, deadline, eps, B, sig_model,
                                   ub_k, channel_cv))(m)

    @jax.jit
    def prep(fleet, m, deadline, eps, B):
        fn = jax.shard_map(
            prep_raw, mesh=mesh,
            in_specs=(_lane_specs(fleet), svec, P("devices"), P("devices"),
                      P()),
            out_specs=svec)
        return fn(fleet, m, deadline, eps, B)

    def bsum_raw(prep_v, w, B, log_lam, need):
        lam = jnp.where(need, 10.0 ** log_lam, 0.0)  # (S,) in-trace pow
        b = jax.vmap(
            lambda p, l: _alloc_solve_at(p, B, l, channel_cv)[0])(prep_v, lam)
        return jax.lax.psum(jnp.sum(w[None, :] * b, axis=-1), "devices")

    @jax.jit
    def bsum(prep_v, w, B, log_lam, need):
        fn = jax.shard_map(
            bsum_raw, mesh=mesh,
            in_specs=(svec, P("devices"), P(), P(None), P(None)),
            out_specs=P(None))
        return fn(prep_v, w, B, log_lam, need)

    def solve_raw(prep_v, B, log_lam, need):
        lam = jnp.where(need, 10.0 ** log_lam, 0.0)
        return jax.vmap(
            lambda p, l: _alloc_solve_at(p, B, l, channel_cv))(prep_v, lam)

    @jax.jit
    def solve(prep_v, B, log_lam, need):
        fn = jax.shard_map(
            solve_raw, mesh=mesh,
            in_specs=(svec, P(), P(None), P(None)),
            out_specs=svec)
        return fn(prep_v, B, log_lam, need)

    # ---- μ path + partition: per-group tables, once per outer step ----
    # (not lane-sharded: these run once per step vs ~60 λ probes, and the
    # PCCP inner barrier is kept off shard_map on purpose — its iterates
    # are already native-width, which is where the win is)

    @jax.jit
    def edge_state(fleet, b, f, deadline, eps):
        sigma = ccp.SIGMA_FNS[sig_model](eps)

        def one(b1, f1):
            e_t, t_t, v_t = policy_point_tables(fleet, b1, f1, policy,
                                                channel_cv)
            feas, any_feas, mlb = _edge_occ_prep(t_t, v_t, sigma, deadline)
            return e_t, feas, any_feas, mlb

        return jax.vmap(one)(b, f)

    @jax.jit
    def occ_sum(occ, e_t, feas, any_feas, mlb, w, log_mu, need):
        def one(e1, fe1, af1, mlb1, lm, nd):
            mu = jnp.where(nd, 10.0 ** lm, 0.0)  # probes: no safety factor
            cost = jnp.where(fe1, e1 + mu * occ, jnp.inf)
            m = jnp.where(af1, jnp.argmin(cost, axis=-1), mlb1)
            return jnp.sum(w * jnp.take_along_axis(occ, m[:, None], -1)[:, 0])

        return jax.vmap(one)(e_t, feas, any_feas, mlb, log_mu, need)

    @jax.jit
    def partition(fleet, m, b, f, log_mu, mu_need, deadline, eps, w):
        sigma = ccp.SIGMA_FNS[sig_model](eps)
        occ = fleet.chain.t_vm

        def one(m1, b1, f1, lm, mn):
            mu = jnp.where(mn, 10.0 ** lm * _MU_SAFETY, 0.0)
            e_t, t_t, v_t = policy_point_tables(fleet, b1, f1, policy,
                                                channel_cv)
            m_new, feas, iters = policy.partition(
                m1, e_t + mu * occ, t_t, v_t, sigma, deadline, pccp_iters,
                solver, pccp_gated)
            # the trace records true energy, not the μ-priced surrogate
            obj = jnp.sum(
                w * jnp.take_along_axis(e_t, m_new[:, None], -1)[:, 0])
            return m_new, feas, iters, obj

        return jax.vmap(one)(m, b, f, log_mu, mu_need)

    # ---- placement path (per-node capacity vectors, DESIGN.md §placement):
    # compiled only when a vector capacity is planned, so the scalar path's
    # program_cache_sizes pins are untouched ----

    @jax.jit
    def occ_sum_node(occ, mask, e_t, feas, any_feas, mlb, w, log_mu, need):
        """One node's occupancy partial: every lane argmins the full priced
        table at the node's trial μ (exactly ``_node_clearing_prices``) and
        only the lanes *assigned to the node* count toward the sum."""
        def one(mk1, e1, fe1, af1, mlb1, lm, nd):
            mu = jnp.where(nd, 10.0 ** lm, 0.0)  # probes: no safety factor
            cost = jnp.where(fe1, e1 + mu * occ, jnp.inf)
            m = jnp.where(af1, jnp.argmin(cost, axis=-1), mlb1)
            occ_sel = jnp.take_along_axis(occ, m[:, None], -1)[:, 0]
            return jnp.sum(jnp.where(mk1, w * occ_sel, 0.0))

        return jax.vmap(one)(mask, e_t, feas, any_feas, mlb, log_mu, need)

    @jax.jit
    def partition_nodes(fleet, m, b, f, log_mu_dev, mu_need_dev, deadline,
                        eps, w):
        """``partition`` with a per-device price row: each lane pays its
        own node's μ_{a_n}·occ in the priced table."""
        sigma = ccp.SIGMA_FNS[sig_model](eps)
        occ = fleet.chain.t_vm

        def one(m1, b1, f1, lmd, mnd):
            mu_dev = jnp.where(mnd, 10.0 ** lmd * _MU_SAFETY, 0.0)
            e_t, t_t, v_t = policy_point_tables(fleet, b1, f1, policy,
                                                channel_cv)
            m_new, feas, iters = policy.partition(
                m1, e_t + mu_dev[:, None] * occ, t_t, v_t, sigma, deadline,
                pccp_iters, solver, pccp_gated)
            obj = jnp.sum(
                w * jnp.take_along_axis(e_t, m_new[:, None], -1)[:, 0])
            return m_new, feas, iters, obj

        return jax.vmap(one)(m, b, f, log_mu_dev, mu_need_dev)

    for name, fn in (("group_prep", prep), ("group_bsum", bsum),
                     ("group_solve", solve), ("group_edge_state", edge_state),
                     ("group_occ_sum", occ_sum),
                     ("group_partition", partition),
                     ("group_occ_sum_node", occ_sum_node),
                     ("group_partition_nodes", partition_nodes)):
        _register(name, fn)
    return GroupPrograms(prep=prep, bsum=bsum, solve=solve,
                         edge_state=edge_state, occ_sum=occ_sum,
                         partition=partition, occ_sum_node=occ_sum_node,
                         partition_nodes=partition_nodes)


# ---------------------------------------------------------------------------
# Global programs: the only cross-group compiled steps
# ---------------------------------------------------------------------------

@jax.jit
def _global_rescale(b, b_lo, need, B):
    """The Σb ≤ B floor-respecting rescale of ``_alloc_finalize``, applied
    to the fleet-order (S, N) concatenation mid-alternation (the partition
    step reads the post-rescale b, exactly as the monolithic step does)."""

    def one(b1, blo1, nd):
        return jnp.where(nd & (jnp.sum(b1) > B),
                         _rescale_with_floor(b1, blo1, B), b1)

    return jax.vmap(one)(b, b_lo, need)


@partial(jax.jit, static_argnames=("sigma_model", "channel_cv"))
def _global_finish(prep_v, b, f, feas, part_feas, B, log_lam, need, edge_cap,
                   log_mu, mu_need, deadline, eps, sigma_model="cantelli",
                   channel_cv=0.0):
    """Final fleet-order scoring on the concatenated per-group solves:
    the identical ``_alloc_finalize`` + margins the monolithic alternation
    ends with, vmapped over starts."""

    def one(p, b1, f1, fe1, pf1, ll, nd, lm, mn):
        lam = jnp.where(nd, 10.0 ** ll, 0.0)
        mu = jnp.where(mn, 10.0 ** lm * _MU_SAFETY, 0.0)
        alloc = _alloc_finalize(p, b1, f1, fe1, B, lam, nd, channel_cv,
                                edge_capacity_s=edge_cap, edge_price=mu)
        sel = p.sel
        t_mean = (energy.mean_local_time(sel.w_flops, sel.g_eff, alloc.f)
                  + channel.offload_time(sel.d_bits, alloc.b, p.p_tx, p.gain)
                  + sel.t_vm)
        margins = ccp.deterministic_deadline_margin(
            t_mean, sel.v_loc + sel.v_vm, eps, deadline, sigma_model)
        total = jnp.sum(alloc.energy)
        return (alloc, total, pf1 & alloc.feasible, margins,
                _traced_status(alloc, total, margins))

    return jax.vmap(one)(prep_v, b, f, feas, part_feas, log_lam, need,
                         log_mu, mu_need)


@partial(jax.jit, static_argnames=("sigma_model", "channel_cv"))
def _global_finish_nodes(prep_v, b, f, feas, part_feas, B, log_lam, need,
                         edge_cap, log_mu_node, mu_need_node, assignment,
                         deadline, eps, sigma_model="cantelli",
                         channel_cv=0.0):
    """Per-node-price variant of ``_global_finish`` (DESIGN.md §placement):
    ``_alloc_finalize`` checks each node's occupancy against its own C_e at
    the device→node assignment and stamps the (E,) price vector into
    ``alloc.mu``. ``log_mu_node``/``mu_need_node`` are (S, E),
    ``assignment`` is (S, N) int32."""

    def one(p, b1, f1, fe1, pf1, ll, nd, lmn, mnn, a1):
        lam = jnp.where(nd, 10.0 ** ll, 0.0)
        mu_node = jnp.where(mnn, 10.0 ** lmn * _MU_SAFETY, 0.0)
        alloc = _alloc_finalize(p, b1, f1, fe1, B, lam, nd, channel_cv,
                                edge_capacity_s=edge_cap, edge_price=mu_node,
                                assignment=a1)
        sel = p.sel
        t_mean = (energy.mean_local_time(sel.w_flops, sel.g_eff, alloc.f)
                  + channel.offload_time(sel.d_bits, alloc.b, p.p_tx, p.gain)
                  + sel.t_vm)
        margins = ccp.deterministic_deadline_margin(
            t_mean, sel.v_loc + sel.v_vm, eps, deadline, sigma_model)
        total = jnp.sum(alloc.energy)
        return (alloc, total, pf1 & alloc.feasible, margins,
                _traced_status(alloc, total, margins))

    return jax.vmap(one)(prep_v, b, f, feas, part_feas, log_lam, need,
                         log_mu_node, mu_need_node, assignment)


_register("global_rescale", _global_rescale)
_register("global_finish", _global_finish)
_register("global_finish_nodes", _global_finish_nodes)


# ---------------------------------------------------------------------------
# Host-level price loops (numpy float64 replicas of the traced searches)
# ---------------------------------------------------------------------------

def _host_bisect(fn, lo, hi, iters=60, endpoint="mid"):  # analyze: ok(TRC001,TRC002,TRC003): host-level global price loop by design (numpy replica of solvers.scalar.bisect)
    """Per-lane ``solvers.scalar.bisect`` in numpy float64.

    Vectorized over the multi-start lanes with masked per-lane updates —
    exactly what ``vmap(bisect)`` lowers to — and IEEE-identical midpoint
    arithmetic, so the host search visits the same points the traced
    search would at the same excess values.
    """
    lo = np.asarray(lo, np.float64).copy()
    hi = np.asarray(hi, np.float64).copy()
    f_lo = fn(lo)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        f_mid = fn(mid)
        go_right = np.sign(f_mid) == np.sign(f_lo)
        lo = np.where(go_right, mid, lo)
        f_lo = np.where(go_right, f_mid, f_lo)
        hi = np.where(go_right, hi, mid)
    return hi if endpoint == "hi" else 0.5 * (lo + hi)


def _host_expand(fn, hi_start=None, size=1):  # analyze: ok(TRC001,TRC002,TRC003): host-level global price loop by design (numpy replica of resource.price_search)
    """Per-lane bracket expansion of ``resource.price_search`` in numpy float64:
    warm-start snap to the expansion grid, contract while the next-lower
    grid point clears, then the standard upward expansion. Masked per-lane
    updates replicate the vmapped while_loop batching rule (inactive lanes
    freeze their carry; every lane's excess is still evaluated, as the
    batched trace does). ``hi_start=None`` is the cold start (no
    contraction pass), matching the traced cold path."""
    hi0 = _LOG_PRICE_HI0
    if hi_start is None:
        hi = np.full(size, hi0)
        f_hi = fn(hi)
    else:
        k = np.round((np.asarray(hi_start, np.float64) - hi0)
                     / _LOG_PRICE_STEP)
        k_max = (_LOG_PRICE_HI_MAX - _LOG_PRICE_HI0) // _LOG_PRICE_STEP
        hi = hi0 + np.clip(k, 0.0, k_max) * _LOG_PRICE_STEP
        f_hi = fn(hi)

        def probe_down(h):
            f = fn(h - _LOG_PRICE_STEP)
            return np.where(h > hi0 + 1e-9, f, 1.0)

        f_dn = probe_down(hi)
        while True:
            active = (hi > hi0 + 1e-9) & (f_dn <= 0.0)
            if not active.any():
                break
            hi_new = np.where(active, hi - _LOG_PRICE_STEP, hi)
            f_hi = np.where(active, f_dn, f_hi)
            f_dn = np.where(active, probe_down(hi_new), f_dn)
            hi = hi_new
    while True:
        active = (f_hi > 0.0) & (hi < _LOG_PRICE_HI_MAX - 1e-9)
        if not active.any():
            break
        hi = np.where(active, hi + _LOG_PRICE_STEP, hi)
        f_hi = np.where(active, fn(hi), f_hi)
    return hi, f_hi


def _lam_clear(programs, groups, preps, B_dev, B_host, S, lam_hi):  # analyze: ok(TRC001,TRC002,TRC003): host-level global price loop by design
    """Clear the bandwidth price λ across groups: the global excess is the
    sum of per-group device-evaluated partials at the same price. Returns
    ``(log_lam, need, lam_hi)`` with the expanded bracket top threaded for
    the next alternation step (warm-start is value-invariant, see
    ``resource.price_search``). When no start lane needs pricing
    (Σ b(0) ≤ B everywhere) the search is skipped outright — λ = 0
    regardless, exactly what the monolithic ``where(need, …, 0)`` yields.
    """

    def excess(log_lam, need):
        with TraceAnnotation(_SPAN_PROBE):
            ll, nd = jnp.asarray(log_lam, jnp.float64), jnp.asarray(need)
            tot = None
            for g, p in zip(groups, preps, strict=True):
                part = programs.bsum(p, g.w, B_dev, ll, nd)
                tot = part if tot is None else tot + part
            with TraceAnnotation(_SPAN_WAIT):
                tot = np.asarray(tot)
            return tot - B_host

    with TraceAnnotation(_SPAN_LAM):
        all_on = np.ones(S, bool)
        need = excess(np.zeros(S), np.zeros(S, bool)) > 0.0
        if not need.any():
            return np.zeros(S), need, lam_hi
        fn = lambda x: excess(x, all_on)
        hi, _ = _host_expand(fn, hi_start=lam_hi)
        log_lam = _host_bisect(fn, np.full(S, _LOG_PRICE_LO), hi, iters=60)
        return log_lam, need, hi


def _mu_clear(programs, groups, states, cap_host, S, mu_hi):  # analyze: ok(TRC001,TRC002,TRC003): host-level global price loop by design
    """Clear the shared-edge price μ across groups on the held per-group
    μ-invariant tables (``edge_state``): Σ_g Σ_n occ[m*(μ)] vs C_edge.
    Same skip/warm-start discipline as ``_lam_clear``; the bisection keeps
    the ``endpoint="hi"`` step-function semantics of
    ``planner._clearing_price``."""

    def occ_excess(log_mu, need):
        with TraceAnnotation(_SPAN_PROBE):
            lm, nd = jnp.asarray(log_mu, jnp.float64), jnp.asarray(need)
            tot = None
            for g, st in zip(groups, states, strict=True):
                part = programs.occ_sum(g.fleet.chain.t_vm, *st, g.w, lm, nd)
                tot = part if tot is None else tot + part
            with TraceAnnotation(_SPAN_WAIT):
                tot = np.asarray(tot)
            return tot - cap_host

    with TraceAnnotation(_SPAN_MU):
        all_on = np.ones(S, bool)
        need = occ_excess(np.zeros(S), np.zeros(S, bool)) > 0.0
        if not need.any():
            return np.zeros(S), need, mu_hi
        fn = lambda x: occ_excess(x, all_on)
        hi, _ = _host_expand(fn, hi_start=mu_hi)
        log_mu = _host_bisect(fn, np.full(S, _LOG_PRICE_LO), hi, iters=60,
                              endpoint="hi")
        return log_mu, need, hi


def _mu_clear_nodes(programs, groups, states, masks, caps_host, S, mu_hi):  # analyze: ok(TRC001,TRC002,TRC003): host-level global price loop by design
    """Per-node μ clearing at a fixed device→node assignment (DESIGN.md
    §placement): node e's occupancy sums masked per-group partials
    (``occ_sum_node``) against its own C_e — E independent replicas of
    ``_mu_clear`` with per-node warm brackets. ``masks`` is a per-group
    list of (E, S, n_pad) lane masks; ``mu_hi`` is (E, S). Returns
    ``(log_mu (E, S), need (E, S), mu_hi)`` — absent (C_e = 0) and
    unconstrained (C_e = ∞) nodes never clear (occupancy 0 resp. excess
    −∞ keeps ``need`` False)."""
    e_count = caps_host.shape[0]
    log_mu = np.zeros((e_count, S))
    mu_need = np.zeros((e_count, S), bool)
    hi_out = np.array(mu_hi, np.float64, copy=True)
    all_on = np.ones(S, bool)
    for e in range(e_count):
        def occ_excess(lm_s, need_s, e=e):
            with TraceAnnotation(_SPAN_PROBE):
                ll, nd = jnp.asarray(lm_s, jnp.float64), jnp.asarray(need_s)
                tot = None
                for g, st, mk in zip(groups, states, masks, strict=True):
                    part = programs.occ_sum_node(g.fleet.chain.t_vm, mk[e],
                                                 *st, g.w, ll, nd)
                    tot = part if tot is None else tot + part
                with TraceAnnotation(_SPAN_WAIT):
                    tot = np.asarray(tot)
                return tot - caps_host[e]

        with TraceAnnotation(_SPAN_MU):
            need_e = occ_excess(np.zeros(S), np.zeros(S, bool)) > 0.0
            if not need_e.any():
                continue
            fn = lambda x: occ_excess(x, all_on)
            hi, _ = _host_expand(fn, hi_start=mu_hi[e])
            log_mu[e] = _host_bisect(fn, np.full(S, _LOG_PRICE_LO), hi,
                                     iters=60, endpoint="hi")
        mu_need[e] = need_e
        hi_out[e] = hi
    return log_mu, mu_need, hi_out


# ---------------------------------------------------------------------------
# The decomposed Algorithm-2 alternation
# ---------------------------------------------------------------------------

def _plan_groups(groups, sc, policy: Policy, outer_iters, m0_groups, S,  # analyze: ok(TRC001,TRC002,TRC003): host-level orchestrator of compiled per-group programs by design
                 programs, channel_cv, mesh):
    """Run the start-vectorized alternation over the group programs.

    Per step: per-group λ-invariant prep → global λ clearing → per-group
    solve at λ → global Σb ≤ B rescale → (finite capacity only) global μ
    clearing on held per-group tables → per-group partition at the priced
    tables. After ``outer_iters`` steps: one more λ clearing at the final
    partition, then the global finish (finalize + margins) on the
    fleet-order concatenation, then the standard multi-start selection.
    """
    deadline_np = np.asarray(sc.deadline)
    eps_np = np.asarray(sc.eps)
    B_dev, cap_dev = sc.B, sc.edge_capacity_s
    B_host = float(np.asarray(sc.B))
    cap_np = np.asarray(cap_dev, np.float64)
    multi_node = cap_np.ndim == 1  # per-node capacity vector (§placement)
    if multi_node:
        caps_host = cap_np
        e_count = int(caps_host.shape[0])
        price_edge = policy.edge_aware
    else:
        cap_host = float(cap_np)
        price_edge = np.isfinite(cap_host) and policy.edge_aware

    dls = [jnp.asarray(_pad_lanes(deadline_np[g.start:g.stop], g.n_pad))
           for g in groups]
    epss = [jnp.asarray(_pad_lanes(eps_np[g.start:g.stop], g.n_pad))
            for g in groups]
    t_vm_np = [np.asarray(g.fleet.chain.t_vm) for g in groups]

    def host_assignment(m_gs):
        """Fleet-order (S, N) device→node map at the current partitions —
        the host replay of the monolithic per-step ``assign_devices`` (the
        numpy mirror is pinned bit-identical in ``tests/test_placement``)."""
        occ_parts = []
        for g, m_g, tv in zip(groups, m_gs, t_vm_np, strict=True):
            m_np = np.asarray(m_g)[:, :g.n]  # (S, n) real lanes
            occ_parts.append(np.take_along_axis(
                tv[None, :g.n, :], m_np[:, :, None], axis=2)[:, :, 0])
        occ = np.concatenate(occ_parts, axis=1)  # (S, N)
        return np.stack([
            assign_devices_host(occ[s], caps_host, policy.assign)
            for s in range(occ.shape[0])]).astype(np.int32)

    def node_masks(a):
        """Per-group (E, S, n_pad) lane masks from a fleet-order (S, N)
        assignment (pad lanes match no node → zero partials)."""
        out = []
        for g in groups:
            a_g = a[:, g.start:g.stop]
            pad = np.full((a.shape[0], g.n_pad - g.n), -1, a_g.dtype)
            a_p = np.concatenate([a_g, pad], axis=1)
            out.append(jnp.asarray(
                a_p[None, :, :] == np.arange(e_count)[:, None, None]))
        return out

    def per_device_prices(a, log_mu_e, mu_need_e):
        """Per-group (S, n_pad) price rows: lane n pays its node's
        μ_{a_n} (pad lanes priced 0 via need=False)."""
        rows = np.arange(a.shape[0])[:, None]
        lm_dev = log_mu_e.T[rows, a]  # (S, N)
        nd_dev = mu_need_e.T[rows, a]
        lms, nds = [], []
        for g in groups:
            k = g.n_pad - g.n
            lm_g = np.concatenate(
                [lm_dev[:, g.start:g.stop],
                 np.zeros((a.shape[0], k))], axis=1)
            nd_g = np.concatenate(
                [nd_dev[:, g.start:g.stop],
                 np.zeros((a.shape[0], k), bool)], axis=1)
            lms.append(jnp.asarray(lm_g))
            nds.append(jnp.asarray(nd_g))
        return lms, nds
    # The initial starts are committed with the replicated mesh sharding
    # the program outputs carry: from iteration 2 on, m is a loop-carried
    # program output, and an uncommitted first m would re-key the
    # prep/partition jit caches — two compiles per group instead of one.
    rep = NamedSharding(mesh, P())
    m_gs = [jax.device_put(np.broadcast_to(m0[:, None], (S, g.n_pad)), rep)
            for m0, g in zip(m0_groups, groups, strict=True)]

    lam_hi = np.full(S, _LOG_PRICE_HI0)
    mu_hi = np.full(S, _LOG_PRICE_HI0)
    log_mu, mu_need = np.zeros(S), np.zeros(S, bool)
    if multi_node:
        mu_hi_e = np.full((e_count, S), _LOG_PRICE_HI0)
        log_mu_e = np.zeros((e_count, S))
        mu_need_e = np.zeros((e_count, S), bool)
    objs, iters_steps = [], []
    part_feas = None

    def lam_solve(m_gs):
        """prep → λ clearing → per-group (b, f, feas) at the cleared λ."""
        preps = [programs.prep(g.fleet, m, dl, ep, B_dev)
                 for g, m, dl, ep in zip(groups, m_gs, dls, epss, strict=True)]
        log_lam, need, hi = _lam_clear(programs, groups, preps, B_dev, B_host,
                                       S, lam_hi)
        ll, nd = jnp.asarray(log_lam), jnp.asarray(need)
        sols = [programs.solve(p, B_dev, ll, nd) for p in preps]
        return preps, sols, log_lam, need, hi

    for _ in range(outer_iters):
        with TraceAnnotation(_SPAN_STEP):
            preps, sols, log_lam, lam_need, lam_hi = lam_solve(m_gs)
            nd = jnp.asarray(lam_need)
            b_cat = _global_rescale(
                _cat_real([s[0] for s in sols], groups),
                _cat_real([p.b_lo for p in preps], groups), nd, B_dev)
            b_gs = [_repad(b_cat[:, g.start:g.stop], g.n_pad)
                    for g in groups]
            f_gs = [s[1] for s in sols]
            if multi_node:
                a_now = host_assignment(m_gs)
                if price_edge:
                    states = [programs.edge_state(g.fleet, b, f, dl, ep)
                              for g, b, f, dl, ep in zip(
                                  groups, b_gs, f_gs, dls, epss, strict=True)]
                    log_mu_e, mu_need_e, mu_hi_e = _mu_clear_nodes(
                        programs, groups, states, node_masks(a_now),
                        caps_host, S, mu_hi_e)
                lms, nds = per_device_prices(a_now, log_mu_e, mu_need_e)
                parts = [programs.partition_nodes(g.fleet, m, b, f, lmd, ndd,
                                                  dl, ep, g.w)
                         for g, m, b, f, lmd, ndd, dl, ep in zip(
                             groups, m_gs, b_gs, f_gs, lms, nds, dls, epss,
                             strict=True)]
            else:
                if price_edge:
                    states = [programs.edge_state(g.fleet, b, f, dl, ep)
                              for g, b, f, dl, ep in zip(
                                  groups, b_gs, f_gs, dls, epss, strict=True)]
                    log_mu, mu_need, mu_hi = _mu_clear(
                        programs, groups, states, cap_host, S, mu_hi)
                lm, mn = jnp.asarray(log_mu), jnp.asarray(mu_need)
                parts = [programs.partition(g.fleet, m, b, f, lm, mn, dl, ep,
                                            g.w)
                         for g, m, b, f, dl, ep in zip(
                             groups, m_gs, b_gs, f_gs, dls, epss, strict=True)]
            m_gs = [pt[0] for pt in parts]
            part_feas = _cat_real([pt[1] for pt in parts], groups)
            iters_steps.append(_cat_real([pt[2] for pt in parts], groups))
            objs.append(sum(np.asarray(pt[3]) for pt in parts))

    with TraceAnnotation(_SPAN_STEP):
        preps, sols, log_lam, lam_need, lam_hi = lam_solve(m_gs)
        prep_cat = jax.tree_util.tree_map(
            lambda *xs: _cat_real(xs, groups), *preps)
        b_cat = _cat_real([s[0] for s in sols], groups)
        f_cat = _cat_real([s[1] for s in sols], groups)
        feas_cat = _cat_real([s[2] for s in sols], groups)
        if multi_node:
            # like the monolithic tail: assignment recomputed at the final m,
            # priced with the last step's node prices
            assignment_s = jnp.asarray(host_assignment(m_gs))
            (alloc_s, total_s, feas_s, margins_s,
             status_s) = _global_finish_nodes(
                prep_cat, b_cat, f_cat, feas_cat, part_feas, B_dev,
                jnp.asarray(log_lam), jnp.asarray(lam_need), cap_dev,
                jnp.asarray(log_mu_e.T), jnp.asarray(mu_need_e.T),
                assignment_s, sc.deadline, sc.eps,
                sigma_model=policy.sigma_model, channel_cv=channel_cv)
        else:
            assignment_s = jnp.zeros(
                (S, int(b_cat.shape[1])), jnp.int32)
            alloc_s, total_s, feas_s, margins_s, status_s = _global_finish(
                prep_cat, b_cat, f_cat, feas_cat, part_feas, B_dev,
                jnp.asarray(log_lam), jnp.asarray(lam_need), cap_dev,
                jnp.asarray(log_mu), jnp.asarray(mu_need), sc.deadline,
                sc.eps, sigma_model=policy.sigma_model, channel_cv=channel_cv)

        plans = Plan(
            m_sel=_cat_real(m_gs, groups),
            alloc=alloc_s,
            total_energy=total_s,
            feasible=feas_s,
            objective_trace=jnp.swapaxes(
                jnp.asarray(np.stack(objs, axis=0)), 0, 1),  # (S, outer)
            pccp_iters=jnp.stack(iters_steps, axis=1),  # (S, outer, N)
            margins=margins_s,
            status=status_s,
            assignment=assignment_s,
        )
        idx = int(_select_best(plans))
        return jax.tree_util.tree_map(lambda x: x[idx], plans)


# ---------------------------------------------------------------------------
# Optimal baseline: group-sharded (λ, μ) two-price exact search
# ---------------------------------------------------------------------------

class OptimalPrograms(NamedTuple):
    prep: object  # (fleet, deadline, eps, B) -> λ-invariant tables
    tables: object  # (fleet, prep…, B, log_lam, need) -> per-λ point tables
    occ: object  # (fleet, cost, feas, budget, w, log_mu, need) -> Σ occ[m*]
    eval: object  # final per-lane selection + Σ w·b / Σ w·occ partials


@lru_cache(maxsize=None)
def _optimal_programs(mesh, sigma_model: str) -> OptimalPrograms:
    """Per-group programs of the exact joint search (``plan_optimal``) at
    native width, sharing ``planner._optimal_*`` so the two paths cannot
    drift. No start axis: the exact search has no alternation."""

    def prep_raw(fleet, deadline, eps, B):
        sigma = ccp.SIGMA_FNS[sigma_model](eps)
        return _optimal_prep(fleet, deadline, sigma, B)

    @jax.jit
    def prep(fleet, deadline, eps, B):
        fn = jax.shard_map(
            prep_raw, mesh=mesh,
            in_specs=(_lane_specs(fleet), P("devices"), P("devices"), P()),
            out_specs=P("devices", None))
        return fn(fleet, deadline, eps, B)

    def tables_raw(fleet, budget_all, b_lo_all, feas0_all, B, log_lam, need):
        lam = jnp.where(need, 10.0 ** log_lam, 0.0)
        return _optimal_point_solve(fleet, budget_all, b_lo_all, feas0_all,
                                    lam, B)

    @jax.jit
    def tables(fleet, budget_all, b_lo_all, feas0_all, B, log_lam, need):
        fn = jax.shard_map(
            tables_raw, mesh=mesh,
            in_specs=(_lane_specs(fleet), P("devices", None),
                      P("devices", None), P("devices", None), P(), P(), P()),
            out_specs=P("devices", None))
        return fn(fleet, budget_all, b_lo_all, feas0_all, B, log_lam, need)

    def occ_raw(fleet, cost, feas, budget_all, w, log_mu, need):
        mu = jnp.where(need, 10.0 ** log_mu, 0.0)  # probes: no safety factor
        m_sel, _ = _optimal_select(cost, feas, budget_all, fleet.chain.t_vm,
                                   mu)
        occ_sel = jnp.take_along_axis(
            fleet.chain.t_vm, m_sel[:, None], -1)[:, 0]
        return jax.lax.psum(jnp.sum(w * occ_sel), "devices")

    @jax.jit
    def occ(fleet, cost, feas, budget_all, w, log_mu, need):
        fn = jax.shard_map(
            occ_raw, mesh=mesh,
            in_specs=(_lane_specs(fleet), P("devices", None),
                      P("devices", None), P("devices", None), P("devices"),
                      P(), P()),
            out_specs=P())
        return fn(fleet, cost, feas, budget_all, w, log_mu, need)

    def eval_raw(fleet, cost, b, f, feas, budget_all, w, deadline, eps,
                 log_mu, need):
        mu = jnp.where(need, 10.0 ** log_mu * _MU_SAFETY, 0.0)
        m_sel, any_feas = _optimal_select(cost, feas, budget_all,
                                          fleet.chain.t_vm, mu)
        pick = lambda a: jnp.take_along_axis(a, m_sel[:, None], -1)[:, 0]
        b_sel, f_sel = pick(b), pick(f)
        sel = select_point(fleet, m_sel)
        e_loc = energy.expected_local_energy(
            fleet.platform.kappa, sel.w_flops, sel.g_eff, f_sel)
        e_off = channel.offload_energy(sel.d_bits, b_sel, fleet.link.p_tx,
                                       fleet.link.gain)
        t_mean = (energy.mean_local_time(sel.w_flops, sel.g_eff, f_sel)
                  + channel.offload_time(sel.d_bits, b_sel, fleet.link.p_tx,
                                         fleet.link.gain)
                  + sel.t_vm)
        margins = ccp.deterministic_deadline_margin(
            t_mean, sel.v_loc + sel.v_vm, eps, deadline, sigma_model)
        b_part = jax.lax.psum(jnp.sum(w * b_sel), "devices")
        occ_part = jax.lax.psum(jnp.sum(w * sel.t_vm), "devices")
        return (m_sel, b_sel, f_sel, e_loc, e_off, pick(feas) & any_feas,
                margins, b_part, occ_part)

    @jax.jit
    def eval_(fleet, cost, b, f, feas, budget_all, w, deadline, eps, log_mu,
              need):
        fn = jax.shard_map(
            eval_raw, mesh=mesh,
            in_specs=(_lane_specs(fleet), P("devices", None),
                      P("devices", None), P("devices", None),
                      P("devices", None), P("devices", None), P("devices"),
                      P("devices"), P("devices"), P(), P()),
            out_specs=(P("devices"), P("devices"), P("devices"),
                       P("devices"), P("devices"), P("devices"),
                       P("devices"), P(), P()))
        return fn(fleet, cost, b, f, feas, budget_all, w, deadline, eps,
                  log_mu, need)

    for name, fn in (("opt_prep", prep), ("opt_tables", tables),
                     ("opt_occ", occ), ("opt_eval", eval_)):
        _register(name, fn)
    return OptimalPrograms(prep=prep, tables=tables, occ=occ, eval=eval_)


def _plan_optimal_sharded(groups, sc, policy: Policy, mesh) -> Plan:  # analyze: ok(TRC001,TRC002,TRC003): host-level orchestrator of compiled per-group programs by design
    """Group-decomposed ``plan_optimal``: the nested (λ, μ) exact search
    with per-group native-width point tables. The λ excess and the inner
    μ clearing both sum per-group device partials on the host; the μ
    search at each λ probe is cold (matching ``plan_optimal.mu_star``)
    and skipped entirely when the unpriced selection already fits."""
    progs = _optimal_programs(mesh, policy.sigma_model)
    deadline_np = np.asarray(sc.deadline)
    eps_np = np.asarray(sc.eps)
    B_dev, cap_dev = sc.B, sc.edge_capacity_s
    B_host = float(np.asarray(sc.B))
    cap_np = np.asarray(cap_dev)
    if cap_np.ndim:
        raise NotImplementedError(
            "plan_sharded with a per-node edge_capacity_s vector needs an "
            "alternating policy (the exact solve-override path is "
            "monolithic-only — use Planner.plan, or policy='robust')")
    cap_host = float(cap_np)
    finite_cap = np.isfinite(cap_host)

    dls = [jnp.asarray(_pad_lanes(deadline_np[g.start:g.stop], g.n_pad))
           for g in groups]
    epss = [jnp.asarray(_pad_lanes(eps_np[g.start:g.stop], g.n_pad))
            for g in groups]
    preps = [progs.prep(g.fleet, dl, ep, B_dev)
             for g, dl, ep in zip(groups, dls, epss, strict=True)]

    def solve_at(log_lam, lam_need):
        """Full (λ, μ*(λ)) solve: per-group tables at λ, μ cleared on the
        held tables, then the final per-lane selection. Returns the λ
        excess, the per-group eval outputs, and (log_mu, mu_need)."""
        ll = jnp.asarray(log_lam, jnp.float64)
        nd = jnp.asarray(bool(lam_need))
        tabs = [progs.tables(g.fleet, *p, B_dev, ll, nd)
                for g, p in zip(groups, preps, strict=True)]

        log_mu, mu_need = 0.0, False
        if finite_cap:
            def occ_excess(lms):
                tot = 0.0
                for g, p, t in zip(groups, preps, tabs, strict=True):
                    tot += float(progs.occ(
                        g.fleet, t[0], t[4], p[0], g.w,
                        jnp.asarray(float(lms[0]), jnp.float64),
                        jnp.asarray(lms[1])))
                return np.asarray([tot - cap_host])

            if occ_excess((0.0, False))[0] > 0.0:
                fn = lambda x: occ_excess((x[0], True))
                hi, _ = _host_expand(fn, hi_start=None, size=1)
                log_mu = float(_host_bisect(
                    fn, np.full(1, _LOG_PRICE_LO), hi, iters=60,
                    endpoint="hi")[0])
                mu_need = True

        lm = jnp.asarray(log_mu, jnp.float64)
        mn = jnp.asarray(mu_need)
        evals = [progs.eval(g.fleet, t[0], t[1], t[2], t[4], p[0], g.w, dl,
                            ep, lm, mn)
                 for g, t, p, dl, ep in zip(groups, tabs, preps, dls, epss,
                                            strict=True)]
        b_total = sum(float(ev[7]) for ev in evals)
        return b_total - B_host, evals, (log_mu, mu_need)

    need_price = solve_at(0.0, False)[0] > 0.0
    fn = lambda x: np.asarray([solve_at(float(x[0]), True)[0]])
    hi, _ = _host_expand(fn, hi_start=None, size=1)  # cold, as plan_optimal
    log_lam = float(_host_bisect(fn, np.full(1, _LOG_PRICE_LO), hi,
                                 iters=60)[0])
    _, evals, (log_mu, mu_need) = solve_at(log_lam, need_price)

    cat = lambda i: jnp.concatenate(
        [ev[i][:g.n] for ev, g in zip(evals, groups, strict=True)])
    m_sel, b, f = cat(0), cat(1), cat(2)
    e_loc, e_off, feas, margins = cat(3), cat(4), cat(5), cat(6)
    occ_total = sum(float(ev[8]) for ev in evals)
    # primal capacity check at the rounded discrete selection
    feas = feas & (occ_total <= cap_host * (1.0 + _EDGE_CAP_RTOL))

    lam = jnp.where(jnp.asarray(bool(need_price)),
                    10.0 ** jnp.asarray(log_lam, jnp.float64), 0.0)
    mu = jnp.where(jnp.asarray(mu_need),
                   10.0 ** jnp.asarray(log_mu, jnp.float64) * _MU_SAFETY, 0.0)
    alloc = Allocation(b=b, f=f, e_loc=e_loc, e_off=e_off, feasible=feas,
                       lam=lam, mu=mu)
    total_energy = jnp.sum(alloc.energy)
    n = int(m_sel.shape[0])
    return Plan(
        m_sel=m_sel,
        alloc=alloc,
        total_energy=total_energy,
        feasible=feas,
        objective_trace=total_energy[None],
        pccp_iters=jnp.ones((1, n), jnp.int32),
        margins=margins,
        status=_traced_status(alloc, total_energy, margins),
        assignment=jnp.zeros((n,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def _resolve_starts(spec: FleetSpec, init_m, multi_start: bool):  # analyze: ok(TRC001,TRC002,TRC003): scalar start resolution on concrete host ints
    """Per-group (S,) start vectors replicating ``planner.initial_points``
    on the monolithic padded fleet: the spread is derived from the padded
    width ``spec.max_points`` and clamped to each group's own chain."""
    m1 = spec.max_points
    if multi_start and init_m is None:
        starts = default_starts(m1)
    elif init_m is None:
        starts = [m1 - 1]
    else:
        if not isinstance(init_m, (int, np.integer)):
            raise TypeError(
                "plan_sharded resolves starts per group and supports only "
                f"scalar init_m (or None), got {type(init_m).__name__}; use "
                "Planner.plan for per-device warm-start arrays")
        if not 0 <= int(init_m) <= m1 - 1:
            raise ValueError(
                f"init_m must lie in [0, {m1 - 1}] (partition points 0..M "
                f"for a {m1 - 1}-block chain); got {init_m!r}")
        starts = [int(init_m)]
    starts = np.asarray(starts, np.int32)
    return [np.minimum(starts, g.chain.num_points - 1) for g in spec.groups]


def plan_sharded(spec: FleetSpec, scenario, config, *, key=None, gains=None,  # analyze: ok(TRC001,TRC002,TRC003): host-level orchestrator entry point by design
                 mesh=None, init_m: Optional[int] = None) -> Plan:
    """Plan a (possibly huge) mixed fleet through the group decomposition.

    Takes the :class:`FleetSpec` — the grouping truth — rather than a
    built ``Fleet``: the padded monolithic fleet is never materialized.
    Gains are sampled once fleet-wide (``spec.sample_gains(key)``, the
    same sequence ``spec.build(key)`` would use) or passed explicitly as
    a fleet-order ``(N,)`` array, then sliced per group.

    ``config`` is a ``PlannerConfig``; its statics select the compiled
    per-group programs. Differences from ``Planner.plan``: ``init_m``
    must be a scalar (per-device warm-start arrays stay on the monolithic
    path), and there is no host fail-soft ladder — ``Plan.status`` still
    carries the traced OK/DEGRADED stamp for the caller to act on.
    """
    with TraceAnnotation(_SPAN_PLAN):
        policy = get_policy(config.policy)
        if getattr(config, "edge_eps", None) is not None:
            raise NotImplementedError(
                "plan_sharded does not support the Cantelli edge_eps "
                "occupancy row yet — plan monolithically (Planner.plan) for "
                "chance-constrained edge capacity")
        if mesh is None:
            mesh = planner_mesh()
        if gains is None:
            if key is None:
                raise ValueError("plan_sharded needs a PRNG key (to place "
                                 "devices) or explicit link gains")
            gains = spec.sample_gains(key)
        sc = scenario.normalized(spec.num_devices)
        groups = build_groups(spec, gains, mesh)

        if policy.solve is not None:
            if init_m is not None or config.init_m is not None:
                raise ValueError(
                    f"policy {policy.name!r} solves exactly (no "
                    "alternation), so init_m warm starts have no effect — "
                    "drop init_m or pick an alternating policy")
            return _plan_optimal_sharded(groups, sc, policy, mesh)

        if init_m is None:
            init_m = config.init_m
        m0_groups = _resolve_starts(spec, init_m, config.multi_start)
        S = int(m0_groups[0].shape[0])
        programs = _group_programs(
            mesh, policy, int(config.pccp_iters), str(config.solver),
            bool(config.pccp_gated), float(config.channel_cv))
        return _plan_groups(groups, sc, policy, int(config.outer_iters),
                            m0_groups, S, programs, float(config.channel_cv),
                            mesh)

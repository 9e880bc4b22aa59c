"""Smoke run of the planner's main path on a TPU chip.

    python chip_smoke.py             # one chip: plan, sharded and replay phases
    python chip_smoke.py --chips 4   # four chips: plan_sharded at N=100 000,
                                     # a 4-device mesh against a 1-device mesh

Phases of the one-chip run, each checked against a reference:

- ``plan``: ``Planner.plan`` on the paper's §VI anchors (AlexNet and
  ResNet152, N=12, fleets from ``PRNGKey(0)``) at the golden settings,
  against ``tests/golden/seed_plans.json``; ``violation_report`` on the
  robust AlexNet plan holds the chance constraint.
- ``sharded``: ``Planner.plan_sharded`` on ``mixed_spec(1000)`` leaf-wise
  against ``Planner.plan`` on the built fleet, then ``plan_sharded`` at
  N=10 000 (feasible, ``PLAN_OK``, Σb ≤ B).
- ``replay``: the guarded replay incident of ``benchmarks/bench_replay.py``
  (E=3, N=8, 40 epochs, a brownout to 3 % at epoch 10) migrates and ends
  with its final-window violation rate ≤ ε.

Every phase runs in this one process and one thread, one after the
other: with three or four steps compiling at once, the TPU compiler
crashed natively on a v5e host. Each phase prints its compile seconds,
wall seconds, the deviations it observed and the devices holding its
results. The last line is one JSON object naming the device. Without a
TPU the script exits nonzero before any phase runs.

JAX keeps its compile cache where ``JAX_COMPILATION_CACHE_DIR`` says;
when that is unset the script uses ``.jax_cache/`` beside itself.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import resource
import sys
import time
import warnings

import numpy as np

from chipbench.run import CompileClock

ROOT = os.path.dirname(os.path.abspath(__file__))

#: golden settings of tests/golden/seed_plans.json (tests/test_plan_golden.py)
ANCHORS = {
    "alexnet": ("alexnet_fleet", 0.180, 10e6, 0.02),
    "resnet152": ("resnet152_fleet", 0.120, 30e6, 0.04),
}
PLAN_RUNS = (("alexnet", "robust"), ("resnet152", "robust"),
             ("alexnet", "robust_exact"))
ENERGY_RTOL = 1e-6
PARITY_RTOL = 1e-6
#: tests/test_decompose.py's scenario (30 MHz over 8 devices) and key; the
#: bandwidth per device is held as the fleet grows
SHARD_DEADLINE, SHARD_EPS, SHARD_B_PER_DEVICE, SHARD_KEY = 0.2, 0.04, 3.75e6, 11


def _shard_scenario(n):
    from repro.core import Scenario

    return Scenario(SHARD_DEADLINE, SHARD_EPS, SHARD_B_PER_DEVICE * n)


def _setup():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # The goldens and the replay incident were drawn with JAX's original
    # threefry bit layout; the partitionable default draws other fleets.
    jax.config.update("jax_threefry_partitionable", False)
    warnings.filterwarnings("error", message="plan fail-soft")


def _devices_of(tree, devices, committed=True):
    """The devices holding ``tree``'s arrays. Fails unless every leaf is a
    jax.Array on ``devices`` (one device or a list) and, with
    ``committed``, committed there. ``plan_sharded`` assembles some leaves
    from host sums of per-group device partials; those land, uncommitted,
    on the default device, so its plans are checked with
    ``committed=False``."""
    import jax

    devices = set(devices) if isinstance(devices, (list, tuple)) else {devices}
    held = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if not isinstance(leaf, jax.Array):
            raise AssertionError(f"result leaf is a {type(leaf)}")
        if committed and not leaf.committed:
            raise AssertionError("result leaf not committed to a device")
        held |= leaf.devices()
    if not held or not held <= devices:
        raise AssertionError(f"results on {held}, expected {devices}")
    return sorted(str(d) for d in held)


def _max_rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    den = np.maximum(np.abs(b), 1e-300)
    return float(np.max(np.abs(a - b) / den)) if a.size else 0.0


def _compare_plans(got, ref, rtol):
    """Leaf-wise: floats within ``rtol`` (atol 1e-12), ints/bools exact;
    ``pccp_iters`` is a convergence diagnostic and is shape-checked only.
    Returns the largest relative float deviation."""
    import jax

    worst = 0.0
    flat_g, tdef_g = jax.tree_util.tree_flatten_with_path(got)
    flat_r, tdef_r = jax.tree_util.tree_flatten_with_path(ref)
    if tdef_g != tdef_r:
        raise AssertionError("plan tree structures differ")
    for (path, a), (_, b) in zip(flat_g, flat_r, strict=True):
        name = jax.tree_util.keystr(path)
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            raise AssertionError(f"{name}: {a.shape}/{a.dtype} vs {b.shape}/{b.dtype}")
        if "pccp_iters" in name:
            continue
        if np.issubdtype(a.dtype, np.floating):
            np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-12,
                                       err_msg=name)
            worst = max(worst, _max_rel(a, b))
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)
    return worst


# ---------------------------------------------------------------------------
# phases: each checks its result against its reference and raises on a
# mismatch; what it returns is printed
# ---------------------------------------------------------------------------

def plan_anchor(device, name, policy):
    import jax

    from repro.configs import paper_tables
    from repro.core import (PLAN_OK, Planner, PlannerConfig, Scenario,
                            violation_report)

    fleet_fn, deadline, bandwidth, eps = ANCHORS[name]
    with open(os.path.join(ROOT, "tests", "golden", "seed_plans.json")) as f:
        golden = json.load(f)[f"{name}/{policy}"]
    fleet = jax.device_put(
        getattr(paper_tables, fleet_fn)(jax.random.PRNGKey(0), 12), device)
    planner = Planner(PlannerConfig(policy=policy, outer_iters=3,
                                    pccp_iters=6))
    p = planner.plan(fleet, Scenario(deadline, eps, bandwidth))
    m_sel = np.asarray(p.m_sel)
    out = {"m_sel": m_sel.tolist(),
           "energy": float(p.total_energy),
           "energy_rel_dev": abs(float(p.total_energy) - golden["total_energy"])
           / golden["total_energy"],
           "devices": _devices_of(p, device)}
    if int(p.status) != PLAN_OK:
        raise AssertionError(f"status {int(p.status)} != PLAN_OK")
    if m_sel.tolist() != golden["m_sel"]:
        raise AssertionError(f"m_sel {m_sel.tolist()} != golden {golden['m_sel']}")
    if np.asarray(p.feasible).astype(int).tolist() != golden["feasible"]:
        raise AssertionError("feasible differs from the golden")
    if out["energy_rel_dev"] > ENERGY_RTOL:
        raise AssertionError(f"energy deviates by {out['energy_rel_dev']:.3e}")
    if (name, policy) == ("alexnet", "robust"):
        vr = violation_report(jax.random.PRNGKey(1), fleet, p.m_sel, p.alloc,
                              deadline, dist="gamma", var_scale=1.0)
        out["violation_rate_max"] = float(vr.rate.max())
        out["devices"] = _devices_of((p, vr), device)
        if out["violation_rate_max"] > eps + 0.01:
            raise AssertionError(
                f"violation rate {out['violation_rate_max']} > eps + 0.01")
    return out


def _shard_planner():
    from repro.core import Planner, PlannerConfig

    return Planner(PlannerConfig(policy="robust_exact", outer_iters=3))


def sharded_monolithic_1000(device):
    import jax

    from repro.configs.paper_tables import mixed_spec

    spec = mixed_spec(1000)
    fleet = jax.device_put(spec.build(jax.random.PRNGKey(SHARD_KEY)), device)
    return _shard_planner().plan(fleet, _shard_scenario(1000))


def sharded_1000(device):
    import jax

    from repro.configs.paper_tables import mixed_spec
    from repro.parallel.sharding import planner_mesh

    return _shard_planner().plan_sharded(
        mixed_spec(1000), _shard_scenario(1000),
        key=jax.random.PRNGKey(SHARD_KEY), mesh=planner_mesh([device]))


def sharded_large(devices, n):
    import jax

    from repro.configs.paper_tables import mixed_spec
    from repro.core import PLAN_OK
    from repro.parallel.sharding import planner_mesh

    mesh = planner_mesh(devices)
    bandwidth = SHARD_B_PER_DEVICE * n
    p = _shard_planner().plan_sharded(
        mixed_spec(n), _shard_scenario(n),
        key=jax.random.PRNGKey(SHARD_KEY), mesh=mesh)
    b_sum = float(np.asarray(p.alloc.b).sum())
    out = {"n": n, "energy": float(p.total_energy),
           "b_sum_over_B": b_sum / bandwidth,
           "devices": _devices_of(p, list(mesh.devices.flat),
                                  committed=False)}
    if int(p.status) != PLAN_OK:
        raise AssertionError(f"status {int(p.status)} != PLAN_OK")
    if not bool(np.asarray(p.feasible).all()):
        raise AssertionError("infeasible devices in the sharded plan")
    if b_sum > bandwidth * (1.0 + 1e-9):
        raise AssertionError(f"sum(b) = {b_sum} exceeds B")
    return out, p


#: bench_replay's incident: N=8 mixed fleet on E=3 nodes whose capacities
#: are these shares of the uncapped plan's occupancy
REPLAY_N, REPLAY_EPOCHS, REPLAY_FAULT_START = 8, 40, 10
REPLAY_SC, REPLAY_SHARES = (0.2, 0.04, 30e6), (0.2, 0.1, 0.05)


def _replay_setup(device):
    import jax

    from repro.configs.paper_tables import mixed_spec
    from repro.core import Planner, PlannerConfig

    fleet = jax.device_put(
        mixed_spec(REPLAY_N).build(jax.random.PRNGKey(11)), device)
    return fleet, Planner(PlannerConfig(policy="robust_exact", outer_iters=3,
                                        pccp_iters=6))


def replay_incident(device):
    """bench_replay's guarded run: the node holding most devices browns
    out to 3 % from epoch 10; the guard must migrate and recover."""
    import jax
    import jax.numpy as jnp

    from repro.core import Scenario
    from repro.core.resource import select_point
    from repro.serve import replay as rp
    from repro.serve.closedloop import GuardConfig
    from repro.serve.faults import brownout
    from repro.serve.guard import SentinelConfig

    n, epochs, start = REPLAY_N, REPLAY_EPOCHS, REPLAY_FAULT_START
    eps = REPLAY_SC[1]
    fleet, planner = _replay_setup(device)
    slack = planner.plan(fleet, Scenario(*REPLAY_SC))
    occ0 = float(select_point(fleet, slack.m_sel).t_vm.sum())
    sc = Scenario(*REPLAY_SC, jnp.asarray(REPLAY_SHARES) * occ0)
    p0 = planner.plan(fleet, sc)
    node = int(np.argmax(np.bincount(np.asarray(p0.assignment), minlength=3)))
    sched = brownout(epochs, start=start, length=epochs - start, depth=0.03,
                     node=node, num_nodes=3)
    trace = rp.poisson_trace(rate_per_epoch=96.0, epochs=epochs, epoch_s=1.0,
                             num_devices=n, seed=7)
    guard = GuardConfig(sentinel=SentinelConfig(window=256, alpha=1e-3,
                                                min_count=48))
    r = rp.replay(fleet, sc, sched, planner, trace, jax.random.PRNGKey(5),
                  guarded=True, guard=guard)
    out = {"final_window_rate": r.final_window_rate, "eps": eps,
           "migrations": r.migrations, "replans": r.replans,
           "violations": r.total_violations,
           "devices": _devices_of(p0, device)}
    if r.migrations < 1:
        raise AssertionError("the guarded replay did not migrate")
    if not r.final_window_rate <= eps:
        raise AssertionError(
            f"final-window violation {r.final_window_rate} > eps {eps}")
    return out


# ---------------------------------------------------------------------------
# running the phases
# ---------------------------------------------------------------------------

def _rss_gib():
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 2**30


class Steps:
    """Runs the steps of the phases one at a time and keeps, per step,
    its wall seconds, compile seconds and compile-cache hits."""

    def __init__(self, clock):
        self.clock = clock
        self.wall, self.compile_s, self.hits = {}, {}, {}

    def run(self, name, thunk):
        c0, h0 = self.clock.secs["backend_s"], self.clock.hits
        t0 = time.perf_counter()
        print(f"[{name}] start, host rss {_rss_gib():.1f} GiB", flush=True)
        out = thunk()
        self.wall[name] = time.perf_counter() - t0
        self.compile_s[name] = self.clock.secs["backend_s"] - c0
        self.hits[name] = self.clock.hits - h0
        print(f"[{name}] done in {self.wall[name]:.1f} s, host rss "
              f"{_rss_gib():.1f} GiB", flush=True)
        return out

    def report(self, phase, names, **fields):
        print(json.dumps({"phase": phase,
                          "compile_s": sum(self.compile_s[n] for n in names),
                          "cache_hits": sum(self.hits[n] for n in names),
                          "wall_s": sum(self.wall[n] for n in names),
                          **fields}, default=str), flush=True)


def run_one_chip(device, clock):
    steps = Steps(clock)
    for a, p in PLAN_RUNS:  # one line each, so a cut run keeps its checks
        name = f"plan:{a}/{p}"
        steps.report(name, [name],
                     **steps.run(name, lambda: plan_anchor(device, a, p)))

    mono = steps.run("sharded:1000/monolithic",
                     lambda: sharded_monolithic_1000(device))
    shard = steps.run("sharded:1000", lambda: sharded_1000(device))
    big, _ = steps.run("sharded:10000",
                       lambda: sharded_large([device], 10_000))
    _devices_of(mono, device)
    steps.report("sharded",
                 ["sharded:1000/monolithic", "sharded:1000", "sharded:10000"],
                 parity_1000_max_rel_dev=_compare_plans(shard, mono,
                                                        PARITY_RTOL),
                 devices_1000=_devices_of(shard, device, committed=False),
                 n10000=big)

    out = steps.run("replay", lambda: replay_incident(device))
    steps.report("replay", ["replay"], **out)


def _lane_split_check(devices, n):
    """Group lanes really split across the mesh: every bucket is a
    multiple of the mesh size, each device holds an equal lane block of
    the λ-path outputs, and the per-shard partial Σb add up to the
    psummed total the host loop reads."""
    import jax
    import jax.numpy as jnp

    from repro.configs.paper_tables import mixed_spec
    from repro.core.decompose import _group_programs, build_groups
    from repro.parallel.sharding import planner_mesh

    mesh = planner_mesh(devices)
    size = len(devices)
    spec = mixed_spec(n)
    gains = spec.sample_gains(jax.random.PRNGKey(SHARD_KEY))
    planner = _shard_planner()
    st = planner._statics()
    progs = _group_programs(mesh, st["policy"], st["pccp_iters"],
                            st["solver"], st["pccp_gated"], st["channel_cv"])
    groups = build_groups(spec, gains, mesh)
    out = []
    B = jnp.asarray(SHARD_B_PER_DEVICE * n, jnp.float64)
    for g in groups:
        if g.n_pad % size:
            raise AssertionError(f"bucket {g.n_pad} not a multiple of {size}")
        m0 = jnp.full((1, g.n_pad), g.fleet.max_points - 1, jnp.int32)
        dl = jnp.full((g.n_pad,), SHARD_DEADLINE, jnp.float64)
        ep = jnp.full((g.n_pad,), SHARD_EPS, jnp.float64)
        prep = progs.prep(g.fleet, m0, dl, ep, B)
        ll, nd = jnp.zeros((1,), jnp.float64), jnp.zeros((1,), bool)
        b = progs.solve(prep, B, ll, nd)[0]
        total = float(progs.bsum(prep, g.w, B, ll, nd)[0])
        shards = b.addressable_shards
        if len({s.device for s in shards}) != size:
            raise AssertionError(f"lanes on {len(shards)} devices, not {size}")
        if {s.data.shape[1] for s in shards} != {g.n_pad // size}:
            raise AssertionError("uneven lane blocks")
        w = np.asarray(g.w)
        parts = []
        for s in shards:
            lanes = s.index[1]
            parts.append(float(np.sum(w[lanes] * np.asarray(s.data)[0])))
        rel = abs(sum(parts) - total) / max(abs(total), 1e-300)
        if rel > 1e-12:
            raise AssertionError(f"per-shard partial sums off by {rel:.3e}")
        out.append({"n_pad": g.n_pad, "lanes_per_device": g.n_pad // size,
                    "partial_sums": parts, "psum": total,
                    "partial_sum_rel_dev": rel})
    return out


def run_four_chips(devices, clock, n=100_000):
    steps = Steps(clock)
    four, p4 = steps.run("sharded:4", lambda: sharded_large(devices, n))
    one, p1 = steps.run("sharded:1", lambda: sharded_large(devices[:1], n))
    dev = _compare_plans(p4, p1, PARITY_RTOL)
    split = steps.run("lane_split", lambda: _lane_split_check(devices, n))
    steps.report("sharded_4chip", ["sharded:4", "sharded:1", "lane_split"],
                 mesh4=four, mesh1=one, parity_max_rel_dev=dev,
                 lane_split=split)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    faulthandler.enable()  # a native crash prints the Python stack
    sys.path.insert(0, os.path.join(ROOT, "src"))
    _setup()
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(devices[:4], clock)
    else:
        run_one_chip(devices[0], clock)
    print(json.dumps({
        "total_wall_s": time.perf_counter() - t0,
        "total_compile_s": clock.secs["backend_s"],
        "cache_hits": clock.hits,
        "host_peak_rss_gib":
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20}),
        flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One cell's timed path: a deployment (config), a traffic mix, a seed.

The config file holds the deployment: its groups of devices (chain,
platform, count and each group's own scenario: deadline, ε and the
per-device share of the uplink), the channel and the planner's
settings. A group's chain is the paper's table columns, or, where the
config names a ``"kind"``, what that kind's files say:

- ``chipbench/deployments/<kind>.py`` gives ``device_specs(config)``,
  the groups' ``DeviceSpec``s in config order;
- ``chipbench/references/<kind>.py`` is its plain reference, in the
  interface of ``chipbench.reference`` (see ``chipbench.check``).

The traffic file names the entry a client calls:

- ``"entry": "plan_sharded"``: ``Planner.plan_sharded`` of the whole
  fleet on a ``planner_mesh`` of the cell's chips (its lanes split
  across them), every device held to its group's deadline and ε, the
  groups' shares of the uplink pooled into one budget;
- any other name: ``chipbench/entries/<entry>.py``, which gives
  ``call(cell, gains)``, the planner's result, and ``fetch(result)``,
  its ``Answer`` on the host.

Request ``i`` draws a fresh fleet (link gains) from ``(seed, i)`` on the
first chip and makes one call; its plan is synced and fetched to the
host as a caller would. Only the system under test comes from the
program (``repro``).
"""
from __future__ import annotations

import contextlib
import time
from typing import NamedTuple

import numpy as np

from chipbench import run

BUILT_IN_ENTRY = "plan_sharded"


class Scenario(NamedTuple):
    deadline_s: np.ndarray  # (N,) s
    eps: np.ndarray  # (N,)
    bandwidth_hz: float  # the pooled uplink budget


class Answer(NamedTuple):
    """A request's plan as fetched to the host, one entry per device."""

    m: np.ndarray  # (N,) int
    b: np.ndarray  # (N,) Hz
    f: np.ndarray  # (N,) Hz
    feasible: np.ndarray  # (N,) bool
    total_energy: float  # J
    status: int  # the planner's status code (0 = ok)


def scenario(config: dict) -> Scenario:
    """The deployment's scenario: each device's deadline and ε from its
    group, and the budget that pools every device's share of the uplink."""
    groups = config["groups"]
    per = lambda key: np.concatenate(
        [np.full(int(g["count"]), float(g["scenario"][key])) for g in groups])
    share = lambda g: (float(g["scenario"]["paper_bandwidth_hz"])
                       / int(g["scenario"]["paper_devices"]))
    return Scenario(per("deadline_s"), per("eps"),
                    sum(int(g["count"]) * share(g) for g in groups))


def device_specs(config: dict) -> tuple:
    """The deployment's groups, ``DeviceSpec``s in config order: those of
    the config's kind, or the paper's table columns where it names none."""
    if "kind" in config:
        return run.load_module("deployments", config["kind"]).device_specs(config)
    from repro.configs.paper_tables import build_chain
    from repro.core import DeviceSpec

    groups = []
    for gr in config["groups"]:
        ch, pl = gr["chain"], gr["platform"]
        groups.append(DeviceSpec(
            chain=build_chain(ch["d_mb"], ch["w_gflops"], ch["g_eff"],
                              ch["v_loc_ms2"], ch["vm_full_s"], ch["vm_cv"]),
            kappa=pl["kappa"], f_min_hz=pl["f_min_hz"],
            f_max_hz=pl["f_max_hz"], p_tx_w=pl["p_tx_w"],
            count=int(gr["count"]), name=gr["name"]))
    return tuple(groups)


def plan_sharded(cell, gains):
    """The built-in entry: one ``plan_sharded`` of the cell's fleet."""
    return cell.planner.plan_sharded(cell.spec, cell.plan_scenario,
                                     gains=gains, mesh=cell.mesh)


def fetch_plan(p) -> Answer:
    """A ``Plan``'s answer, synced and copied to the host."""
    import jax

    m, b, f, feas, e, st = jax.device_get(
        (p.m_sel, p.alloc.b, p.alloc.f, p.feasible, p.total_energy, p.status))
    return Answer(m=np.asarray(m), b=np.asarray(b), f=np.asarray(f),
                  feasible=np.asarray(feas), total_energy=float(e),
                  status=int(st))


def entry(name: str):
    """``(call, fetch)`` of the traffic's entry ``name``."""
    if name == BUILT_IN_ENTRY:
        return plan_sharded, fetch_plan
    mod = run.load_module("entries", name)
    return mod.call, mod.fetch


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Cell:
    """Builds the planner and the fleet spec of a config, and runs
    requests against them on ``devices`` (a list). Nothing here compiles
    until the first request."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.core import FleetSpec, Planner, PlannerConfig
        from repro.core import Scenario as PlanScenario
        from repro.parallel.sharding import planner_mesh

        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = list(devices)
        self.call, self.fetch = entry(traffic["entry"])
        self.scenario = scenario(config)
        ch = config["channel"]
        self.spec = FleetSpec(device_specs(config), area_m=ch["area_m"],
                              min_dist_m=ch["min_dist_m"])
        pl = config["planner"]
        self.planner = Planner(PlannerConfig(
            policy=pl["policy"], outer_iters=int(pl["outer_iters"]),
            pccp_iters=int(pl["pccp_iters"]),
            multi_start=bool(pl["multi_start"])))
        self.plan_scenario = PlanScenario(*self.scenario)
        self.mesh = planner_mesh(self.devices)
        self.trace_spans = False
        #: seconds of the last request's draw, plan and fetch
        self.last_split = (0.0, 0.0, 0.0)

    def gains(self, i: int):
        """Request ``i``'s link gains, on the first device (the planner
        copies them to the host to split them into groups)."""
        from chipbench import fleetgen

        return fleetgen.gains_for(self.config, self.seed, i, self.devices[0])

    def request(self, i: int) -> Answer:
        """Draw request ``i``'s fleet, plan it with the traffic's entry,
        fetch the plan."""
        on = self.trace_spans
        t0 = time.perf_counter()
        with _span("chipbench.draw", on):
            gains = self.gains(i)
        t1 = time.perf_counter()
        with _span("chipbench.plan", on):
            p = self.call(self, gains)
        t2 = time.perf_counter()
        with _span("chipbench.fetch", on):
            ans = self.fetch(p)
        self.last_split = (t1 - t0, t2 - t1, time.perf_counter() - t2)
        return ans

"""The comparison that decides ``correct``: the program's plans against the
plain reference of the deployment's kind, number by number.

The reference is ``chipbench.reference`` for the paper's table columns
(a config that names no ``"kind"``), and ``chipbench/references/<kind>.py``
for a kind of its own. Either imports nothing of the program and gives

- ``deployment(config, gains, dtype=np.float64)``: the per-device
  columns of the config's fleet for the link gains of R requests;
- ``plan(dep, deadlines, epss, Bs, outer_iters, multi_start)``: the
  plans of every request's fleet under K scenarios (``RefPlan``);
- ``constraint_excess(dep, snr_b, m, b, f, deadline, eps)``: each
  device's deadline excess at its plan, relative to its D.

For every checked request the reference plans the same deployment from
the same link gains, in float64 on the host, and the comparison reads:

- ``partition_mismatch``: devices whose partition point differs;
- ``feasible_mismatch``: devices whose feasibility flag differs;
- ``energy_rel_gap``: |E − E_ref| / E_ref of the plan's total energy;
- ``constraint_excess``: how far the plan breaks its own constraints,
  evaluated in float64 by the reference's formulas: the largest
  deadline excess of a device the reference plans feasible, over its D,
  or Σb over B, whichever is larger (0 when every constraint holds).

Each number is the largest over the checked requests. The limits sit
between the largest reading of sound runs of the program on a TPU v5e
and the least reading of the control (the reference computed in
float32, ``chipbench.control``); the readings they were set from are in
PERF.md.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference, run

#: number -> limit (a run is correct when every number is ≤ its limit)
LIMITS = {
    "partition_mismatch": 0,
    "feasible_mismatch": 0,
    # sound ≤ 4.6e-10, control ≥ 6.9e-2
    "energy_rel_gap": 1e-5,
    # sound ≤ 1.5e-10 (the planner's own check allows 1e-9 s of a
    # 0.12 s deadline, 8.3e-9), control ≥ 1.17e-7
    "constraint_excess": 2.5e-8,
}


def reference_of(config: dict):
    """The plain reference of the config's deployment kind."""
    if "kind" in config:
        return run.load_module("references", config["kind"])
    return reference


def constraint_excess(ref_mod, dep, snr_b, m, b, f, deadline, eps, B,
                      feasible):
    """The plan's worst constraint excess, relative: the deadline excess
    of the ``feasible`` devices by the reference ``ref_mod``'s formula,
    and Σb over B, in float64."""
    late = np.where(feasible, ref_mod.constraint_excess(
        dep, snr_b, m, b, f, deadline, eps), 0.0)
    return max(0.0, float(np.max(late)), float(b.sum() / B - 1.0))


def compare(ref_mod, ref: reference.RefPlan, r: int, dep, ans, sc) -> dict:
    """The numbers of one request's plan (``ans``, a ``cell.Answer``)
    against the reference plan of fleet ``r`` of the same deployment
    under the scenario ``sc``, by the reference ``ref_mod``."""
    m = np.asarray(ans.m)
    b, f = np.asarray(ans.b, np.float64), np.asarray(ans.f, np.float64)
    rm, rf = ref.m[r, 0], ref.feasible[r, 0]
    return {
        "partition_mismatch": int(np.sum(m != rm)),
        "feasible_mismatch": int(np.sum(np.asarray(ans.feasible) != rf)),
        "energy_rel_gap": abs(float(ans.total_energy)
                              / float(ref.total_energy[r, 0]) - 1.0),
        "constraint_excess": constraint_excess(
            ref_mod, dep, dep.snr_b[r], m, b, f, sc.deadline_s, sc.eps,
            sc.bandwidth_hz, rf),
    }


def worst(numbers: list[dict]) -> dict:
    """Each number's largest reading over ``numbers`` (NaN counts as the
    largest: a plan that reads NaN is not correct)."""
    out = {}
    for name in LIMITS:
        vals = [n[name] for n in numbers]
        out[name] = (float("nan") if any(v != v for v in vals)
                     else max(vals))
    return out


def verdict(numbers: dict) -> bool:
    """True when every number is within its limit."""
    return all(numbers[k] <= lim for k, lim in LIMITS.items())


def report(numbers: dict) -> dict:
    """``{name: {"value": v, "limit": l}}`` in ``LIMITS`` order."""
    return {k: {"value": numbers[k], "limit": lim} for k, lim in LIMITS.items()}


def check_answers(config: dict, sc, answers: dict, gains_of) -> dict:
    """The worst numbers over ``answers`` ({request index: Answer}) of
    the scenario ``sc``, each request's link gains from ``gains_of(i)``;
    the reference plans every request at once, each start in a process
    of its own."""
    keys = list(answers)
    ref_mod = reference_of(config)
    dep = ref_mod.deployment(config, np.stack([gains_of(i) for i in keys]))
    pl = config["planner"]
    ref = ref_mod.plan(dep, [sc.deadline_s], [sc.eps], [sc.bandwidth_hz],
                       outer_iters=pl["outer_iters"],
                       multi_start=pl["multi_start"])
    return worst([compare(ref_mod, ref, r, dep, answers[i], sc)
                  for r, i in enumerate(keys)])

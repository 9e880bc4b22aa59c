"""Readings that the limits of ``chipbench.check`` are set from.

    python3 -m chipbench.control --workload <cell> --seeds 1 2 3 ... \
        [--requests 3] [--control-seeds 3] [--out FILE]

For each seed it drives the cell's timed path (``Cell.request``) through
``--requests`` requests and prints the comparison's numbers: the
program's readings. For the first ``--control-seeds`` seeds it also puts
the control in the program's place, the plain reference computed one
precision lower (float32 for the configs' float64), and prints its
numbers against the float64 reference: the control's readings. The
control has to come out as not correct.

One JSON line per (seed, side); ``--out`` also writes them to a file. It
starts as ``chipbench.run`` does (``run.start``), every seed in one
process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from chipbench import check, reference, run
from chipbench.cell import Answer


def control_answers(config: dict, sc, gains: dict) -> dict:
    """The control's plans of the requests whose gains are ``gains``
    ({request index: (N,) gains}) under the scenario ``sc``: the
    reference in float32."""
    keys = list(gains)
    dep = reference.deployment(config, np.stack([gains[i] for i in keys]),
                               np.float32)
    pl = config["planner"]
    p = reference.plan(dep, [sc.deadline_s], [sc.eps], [sc.bandwidth_hz],
                       outer_iters=pl["outer_iters"],
                       multi_start=pl["multi_start"])
    return {i: Answer(m=p.m[r, 0], b=p.b[r, 0], f=p.f[r, 0],
                      feasible=p.feasible[r, 0],
                      total_energy=float(p.total_energy[r, 0]), status=0)
            for r, i in enumerate(keys)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    started = run.start(args.workload)
    if isinstance(started, int):
        return started
    entry, devices = started
    from chipbench.cell import Cell

    config = run.load_config(entry["config"])
    traffic = run.load_traffic(entry["traffic"])
    lines = []
    for j, seed in enumerate(args.seeds):
        cell = Cell(config, traffic, seed, devices)
        reqs = range(args.requests)
        answers = {i: cell.request(i) for i in reqs}
        gains = {i: np.asarray(cell.gains(i)) for i in reqs}
        sides = [("program", answers)]
        if j < args.control_seeds:
            sides.append(("control", control_answers(config, cell.scenario,
                                                     gains)))
        for side, ans in sides:
            numbers = check.check_answers(config, cell.scenario, ans,
                                          gains.__getitem__)
            line = {"workload": args.workload, "seed": seed, "side": side,
                    "correct": check.verdict(numbers), **numbers}
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

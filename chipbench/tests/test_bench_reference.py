"""The reference with each start in a process of its own plans the same
bits as ``alternate`` over every start in one process, with the plan of
the fewest infeasible devices, then the least energy, kept: several
fleets (R = 2) and scenarios (K = 2) of the two-group deployment, the
scenario per device."""
import copy

import numpy as np
import pytest

from chipbench import cell as cell_mod
from chipbench import reference, run


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_parallel_plan_is_the_serial_plan(dtype):
    config = copy.deepcopy(run.load_config("mixed-1e4"))
    for g in config["groups"]:
        g["count"] = 48
    sc = cell_mod.scenario(config)
    rng = np.random.default_rng(3000000067)
    r = np.maximum(np.hypot(*rng.uniform(-200.0, 200.0, (2, 2, 96))), 5.0)
    dep = reference.deployment(config, 10.0 ** (-(38 + 30 * np.log10(r)) / 10),
                               dtype)
    # the second scenario tighter: later deadlines, half the budget
    deadlines = [sc.deadline_s, 1.1 * sc.deadline_s]
    epss, Bs = [sc.eps, sc.eps], [sc.bandwidth_hz, 0.5 * sc.bandwidth_hz]
    pooled = reference.plan(dep, deadlines, epss, Bs)
    assert pooled.m.shape == (2, 2, 96)

    col = lambda x: np.asarray(x, dtype).reshape(1, 2, 1, -1)
    s0 = reference.starts(dep, True)
    assert len(s0) > 1
    m, b, f, energy, feasible = reference.alternate(
        dep, np.broadcast_to(s0, (2, 2) + s0.shape), col(deadlines),
        col(epss), col(Bs), 3)
    total = energy.sum(axis=-1, dtype=dtype)
    bad = (~feasible).sum(axis=-1)
    for r_ in range(2):
        for k in range(2):
            j = min(range(len(s0)), key=lambda j: (bad[r_, k, j],
                                                   total[r_, k, j]))
            want = (m, b, f, energy, feasible)
            for name, a in zip(("m", "b", "f", "energy", "feasible"), want):
                got = getattr(pooled, name)[r_, k]
                assert got.dtype == a.dtype, name
                assert np.array_equal(got, a[r_, k, j]), name
            assert pooled.total_energy[r_, k] == total[r_, k, j]

"""The deployment is the paper's: its tables, its scenarios, and seed 0's
request 0 is the fleet behind the repository's golden plans."""
import copy
import json
import os

import jax
import numpy as np
import pytest

from chipbench import cell as cell_mod
from chipbench import fleetgen, reference, run
from chipbench.cell import Cell

GOLDEN = os.path.join(run.ROOT, "tests", "golden", "seed_plans.json")


@pytest.fixture(scope="module")
def config():
    return run.load_config("mixed-1e4")


def _alexnet12(config):
    """The paper's AlexNet fleet alone: 12 devices of the config's first
    group, which bring the paper's 10 MHz between them."""
    one = copy.deepcopy(config)
    one["groups"] = [g for g in one["groups"] if g["name"] == "alexnet"]
    one["groups"][0]["count"] = one["groups"][0]["scenario"]["paper_devices"]
    return one


@pytest.mark.parametrize("group,fleet_fn", [
    ("alexnet", "alexnet_fleet"), ("resnet152", "resnet152_fleet")])
def test_config_tables_are_the_papers(config, group, fleet_fn):
    from repro.configs import paper_tables as pt

    one = copy.deepcopy(config)
    one["groups"] = [g for g in one["groups"] if g["name"] == group]
    one["groups"][0]["count"] = 12
    cell = Cell(one, run.load_traffic("sharded"), 0, [jax.devices()[0]])
    ours = cell.spec.build(gains=np.ones(12))
    paper = getattr(pt, fleet_fn)(jax.random.PRNGKey(0), 12)
    for a, b in zip(jax.tree_util.tree_leaves(ours.chain),
                    jax.tree_util.tree_leaves(paper.chain), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(ours.platform, paper.platform, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scenario_is_the_papers_per_device(config):
    from repro.configs import paper_tables as pt

    sc = cell_mod.scenario(config)
    counts = [g["count"] for g in config["groups"]]
    assert [g["name"] for g in config["groups"]] == ["alexnet", "resnet152"]
    for paper, rows in ((pt.ALEXNET_SCENARIO, slice(0, counts[0])),
                        (pt.RESNET152_SCENARIO, slice(counts[0], None))):
        assert np.all(sc.deadline_s[rows] == paper.deadline_s)
        assert np.all(sc.eps[rows] == paper.eps)
    share = (counts[0] * pt.ALEXNET_SCENARIO.bandwidth_hz
             + counts[1] * pt.RESNET152_SCENARIO.bandwidth_hz) / 12
    assert sc.bandwidth_hz == pytest.approx(share, rel=1e-15)
    assert cell_mod.scenario(_alexnet12(config)).bandwidth_hz == pytest.approx(
        pt.ALEXNET_SCENARIO.bandwidth_hz, rel=1e-15)


def test_seed0_request0_is_the_golden_fleet(config):
    from repro.configs import paper_tables as pt

    one = _alexnet12(config)
    gains = np.asarray(fleetgen.gains_for(one, 0, 0))
    paper = np.asarray(pt.alexnet_fleet(jax.random.PRNGKey(0), 12).link.gain)
    # one fused program against the program's op-by-op draw: a few ulp
    np.testing.assert_allclose(gains, paper, rtol=1e-14, atol=0)
    # request 1 is another fleet
    assert not np.allclose(np.asarray(fleetgen.gains_for(one, 0, 1)), gains)


def test_seed0_request0_plans_the_golden_plan(config):
    with open(GOLDEN) as f:
        golden = json.load(f)["alexnet/robust_exact"]
    cell = Cell(_alexnet12(config), run.load_traffic("sharded"), 0,
                [jax.devices()[0]])
    ans = cell.request(0)
    assert ans.m.tolist() == golden["m_sel"]
    assert ans.feasible.astype(int).tolist() == golden["feasible"]
    np.testing.assert_allclose(ans.total_energy, golden["total_energy"],
                               rtol=1e-8)


def test_reference_takes_per_device_scenarios(config):
    """A scenario given per device plans as the same scenario given once."""
    one = _alexnet12(config)
    dep = reference.deployment(one, np.asarray(fleetgen.gains_for(one, 0, 0)))
    n = 12
    scalar = reference.plan(dep, [0.18], [0.02], [10e6])
    per_device = reference.plan(dep, [np.full(n, 0.18)], [np.full(n, 0.02)],
                                [10e6])
    for a, b in zip(scalar, per_device, strict=True):
        np.testing.assert_array_equal(a, b)

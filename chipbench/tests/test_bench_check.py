"""The comparison that decides ``correct`` fails what it has to fail.

- The control, the plain reference computed in float32, is not correct
  against the float64 reference.
- A run of the harness (everything but its look for a chip) whose timed
  path is broken underneath comes out not correct: an answer altered
  where the planner produces it (a device's bandwidth, or its partition
  point), and half of the fleet left out (its plan taken from the other
  half). The sound run is correct.

The mixed deployment runs at 64 devices here; the chip runs it at 10 000.
"""
import copy
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell as cell_mod
from chipbench import check, control, fleetgen, run

SMALL_MIXED = 64
LOAD_CONFIG = run.load_config
ENTRY = {"name": "mixed-1e4.plan", "config": "mixed-1e4", "traffic": "sharded",
         "chips": 1, "per_layer": [],
         "end_to_end": [{"name": "setup_s", "unit": "s"}]}


def _config(name):
    config = copy.deepcopy(LOAD_CONFIG(name))
    for g in config["groups"]:
        g["count"] = SMALL_MIXED // 2
    return config


@pytest.mark.parametrize("seed", [3000000041, 3000000043, 3000000047])
def test_control_is_not_correct(seed):
    config = _config(ENTRY["config"])
    sc = cell_mod.scenario(config)
    gains = {i: np.asarray(fleetgen.gains_for(config, seed, i))
             for i in range(2)}
    answers = control.control_answers(config, sc, gains)
    numbers = check.check_answers(config, sc, answers, gains.__getitem__)
    assert not check.verdict(numbers), numbers


def _alter_b(p):
    return p._replace(alloc=p.alloc._replace(
        b=p.alloc.b.at[0].multiply(1.0 + 1e-4)))


def _alter_m(p):
    return p._replace(m_sel=p.m_sel.at[0].set(jnp.maximum(p.m_sel[0] - 1, 0)))


def _half_fleet(p):
    """The second half of the devices given the first half's plan."""
    n = p.m_sel.shape[0]

    def half(x):
        if x.ndim == 0 or x.shape[0] != n:
            return x
        return jnp.concatenate([x[:n // 2], x[:n - n // 2]])
    return jax.tree_util.tree_map(half, p)


FAULTS = {"none": None, "bandwidth": _alter_b, "partition": _alter_m,
          "half_fleet": _half_fleet}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.core import Planner

    monkeypatch.setattr(run, "load_config", _config)
    if FAULTS[fault] is not None:
        produce = Planner.plan_sharded
        monkeypatch.setattr(
            Planner, "plan_sharded",
            lambda self, *a, **k: FAULTS[fault](produce(self, *a, **k)))
    result = run.run_cell(ENTRY, 3000000053, 0.5, False, [jax.devices()[0]],
                          out=io.StringIO())
    assert result["attempted"] >= 1
    assert result["correct"] is (fault == "none"), result["check"]
    assert list(result)[-1] == "check"

"""A deployment of a kind of its own arrives as files only.

The fixture (``tests/data/new_kind/``) holds a config of the kind
``layer_list`` (a chain stated layer by layer, with the edge's time and
variance per layer), that kind's program side and plain reference, and
a traffic mix whose entry, ``plan_sharded_host``, is a file too. They
are added to a copy of the harness whose other files are this one's,
byte for byte, and a child process that imports the copy runs
``run_cell`` on the CPU: it builds the fleet, plans a traced request
through the entry and judges it against the kind's reference, which
also fails the kind's control. The default kind, the paper's tables of ``mixed-1e4`` at 64 devices, runs
through the same body.
"""
import ast
import copy
import io
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench import cell as cell_mod
from chipbench import check, control, fleetgen, reference, run

FIXTURE = os.path.join(run.HERE, "tests", "data", "new_kind")
SEED = 3000000071
SMALL_MIXED = 64
#: kind -> (config, traffic) of the case
CASES = {"paper_tables": ("mixed-1e4", "sharded"),
         "layer_list": ("layer_list-64", "host_gains")}
READERS = ("price_probes_per_plan", "price_wait_ms", "price_host_ms")
LOAD_CONFIG = run.load_config


def _small(name):
    config = copy.deepcopy(LOAD_CONFIG(name))
    for g in config["groups"]:
        g["count"] = SMALL_MIXED // 2
    return config


def kinds_child():
    """In a process that imports the copied harness: one traced run of
    each case; one JSON line."""
    jax.config.update("jax_threefry_partitionable", False)
    jax.config.update("jax_enable_x64", True)
    out = {"harness": run.HERE}
    for kind, (config, traffic) in CASES.items():
        run.load_config = _small if kind == "paper_tables" else LOAD_CONFIG
        entry = {"name": f"{config}.{traffic}", "config": config,
                 "traffic": traffic, "chips": 1,
                 "per_layer": [{"name": r, "unit": "x"} for r in READERS],
                 "end_to_end": [{"name": "setup_s", "unit": "s"}]}
        cfg = run.load_config(config)
        cell = cell_mod.Cell(cfg, run.load_traffic(traffic), SEED,
                             jax.devices()[:1])
        result = run.run_cell(entry, SEED, 0.5, True, jax.devices()[:1],
                              out=io.StringIO())
        gains = {0: np.asarray(cell.gains(0))}
        numbers = check.check_answers(
            cfg, cell.scenario,
            control.control_answers(cfg, cell.scenario, gains),
            gains.__getitem__)
        out[kind] = {"result": result, "control": numbers,
                     "reference": check.reference_of(cfg).__name__,
                     "call": cell.call.__module__,
                     "points": [g.chain.num_points for g in cell.spec.groups],
                     "devices": cell.spec.num_devices}
    run.load_config = LOAD_CONFIG
    print(json.dumps(out))


@pytest.fixture(scope="module")
def copied(tmp_path_factory):
    """The copy of the harness with the fixture's files added, and what
    its child process reports."""
    root = tmp_path_factory.mktemp("harness")
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(run.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    added = []
    for dirpath, _, files in os.walk(FIXTURE):
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), FIXTURE)
            dst = root / "chipbench" / rel
            assert not dst.exists(), f"{rel} is not a new file"
            shutil.copy(os.path.join(dirpath, f), dst)
            added.append(rel)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root),
                                           os.path.join(run.ROOT, "src")]))
    p = subprocess.run(
        [sys.executable, "-c", "from chipbench.tests.test_bench_kinds import "
         "kinds_child; kinds_child()"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return root, sorted(added), json.loads(p.stdout.strip().splitlines()[-1])


def test_fixture_adds_files_only(copied):
    root, added, got = copied
    assert got["harness"] == str(root / "chipbench")
    assert added == ["configs/layer_list-64.json",
                     "deployments/layer_list.py", "entries/plan_sharded_host.py",
                     "references/layer_list.py", "traffic/host_gains.json"]
    for name in ("cell.py", "check.py", "run.py", "reference.py", "trace.py"):
        with open(os.path.join(run.HERE, name), "rb") as a, \
                open(root / "chipbench" / name, "rb") as b:
            assert a.read() == b.read(), name


@pytest.mark.parametrize("kind", list(CASES))
def test_a_kind_plans_and_is_judged(copied, kind):
    got = copied[2][kind]
    r = got["result"]
    assert r["correct"] and r["attempted"] >= 1 and r["failed"] == 0, r
    assert set(r["check"]) == set(check.LIMITS)
    # the control, the kind's reference in float32, is not correct
    assert not check.verdict(got["control"]), got["control"]
    assert r["device"]["count"] == 1
    # the request's spans hold the entry: the readers see its price loop
    for name in READERS:
        assert r["metrics"][name]["value"] > 0, name
    # the CPU backend's trace has no device plane, so no idle gap
    assert r["breakdown"]["program_idle_gaps"] == r["breakdown"]["idle_gaps"]
    assert got["devices"] == SMALL_MIXED and got["points"] == [9, 10]
    if kind == "layer_list":
        assert got["reference"] == "chipbench.references.layer_list"
        assert got["call"] == "chipbench.entries.plan_sharded_host"
    else:
        assert got["reference"] == "chipbench.reference"
        assert got["call"] == "chipbench.cell"


def test_default_kind_is_as_before():
    """The paper's tables build the same groups as the harness built them
    inline, and the check's constraint excess reads the same bits as its
    inline formula did."""
    from repro.configs.paper_tables import build_chain
    from repro.core import DeviceSpec

    config = _small("mixed-1e4")
    before = []
    for gr in config["groups"]:
        ch, pl = gr["chain"], gr["platform"]
        before.append(DeviceSpec(
            chain=build_chain(ch["d_mb"], ch["w_gflops"], ch["g_eff"],
                              ch["v_loc_ms2"], ch["vm_full_s"], ch["vm_cv"]),
            kappa=pl["kappa"], f_min_hz=pl["f_min_hz"],
            f_max_hz=pl["f_max_hz"], p_tx_w=pl["p_tx_w"],
            count=int(gr["count"]), name=gr["name"]))
    now = cell_mod.device_specs(config)
    assert len(now) == len(before)
    for a, b in zip(now, before, strict=True):
        for x, y in zip(a.chain, b.chain, strict=True):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert ((a.kappa, a.f_min_hz, a.f_max_hz, a.p_tx_w, a.count, a.name)
                == (b.kappa, b.f_min_hz, b.f_max_hz, b.p_tx_w, b.count,
                    b.name))

    assert check.reference_of(config) is reference
    sc = cell_mod.scenario(config)
    gains = np.asarray(fleetgen.gains_for(config, SEED, 0))[None]
    dep = reference.deployment(config, gains)
    p = reference.plan(dep, [sc.deadline_s], [sc.eps], [sc.bandwidth_hz])
    m, b, f, feas = p.m[0, 0], p.b[0, 0], p.f[0, 0], p.feasible[0, 0]
    # a plan a hair off its deadlines, so that the excess is not 0
    f = f * (1 - 1e-7)
    take = lambda a: np.take_along_axis(a, m[:, None], 1)[:, 0]
    t = (take(dep.w) / (take(dep.g) * f)
         + take(dep.d) / reference.rate(b, dep.snr_b[0]) + take(dep.t_vm)
         + reference.sigma(sc.eps) * np.sqrt(take(dep.v)))
    late = np.where(feas, (t - sc.deadline_s) / sc.deadline_s, 0.0)
    inline = max(0.0, float(np.max(late)), float(b.sum() / sc.bandwidth_hz - 1))
    got = check.constraint_excess(reference, dep, dep.snr_b[0], m, b, f,
                                  sc.deadline_s, sc.eps, sc.bandwidth_hz, feas)
    assert inline > 0 and got == inline


def _imports(path):
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""


REFERENCES = [os.path.join(run.HERE, "reference.py")] + sorted(
    os.path.join(d, f)
    for d in (os.path.join(run.HERE, "references"),
              os.path.join(FIXTURE, "references"))
    for f in os.listdir(d) if f.endswith(".py"))


@pytest.mark.parametrize("path", REFERENCES,
                         ids=lambda p: os.path.relpath(p, run.HERE))
def test_reference_imports_nothing_of_the_program(path):
    """A reference imports no ``repro`` module, and of the benchmark only
    references, which are held to the same."""
    for name in _imports(path):
        root = name.split(".")[0]
        assert root != "repro", (path, name)
        if root == "chipbench":
            assert (name == "chipbench.reference"
                    or name.startswith("chipbench.references")), (path, name)

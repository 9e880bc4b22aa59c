"""A cell on several chips: the planner's mesh spans the cell's devices
and plans as on one, and the per-chip readings are of the busiest plane
of the trace and of the fullest device.

The four-device run is a child process of its own: the CPU backend
gives four devices only where ``XLA_FLAGS`` says so before JAX starts.
The deployment is ``mixed-1e4`` at 64 devices, planned on a mesh of
four devices.
"""
import copy
import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import run, trace
from chipbench.trace import Event

SMALL_MIXED = 64
SEED = 3000000061
ENTRY = {"name": "mixed-1e4.four", "config": "mixed-1e4",
         "traffic": "sharded", "chips": 4, "per_layer": [],
         "end_to_end": [{"name": "setup_s", "unit": "s"}]}


LOAD_CONFIG = run.load_config


def _small(name):
    config = copy.deepcopy(LOAD_CONFIG(name))
    for g in config["groups"]:
        g["count"] = SMALL_MIXED // 2
    return config


def four_devices_child():
    """In a process that sees four CPU devices: one request planned on a
    four-device mesh and on a one-device mesh, the comparison with the
    reference, and a run of the harness on the four; one JSON line."""
    import jax

    from chipbench import check
    from chipbench.cell import Cell

    jax.config.update("jax_threefry_partitionable", False)
    jax.config.update("jax_enable_x64", True)
    devices = jax.devices()
    config = _small(ENTRY["config"])
    traffic = run.load_traffic(ENTRY["traffic"])
    four = Cell(config, traffic, SEED, devices)
    one = Cell(config, traffic, SEED, devices[:1])
    a4, a1 = four.request(0), one.request(0)
    numbers = check.check_answers(config, four.scenario, {0: a4},
                                  lambda i: np.asarray(four.gains(i)))
    run.load_config = _small
    result = run.run_cell(ENTRY, SEED, 0.5, False, devices,
                          out=io.StringIO())
    print(json.dumps({
        "devices": len(devices),
        "mesh": [str(d) for d in four.mesh.devices.flat],
        "mesh_one": [str(d) for d in one.mesh.devices.flat],
        "m": [a4.m.tolist(), a1.m.tolist()],
        "feasible": [a4.feasible.tolist(), a1.feasible.tolist()],
        "energy": [a4.total_energy, a1.total_energy],
        "status": [a4.status, a1.status],
        "verdict": check.verdict(numbers), "numbers": numbers,
        "run": {k: result[k] for k in ("correct", "attempted", "failed",
                                       "device")}}))


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " "
                          "--xla_force_host_platform_device_count=4").strip(),
               PYTHONPATH=os.pathsep.join(
                   [run.ROOT, os.path.join(run.ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run(
        [sys.executable, "-c", "from chipbench.tests.test_bench_mesh import "
         "four_devices_child; four_devices_child()"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_spans_the_cells_devices(four):
    assert four["devices"] == 4
    assert len(set(four["mesh"])) == 4 and len(four["mesh_one"]) == 1
    assert four["mesh_one"][0] == four["mesh"][0]


def test_four_devices_plan_as_one(four):
    a4, a1 = four["m"]
    assert len(a4) == SMALL_MIXED and a4 == a1
    assert four["feasible"][0] == four["feasible"][1]
    assert four["status"] == [0, 0]
    np.testing.assert_allclose(four["energy"][0], four["energy"][1],
                               rtol=1e-12)


def test_four_devices_plan_is_correct(four):
    assert four["verdict"], four["numbers"]
    r = four["run"]
    assert r["correct"] and r["attempted"] >= 1 and r["failed"] == 0, r
    assert r["device"]["count"] == 4


# ---------------------------------------------------------------------------
# per-chip readings
# ---------------------------------------------------------------------------

HOST = "/host:CPU"


def _plane_events(plane, extra=()):
    """The device plane of ``test_bench_trace``'s hand-built trace, and
    ``extra`` executions (name, start, end) on it."""
    runs = (("jit_old(2)", 900, 1100), ("jit_draw_gains(1)", 1200, 1300),
            ("jit__multi_start(7)", 2000, 5000),
            ("jit_broadcast(3)", 5500, 5800),
            ("jit_draw_gains(1)", 7100, 7150),
            ("jit__multi_start(7)", 8000, 10500),
            ("jit_late(4)", 11500, 12000), *extra)
    return [Event(plane, trace.MODULE_LINE, n, s, e - s) for n, s, e in runs]


def _trace(planes, extra=None):
    ev = [Event(HOST, "python3", "chipbench.window", 1000, 10000)]
    for name, s, e in (("draw", 1000, 1500), ("plan", 1500, 6000),
                       ("fetch", 6000, 7000), ("draw", 7000, 7200),
                       ("plan", 7200, 9000), ("fetch", 9000, 11000)):
        ev.append(Event(HOST, "python3", "chipbench." + name, s, e - s))
    for p in planes:
        ev += _plane_events(p, (extra or {}).get(p, ()))
    return ev


READERS = ("launches_per_plan", "plan_program_ms", "idle_share.plan")


def _read(events):
    s = trace.summarize(events)
    return s, {name: run.load_metric(name)(s) for name in READERS}


def test_four_identical_planes_read_as_one():
    one_s, one = _read(_trace(["/device:TPU:0"]))
    planes = [f"/device:TPU:{i}" for i in range(4)]
    four_s, four = _read(_trace(planes))
    assert four_s.devices == 4 and one_s.devices == 1
    for name in ("launches_per_plan", "plan_program_ms"):
        assert four[name] == one[name], name
    # averaged over the planes: the same to rounding
    assert four["idle_share.plan"] == pytest.approx(one["idle_share.plan"])
    assert four_s.busy_s == pytest.approx(one_s.busy_s)
    for got, want in ((four_s.device_ops, one_s.device_ops),
                      (four_s.idle_gaps, one_s.idle_gaps)):
        assert [k for k, _ in got] == [k for k, _ in want]
        assert [v for _, v in got] == pytest.approx([v for _, v in want])
    # the plain tally still adds every plane
    assert four_s.modules["jit__multi_start(7)"][0] == 4 * 2


def test_the_busiest_plane_sets_the_reading():
    planes = [f"/device:TPU:{i}" for i in range(4)]
    extra = {planes[2]: (("jit_bsum(9)", 5000, 5400),
                         ("jit_bsum(9)", 6000, 6300))}
    s, got = _read(_trace(planes, extra))
    # plane 2: 4 + 2 executions, 5900 + 700 ns, over 2 requests
    assert got["launches_per_plan"] == pytest.approx(3.0)
    assert got["plan_program_ms"] == pytest.approx(6600e-9 * 1e3 / 2)
    assert trace.program_modules(s) == (6, pytest.approx(6600e-9))
    # the breakdown is a plane's average
    assert dict(s.device_ops)["jit_bsum(9)"] == pytest.approx(700e-9 / 4)


class _Device:
    def __init__(self, peak):
        self.peak = peak

    def memory_stats(self):
        return None if self.peak is None else {"peak_bytes_in_use": self.peak}


def test_memory_peak_is_the_fullest_devices():
    assert run._memory_peak([_Device(5), _Device(9), _Device(7)]) == 9
    assert run._memory_peak([_Device(None), _Device(3)]) == 3
    assert run._memory_peak([_Device(11)]) == 11

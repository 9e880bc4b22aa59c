"""Lookup by name: every cell of BENCHMARK.json finds its config, its
traffic mix and its per-layer metric readers; unknown names fail."""
import pytest

from chipbench import cell as cell_mod
from chipbench import run


@pytest.fixture(scope="module")
def bench():
    return run.load_benchmark()


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = run.cell_of(bench, w["name"])
        config = run.load_config(cell["config"])
        assert config["name"] == cell["config"]
        assert run.load_traffic(cell["traffic"])["entry"] in cell_mod.ENTRIES
        e2e = [m["name"] for m in cell["end_to_end"]]
        assert "setup_s" in e2e and len(e2e) >= 2, e2e
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert callable(run.load_metric(m["name"]))
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_config_files_are_listed(bench):
    for c in bench["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert run.load_config(c["name"])["reduced"] == c["reduced"]


@pytest.mark.parametrize("load", [run.load_config, run.load_traffic,
                                  run.load_metric])
def test_unknown_name_fails(load):
    with pytest.raises(LookupError, match="known"):
        load("no-such-name")


def test_unknown_workload_fails(bench):
    with pytest.raises(LookupError, match="mixed-1e4.plan"):
        run.cell_of(bench, "no-such-cell")

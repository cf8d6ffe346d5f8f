"""CPU rehearsal of the benchmark harness: every cell file loads and names a
configuration and a driver that exist, builds its ScenarioSpec, and agrees
with BENCHMARK.json; a cell dropped into another directory is found by name;
and the measurement path refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "bench",
                                                      "workloads"))
               if f.endswith(".json"))


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_and_builds_its_spec(name):
    cell = harness.load_cell(name)
    config = harness.load_config(cell["config"])
    assert os.path.exists(os.path.join(ROOT, "bench", "drivers",
                                       f"{cell['driver']}.py"))
    spec = harness.scenario_spec(config)
    assert spec.name == config["name"]
    assert spec.num_samples == config["scenario"]["num_samples"]
    assert cell["chips"] in (1, 4)
    assert set(cell["limits"]) and all(v >= 0 for v in cell["limits"].values())


def test_benchmark_json_agrees_with_the_cell_files():
    listed = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert sorted(listed) == CELLS
    for name, w in listed.items():
        cell = harness.load_cell(name)
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (name, key)
    for c in BENCHMARK["configs"]:
        assert harness.load_config(c["name"])["source"] == c["source"]
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    readers = harness.metric_readers()
    assert {m["name"] for m in BENCHMARK["per_layer"]} <= set(readers)


def test_a_cell_in_another_directory_is_found_by_name(tmp_path):
    cell = harness.load_cell("cifar-wrn.oneshot")
    cell = dict(cell, name="synthetic.cell", params=dict(cell["params"],
                                                         seeds_per_call=3))
    (tmp_path / "synthetic.cell.json").write_text(json.dumps(cell))
    found = harness.load_cell("synthetic.cell", workloads_dir=str(tmp_path))
    assert found["params"]["seeds_per_call"] == 3
    assert harness.load_driver(found["driver"]).setup
    with pytest.raises(FileNotFoundError):
        harness.load_cell("no.such.cell", workloads_dir=str(tmp_path))


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", "cifar-wrn.oneshot", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_derived_seeds_are_stable_and_distinct():
    a = harness.derive_seed(2**31 + 12345, 0)
    assert a == harness.derive_seed(2**31 + 12345, 0)
    assert a != harness.derive_seed(2**31 + 12345, 1)
    assert 0 <= a < 2**31

"""BENCHMARK.json's cells, configurations and metrics resolve to files of
their own, found by name; a new cell is added by files alone."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _spec(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_spec_shape():
    spec = _spec()
    assert spec["command"] == ["python3", "-m", "bench.run"]
    assert 1 <= spec["run_seconds"] <= 51
    names = [c["name"] for c in spec["configs"]]
    cells = [w["name"] for w in spec["workloads"]]
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    for group in (names, cells, metrics):
        assert len(group) == len(set(group))
        assert all(NAME.match(n) for n in group)
    used = {w["config"] for w in spec["workloads"]}
    assert used == set(names)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", cells)
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(cells) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in _spec()["workloads"]])
def test_every_cell_resolves_its_files(cell):
    c = harness.load_cell(ROOT, cell)
    assert c.config["name"] in {x["name"] for x in _spec()["configs"]}
    assert c.traffic["kind"] in ("train", "serve")
    assert os.path.exists(os.path.join(ROOT, "bench", "kinds",
                                       f"{c.traffic['kind']}.py"))
    assert set(c.checks) and all("limit" in v for v in c.checks.values())
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(harness.metric_reader(ROOT, m["name"]))


def test_a_cell_is_added_by_files_alone(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    spec = _spec()
    base = spec["workloads"][0]
    spec["workloads"].append(dict(base, name="train.dummy",
                                  traffic="dummy.mix"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if base["name"] in m.get("workloads", []):
            m["workloads"].append("train.dummy")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    src = tmp_path / "bench" / "workloads"
    mix = json.loads((src / f"{base['traffic']}.json").read_text())
    (src / "dummy.mix.json").write_text(json.dumps(dict(mix, ring=2)))
    checks = tmp_path / "bench" / "checks"
    shutil.copy(checks / f"{base['name']}.json", checks / "train.dummy.json")
    cell = harness.load_cell(tmp_path, "train.dummy")
    assert cell.traffic["ring"] == 2
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in harness.load_cell(ROOT, base["name"]).per_layer]


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = _spec()["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "-m", "bench.run", "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout

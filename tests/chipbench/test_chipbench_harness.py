"""CPU rehearsal of the chip benchmark: its files agree with each other and
with BENCHMARK.json, every cell runs end to end at a small size and matches
its reference, and without a TPU the command prints no result."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import _small as small  # noqa: E402

ROOT, BENCH = small.ROOT, small.BENCH
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = sorted(p.name[:-len(".json")] for p in (BENCH / "workloads").glob(
    "*.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_has_the_contracts_keys():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert SPEC["command"] == ["python3", "chipbench/run.py"]
    assert all((ROOT / p).is_dir() for p in SPEC["paths"])
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert set(e2e) == {"iter_s", "iter_s.mesh", "setup_s"}
    for m in SPEC["per_layer"]:
        assert m["moves"] in ("iter_s", "iter_s.mesh")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    # Every cell reports setup_s and one time per iteration; a per-layer
    # metric is read only in cells that report the metric it moves.
    for cell in CELLS:
        reported, per_layer = small.bench.cell_metrics(cell)
        names = [m["name"] for m in reported]
        assert "setup_s" in names and len(names) == 2, (cell, names)
        assert per_layer
        assert all(m["moves"] in names for m in per_layer)


def test_every_name_and_unit_uses_allowed_characters():
    names = [c["name"] for c in SPEC["configs"]]
    names += [w[k] for w in SPEC["workloads"] for k in ("name", "config",
                                                         "traffic")]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [k for c in SPEC["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    units = [m["unit"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert all(UNIT.match(u) for u in units), units
    for text in ([w["why"] for w in SPEC["workloads"]]
                 + [c["why"] for c in SPEC["configs"]]
                 + [m["layer"] for m in SPEC["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_workload_names_a_configuration_and_metrics_that_exist():
    assert sorted(w["name"] for w in SPEC["workloads"]) == CELLS
    for w in SPEC["workloads"]:
        cell = small.bench.load_cell(w["name"])
        assert cell.workload["config"] == w["config"]
        assert cell.workload["chips"] == w["chips"]
        assert cell.workload["why"] == w["why"]
        for fn in ("Program", "readings", "control", "work"):
            assert callable(getattr(cell.config, fn))
    for c in SPEC["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for m in SPEC["per_layer"]:
        assert callable(small.bench.reader(m["name"]))


@pytest.mark.parametrize("name", ["kmeans.hbm", "hotspot.hbm"])
def test_cell_runs_and_matches_its_reference(name):
    res = small.run_on_cpu(small.small_cell(name), 2**31 + 7)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"iter_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(res["checks"]) & set(
        small.bench.load_cell(name).workload["limits"])


def test_traced_run_reports_per_layer_metrics_only():
    res = small.run_on_cpu(small.small_cell("hotspot.hbm"), 5, trace=True)
    assert res["correct"]
    # Off the chip there is no device trace: only the host-side readers
    # find something (the Context's spans and the window's length).
    assert set(res["metrics"]) == {"plan_ms", "dispatch_ms", "step_mfu"}
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_mesh_cell_runs_and_matches_its_reference():
    code = ("import json\n"
            "cell = small.small_cell('hotspot.mesh4')\n"
            "print(json.dumps(small.run_on_cpu(cell, 3)))\n"
            "print(json.dumps(small.run_on_cpu(cell, 4, trace=True)))\n")
    res, traced = small.run_with_four_devices(code)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"iter_s.mesh", "setup_s"}
    # The mesh cell's per-layer metrics are those that move iter_s.mesh.
    assert traced["correct"], traced["checks"]
    assert set(traced["metrics"]) == {"plan_ms.mesh", "dispatch_ms.mesh",
                                      "step_mfu.mesh"}


def test_without_a_tpu_the_command_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "kmeans.hbm",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr


def test_a_new_workload_file_is_found_without_editing_any_file(tmp_path):
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    spec = json.loads((BENCH / "workloads" / "kmeans.hbm.json").read_text())
    spec["why"] = "a smaller k-means cell added by a data file alone"
    spec["traffic"].update(points=1 << 13, block_rows=1 << 11)
    (tmp_path / "chipbench" / "workloads" / "kmeans.tiny.json").write_text(
        json.dumps(spec))
    copy = small.bench.load_module(tmp_path / "chipbench" / "run.py")
    cell = copy.load_cell("kmeans.tiny")
    assert cell.workload["traffic"]["points"] == 1 << 13
    res = small.run_on_cpu(cell, 11, module=copy)
    assert res["correct"], res["checks"]
    assert all(p.read_bytes() == b for p, b in before.items())

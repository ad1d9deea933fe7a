"""The benchmark's declaration and its files agree, and the harness
refuses to measure anywhere but on a TPU. Runs on the CPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import run as R  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _applies(metric, cell):
    return cell in metric.get("workloads", CELLS)


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = R.load_cell(cell, SPEC)
    assert c.module.is_file()
    assert c.traffic["loop"] in ("open", "closed")
    assert "engine" in c.workload
    mod = R.load_module(c.module, f"spec_{c.conf['name']}")
    assert hasattr(mod, "Model")
    for m in c.end_to_end + c.per_layer:
        assert hasattr(R.load_module(R.reader_path(m["name"]),
                                     f"spec_{m['name']}"), "read")


def test_names_and_units():
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in METRICS]
             + [w["config"] for w in SPEC["workloads"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for kind in ("configs", "workloads"):
        got = [x["name"] for x in SPEC[kind]]
        assert len(got) == len(set(got)), kind
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for text in ([w["why"] for w in SPEC["workloads"]]
                 + [c["why"] for c in SPEC["configs"]]
                 + [m["layer"] for m in SPEC["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in SPEC["end_to_end"])


def test_every_cell_reports_enough():
    for cell in CELLS:
        e2e = [m["name"] for m in SPEC["end_to_end"] if _applies(m, cell)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(_applies(m, cell) for m in SPEC["per_layer"]), cell


def test_per_layer_moves_a_metric_of_its_cells():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS, (m["name"], cell)
            assert _applies(moved, cell), (m["name"], cell)


def test_configs_are_files_under_paths():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        path = ROOT / c["file"]
        assert path.is_file() and c["file"].startswith("bench/")
        conf = json.loads(path.read_text())
        assert conf["name"] == c["name"]
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert set(conf["limits"])


@pytest.mark.parametrize("cell", CELLS)
def test_program_runs_the_stated_configuration(cell):
    c = R.load_cell(cell, SPEC)
    sys.path.insert(0, str(ROOT / "src"))
    mod = R.load_module(c.module, f"published_{c.conf['name']}")
    model = mod.Model.__new__(mod.Model)
    model.conf, model.name = c.conf, c.conf["name"]
    assert model.published()


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "4294967297", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_off_a_tpu_it_exits_nonzero_with_no_result():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 1 TPU" in out.stderr


def test_without_the_program_it_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""

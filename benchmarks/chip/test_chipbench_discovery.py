"""Cells, traffic mixes and metrics are found by name, so a later PR adds
files and entries and edits none; the harness refuses what it cannot
measure honestly."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import testlib_chipbench as lib
from chipbench import harness, registry

NEW_METRIC = '''"""Timesteps inside the traced window."""


def read(r):
    return float(r.steps)
'''


def test_dropped_in_config_traffic_and_metric_run(tmp_path):
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(lib.BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((lib.ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    cfg = json.loads((bench / "configs" / "jacobi3d-g1-hilbert-1024.json").read_text())
    cfg.update(name="jacobi3d-tiny", **lib.SMALL["resident"])
    (bench / "configs" / "jacobi3d-tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "short.json").write_text(json.dumps(
        {"steps_per_call": 8, "in_flight": 2, "warmup_calls": 1}))
    (bench / "metrics" / "steps_traced.py").write_text(NEW_METRIC)
    spec["configs"].append({"name": "jacobi3d-tiny", "source": "https://arxiv.org/abs/2307.07828",
                            "file": "benchmarks/chip/configs/jacobi3d-tiny.json",
                            "reduced": ["M"], "why": "test"})
    spec["workloads"].append({"name": "tiny.short", "config": "jacobi3d-tiny",
                              "traffic": "short", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "steps_traced", "unit": "steps", "better": "higher",
                              "source": "program_span", "layer": "pipeline",
                              "moves": "site_updates_per_s", "workloads": ["tiny.short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = registry.load_cell(tmp_path, "tiny.short", bench_dir=bench)
    assert cell.config["M"] == 16 and cell.traffic["steps_per_call"] == 8
    r = harness.run_cell(cell, 12, 0.2, trace=True)
    assert r["correct"]
    assert set(r["metrics"]) == {"steps_traced"}
    assert r["metrics"]["steps_traced"]["value"] == 8 * r["attempted"]
    assert {"busy_s", "window_s"} <= set(r["device"]) and "breakdown" in r
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        registry.peaks("TPU v99 imaginary")
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        registry.load_cell(lib.ROOT, "nope.steady")


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(lib.BENCH / "run.py"), "--workload",
                          "jacobi1024.steady", "--seed", "1", "--seconds", "1"],
                         env=env, cwd=lib.ROOT, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_every_metric_of_the_benchmark_has_a_reader():
    spec = json.loads((lib.ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(registry.load_module(lib.BENCH / "metrics" / f"{m['name']}.py").read)
    for w in spec["workloads"]:
        cell = registry.load_cell(lib.ROOT, w["name"])
        assert cell.ref().CHANNELS == cell.config["C"]
        assert hasattr(cell.driver(), "Driver")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_its_shape():
    spec = json.loads((lib.ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    cells = {w["name"]: w for w in spec["workloads"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and NAME.match(c["name"])
        assert (lib.ROOT / c["file"]).is_file()
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and NAME.match(w["name"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in names and "bound" not in m

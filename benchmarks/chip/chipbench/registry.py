"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

Everything that belongs to one configuration, one traffic mix, one rule
or one per-layer metric sits in a file of its own under the benchmark's
directory, so a later cell or metric is added by adding files:

    configs/<config>.json    sizes, rule, driver, source, reduced, assumed
    traffic/<mix>.json       the window's parameters
    drivers/<driver>.py      how the program is built, fed and read back
    refs/<rule>.py           the plain reference of one update rule
    metrics/<metric>.py      one reader per per-layer metric
    peaks.json               published peaks, keyed by ``device_kind``
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: Path = BENCH_DIR

    def driver(self) -> ModuleType:
        return load_module(self.bench_dir / "drivers" / f"{self.config['driver']}.py")

    def ref(self) -> ModuleType:
        return load_module(self.bench_dir / "refs" / f"{self.config['rule']}.py")

    def metric(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics" / f"{name}.py")


def load_module(path: Path) -> ModuleType:
    """Import a file of the layout by its path (once per process)."""
    path = Path(path).resolve()
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file not found: {path}")
    key = "chipbench_ext_" + re.sub(r"\W", "_", str(path.relative_to(path.parents[1])))
    mod = sys.modules.get(key)
    if mod is not None and getattr(mod, "__file__", None) == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, its files read."""
    bench = json.loads((Path(root) / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((Path(root) / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = json.loads((bench_dir / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]

"""The comparison fails what it must: the bfloat16 control in the
program's place, and a run with the timed path broken underneath.

The harness runs on the CPU at a small size with its look for a chip
skipped (``run_cell``); each fault of ``faults.py`` is planted in the
driver it loads.
"""

import json

import pytest

import testlib_chipbench as lib
from chipbench import check, harness

import control
import faults

MESH = "jacobi-mesh2x2.steady"
# one call in the window, so few steps that the small box is still far
# from its mean, as the cells' boxes are after theirs; a dropped call
# then shows
WINDOW_S = 0.0
ONE_CHIP_FAULTS = ["unchanged", "one_call_dropped", "half_left_out", "answer_altered"]


@pytest.mark.parametrize("cell_name", ["jacobi1024.steady", MESH])
@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3, 2 ** 32 + 9])
def test_bfloat16_control_fails(cell_name, seed):
    cell = lib.small_cell(cell_name)
    values = control.control_values(cell, seed, 32, control.box_of(cell.config))
    assert not check.judge(values, cell.config["limits"]), values


@pytest.mark.parametrize("fault", ONE_CHIP_FAULTS)
def test_fault_is_not_correct(fault, monkeypatch):
    cell = lib.small_cell("jacobi1024.steady")
    for obj, attr, value in faults.FAULTS[fault](cell.driver()):
        monkeypatch.setattr(obj, attr, value)
    r = harness.run_cell(cell, 2 ** 31 + 21, WINDOW_S)
    assert r["correct"] is False, r["checks"]
    assert r["failed"] == r["attempted"]


MESH_FAULT = """
import json
import testlib_chipbench as lib
import faults
from chipbench import harness
cell = lib.small_cell({cell!r})
for obj, attr, value in faults.FAULTS[{fault!r}](cell.driver()):
    setattr(obj, attr, value)
print(json.dumps(harness.run_cell(cell, 2 ** 31 + 6, {seconds!r})))
"""


def _mesh_run_with(fault: str) -> dict:
    out = lib.run_four_devices(MESH_FAULT.format(cell=MESH, fault=fault, seconds=WINDOW_S))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_without_exchange_is_not_correct():
    r = _mesh_run_with("no_exchange")
    assert r["correct"] is False, r["checks"]


def test_mesh_with_a_call_dropped_is_not_correct():
    r = _mesh_run_with("one_call_dropped")
    assert r["correct"] is False, r["checks"]

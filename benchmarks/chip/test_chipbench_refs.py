"""The benchmark's plain references against the program, on the CPU.

Each rule's reference, run by the harness's chunked ``advance``, agrees
with ``ResidentPipeline`` at a small size, both on the jnp path and
with the fused kernel interpreted; the chunked sweep equals stepping
the whole box; and the mesh driver runs a sound cell on four virtual
CPU devices.
"""

import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import testlib_chipbench as lib
from chipbench import check, harness

import control

STEPS = 8


@pytest.mark.parametrize("use_kernel", [False, True])
def test_reference_agrees_with_program(use_kernel):
    from repro.stencil import ResidentPipeline

    cell = lib.small_cell("jacobi1024.steady")
    cfg, ref = cell.config, cell.ref()
    key = check.seed_key(3)
    box = control.box_of(cfg)
    init = check.initial_state(key, init_planes=ref.init_planes, box=box)
    pipe = ResidentPipeline(M=cfg["M"], T=cfg["T"], g=cfg["g"], kind=cfg["kind"],
                            S=cfg["S"], rule=cfg["rule"], bc=cfg["bc"],
                            use_kernel=use_kernel)
    got = pipe.run(init[0] if ref.CHANNELS == 1 else init, STEPS)
    got = got.reshape(init.shape)
    want = check.reference(ref, cfg, key, STEPS, box)
    values = check.readings(got, want)
    assert values["nonfinite"] == 0
    # rounding alone: a few float32 ulps of the largest magnitude
    assert values["max_rel_err"] < 1e-6, values


@pytest.mark.parametrize("g", [1, 2])
@pytest.mark.parametrize("chunk", [2, 4, 16])
def test_chunked_sweep_equals_whole_box(g, chunk):
    cell = lib.small_cell("jacobi1024.steady")
    cfg, ref = dict(cell.config, g=g), cell.ref()
    x0 = check.initial_state(check.seed_key(4), init_planes=ref.init_planes,
                             box=(16, 8, 8))
    params = check.rule_params(cfg)
    pd = dict(params)
    whole = jax.jit(lambda x: jax.lax.fori_loop(0, 5, lambda _, y: ref.step(y, pd), x))
    want = whole(x0)
    got = check.advance(jnp.array(x0), 5, step=ref.step, params=params, chunk=chunk)
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) <= 4 * float(
        jnp.finfo(jnp.float32).eps * jnp.max(jnp.abs(want)))


def test_seed_key_takes_both_halves():
    a, b = check.seed_key(7), check.seed_key(7 + 2 ** 32)
    assert not bool(jnp.all(jax.random.key_data(a) == jax.random.key_data(b)))


def test_small_cell_runs_correct():
    cell = lib.small_cell("jacobi1024.steady")
    r = harness.run_cell(cell, 2 ** 31 + 11, 0.2)
    assert r["correct"] and r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"site_updates_per_s", "setup_s"}
    assert r["metrics"]["site_updates_per_s"]["value"] > 0
    assert r["device"]["memory_peak_bytes"] >= 0


class _Device:
    def __init__(self, in_use):
        self.in_use = in_use

    def memory_stats(self):
        return {"bytes_in_use": self.in_use, "peak_bytes_in_use": 10 ** 12}


class _Compiled:
    def memory_analysis(self):
        return SimpleNamespace(temp_size_in_bytes=300, output_size_in_bytes=1000,
                               alias_size_in_bytes=900)


def test_program_bytes_is_what_the_timed_program_holds():
    """Per chip: the buffers in use once warmed up, plus the program's
    temporaries and its output beyond the donated input; never the
    runtime's peak, which set-up may have set."""
    devices = [_Device(5000), _Device(7000)]
    assert harness.program_bytes(devices, _Compiled()) == [5400, 7400]
    assert harness.program_bytes(devices, lambda s: s) == [5000, 7000]


def test_reference_chunk_divides_every_box():
    ref = lib.small_cell("jacobi1024.steady").ref()
    cfg, key = {"g": 1}, check.seed_key(5)
    for box in [(24, 8, 8), (16, 8, 8), (8, 8, 8)]:
        got = check.reference(ref, cfg, key, 3, box)
        x = check.initial_state(key, init_planes=ref.init_planes, box=box)
        want = check.advance(x, 3, step=ref.step, params=check.rule_params(cfg),
                             chunk=box[0])
        assert float(jnp.max(jnp.abs(got - want))) <= 4 * float(
            jnp.finfo(jnp.float32).eps * jnp.max(jnp.abs(want)))


MESH_SOUND = """
import json, sys
import testlib_chipbench as lib
from chipbench import harness
cell = lib.small_cell("jacobi-mesh2x2.steady")
print(json.dumps(harness.run_cell(cell, 2 ** 31 + 5, 0.2)))
"""


def test_mesh_driver_on_four_cpu_devices():
    out = lib.run_four_devices(MESH_SOUND)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"], r
    assert r["device"]["count"] == 4

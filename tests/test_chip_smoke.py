"""chip_smoke.py on the CPU: its phases at a tiny size with the kernels
interpreted, and its refusal to report a result without a TPU."""

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gol_and_roi_phases_match_oracles(smoke):
    # T=16 with S=4 puts two k-chunks in every block (kc=8); oracle slabs
    # of 8 planes are shallower than K=8 steps, the hardest slab case
    pipe, store, cube, _ = smoke.phase_gol(M=32, T=16, S=4, K=8,
                                           use_kernel=True)
    assert pipe.use_kernel is True
    assert smoke.phase_roi(pipe, store, cube)
    assert smoke.check_gol(cube, K=8, oracle_planes=8)


def test_wave_phase_matches_oracle(smoke):
    assert smoke.phase_wave(M=32, T=16, S=2, K=4, use_kernel=True)


def test_oracle_mismatches_counts_a_flipped_site(smoke):
    """Slab by slab (4 planes, 3 steps deep) equals the whole non-cubic
    box stepped by gol3d_step_ref."""
    import jax
    import numpy as np

    from repro.kernels import ref

    box = smoke.random_box(jax.random.key(0), (16, 16, 8))
    want = box
    for _ in range(3):
        want = ref.gol3d_step_ref(want, 1)
    want = np.array(want)
    assert smoke.oracle_mismatches(box, want, 3, 4) == 0
    want[5, 2, 7] = 1 - want[5, 2, 7]
    assert smoke.oracle_mismatches(box, want, 3, 4) == 1


def test_mesh_phase_on_four_cpu_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        chip_smoke._import_repro()
        assert chip_smoke.phase_mesh(L=16, T=8, S=2, K=4, use_kernel=True,
                                     oracle_planes=8)
        print("MESH_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "one_shard_per_device=True" in out.stdout
    assert "MESH_OK" in out.stdout


def test_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, cwd=ROOT, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout

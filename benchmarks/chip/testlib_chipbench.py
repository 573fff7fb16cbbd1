"""Helpers of the benchmark's CPU tests: its cells cut to a size that the
CPU runs in seconds, where the pipelines take the jnp path."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import registry  # noqa: E402

# per config: the sizes a CPU run can hold (T=8 keeps S*g=4 | T)
SMALL = {"resident": {"M": 16, "T": 8},
         "distributed": {"M": 16, "T": 8}}


def small_cell(name: str) -> registry.Cell:
    cell = registry.load_cell(ROOT, name)
    cell.config.update(SMALL[cell.config["driver"]])
    return cell


def run_four_devices(code: str, timeout: int = 600) -> subprocess.CompletedProcess:
    """Run ``code`` in a fresh interpreter that sees four CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(BENCH)]))
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)

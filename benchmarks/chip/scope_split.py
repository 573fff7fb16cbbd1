#!/usr/bin/env python3
"""Run one cell traced and split its device time by the program's named
scopes.

    python3 benchmarks/chip/scope_split.py --workload jacobi-mesh2x2.steady \\
        --seed 7 --seconds 20 --out DIR

Runs the cell as ``run.py --trace 1`` does (``harness.run_cell``), keeps
the profile under DIR with the compiled program's HLO text beside it
(``program.hlo.txt``), and prints the result line with ``scopes`` added:
per timestep, the device time of each named scope and of the unscoped
rest outside the kernel and collectives, their sum, and the MB the
collectives carry (``chipbench/scopes.py``). The benchmark's own runs
do not call it.
"""

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, registry, scopes  # noqa: E402

HLO_FILE = "program.hlo.txt"
# {scope: the name of its time per timestep}
NAMES = {"sfc.pack": "face_pack_ms_per_step",
         "sfc.unpack": "face_unpack_ms_per_step",
         "sfc.shell": "shell_scatter_ms_per_step",
         "": "unscoped_device_ms_per_step"}


def run(cell: registry.Cell, seed: int, seconds: float, out: str,
        t_start: float | None = None) -> dict:
    """One traced run of ``cell`` with its profile and program kept under
    ``out``; its result line with ``scopes`` added."""
    drv = cell.driver()
    compile_, texts = drv.Driver.compile, []

    def compile_and_keep(self, n_steps, state):
        run = compile_(self, n_steps, state)
        texts.append(run.as_text())
        return run

    drv.Driver.compile = compile_and_keep
    try:
        result = harness.run_cell(cell, seed, seconds, trace=True,
                                  t_start=t_start, keep_trace=out)
    finally:
        drv.Driver.compile = compile_
    Path(out, HLO_FILE).write_text(texts[-1])
    files = glob.glob(str(Path(out) / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {out}, found {files}")
    sp = scopes.split(scopes.load(files[0], texts[-1]), drv.KERNEL)
    ms = 1e3 / (cell.traffic["steps_per_call"] * result["attempted"])
    result["scopes"] = {NAMES.get(k, k): t * ms for k, t in sorted(sp.scope_s.items())}
    result["scopes"].update(nonkernel_device_ms_per_step=sp.trace.other_s * ms,
                            exchange_mb_per_step=sp.collective_bytes * ms * 1e-9)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True, help="directory for the profile")
    args = ap.parse_args(argv)
    cell = registry.load_cell(harness.ROOT, args.workload)
    import jax

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        harness.log(f"{cell.name} needs {cell.chips} TPU chips; no result")
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    harness.log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run(cell, args.seed, args.seconds, args.out, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

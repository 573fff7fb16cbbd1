#!/usr/bin/env python3
"""The control of a cell's comparison: the reference one precision down.

    python3 benchmarks/chip/control.py --workload jacobi1024.steady \\
        --seeds 11,12,13 --steps 128

The plain reference, computed in bfloat16 (the precision below the
float32 that every configuration states), stands in for the program:
it is run for ``--steps`` steps from each seed's initial state, at the
cell's own size, and compared with the float32 reference by the same
numbers the benchmark compares. A sound limit fails it. Prints one JSON
line per seed and exits non-zero without a TPU. The benchmark's own
runs never run this.
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import check, registry  # noqa: E402
from chipbench.harness import ROOT  # noqa: E402

CONTROL_DTYPE = "bfloat16"


def control_values(cell, seed: int, n_steps: int, box) -> dict:
    """The numbers compared, for the control in the program's place."""
    ref, cfg, key = cell.ref(), cell.config, check.seed_key(seed)
    want = check.reference(ref, cfg, key, n_steps, box)
    low = check.reference(ref, cfg, key, n_steps, box, dtype=CONTROL_DTYPE)
    return check.readings(low, want)


def box_of(config: dict):
    edge = config["M"]
    return tuple(edge * p for p in config.get("mesh", (1, 1, 1)))


def main(argv=None) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--steps", type=int, required=True)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("no TPU; no result", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = registry.load_cell(ROOT, args.workload)
    limits = cell.config["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        values = control_values(cell, seed, args.steps, box_of(cell.config))
        print(json.dumps({"workload": cell.name, "seed": seed, "steps": args.steps,
                          "control": CONTROL_DTYPE, "values": values,
                          "limits": limits, "fails": not check.judge(values, limits),
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Faults planted in the timed path, to show that ``correct`` catches them.

    python3 benchmarks/chip/faults.py --workload jacobi-mesh2x2.steady \\
        --fault no_exchange --seeds 11,12 --seconds 20

Each fault breaks the program underneath an otherwise whole run of the
harness (set-up, window, comparison with the plain reference), at the
cell's own size and step count. Prints one JSON line per seed with the
numbers compared beside their limits; a sound limit gives
``"correct": false`` on every one. Exits non-zero without enough TPU
chips. The CPU tests plant the same faults at a small size; the
benchmark's own runs never run this.

A fault is a function of the cell's driver module that returns the
``(object, attribute, replacement)`` patches that plant it.
"""

import itertools
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, registry  # noqa: E402


def unchanged(driver):
    """Every call returns its state unchanged."""
    def compile(self, n_steps, state):
        return lambda s: s
    return [(driver.Driver, "compile", compile)]


def one_call_dropped(driver):
    """The first call after the warm-up returns its state unchanged."""
    orig = driver.Driver.compile

    def compile(self, n_steps, state):
        run, calls = orig(self, n_steps, state), itertools.count()
        return lambda s: s if next(calls) == 1 else run(s)
    return [(driver.Driver, "compile", compile)]


def half_left_out(driver):
    """The second half of the blocks keeps its input in every call."""
    orig = driver.Driver.compile

    def compile(self, n_steps, state):
        run = orig(self, n_steps, state)

        def half(s):
            out, nb = run(s), s.shape[-4]
            return out.at[..., nb // 2:, :, :, :].set(s[..., nb // 2:, :, :, :])
        return half
    return [(driver.Driver, "compile", compile)]


def answer_altered(driver):
    """One value of the answer read back is off by 0.01."""
    orig = driver.Driver.readback

    def readback(self, state):
        out = orig(self, state)
        return out.at[(0,) * out.ndim].add(0.01)
    return [(driver.Driver, "readback", readback)]


def no_exchange(driver):
    """The exchange between chips left out: every shard keeps its own
    faces, so each wraps on itself. Planted before the program is traced."""
    import jax
    return [(jax.lax, "ppermute", lambda x, axis_name, perm: x)]


FAULTS = {f.__name__: f for f in
          (unchanged, one_call_dropped, half_left_out, answer_altered, no_exchange)}


def main(argv=None) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = registry.load_cell(harness.ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"needs {cell.chips} TPU chips, JAX finds {devices}; no result",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for obj, attr, value in FAULTS[args.fault](cell.driver()):
        setattr(obj, attr, value)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(cell, seed, args.seconds)
        print(json.dumps({"workload": cell.name, "fault": args.fault, "seed": seed,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell: set-up, the measured window, the comparison.

Set-up draws the initial state from the seed on the first chip, puts it
into the program's own form once (block store or shards), compiles the
cell's one K-step program (from the persistent cache after the first
run) and runs it once. The window then calls that program back to back,
at most ``in_flight`` calls queued, until the first call boundary after
``--seconds``; it is timed to that call's end, and the calls still
queued then finish outside it. The state stays in the
program's form throughout. Afterwards the state is read back into the
canonical layout and compared with the plain reference advanced by as
many steps (``check.py``).

``--trace 1`` runs the same window under the profiler and reports the
cell's per-layer metrics, each read by ``metrics/<name>.py`` from the
reduced trace (``tracered.py``); ``--trace 0`` reports the end-to-end
metrics, taken by the host clock with the profiler off.
"""

from __future__ import annotations

import argparse
import glob
import json
import shutil
import sys
import tempfile
import time
from collections import deque
from dataclasses import dataclass
from pathlib import Path

from . import check, registry, tracered

ROOT = registry.BENCH_DIR.parents[1]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Reading:
    """What a per-layer metric reader is given."""
    trace: tracered.Reduction
    steps: int              # timesteps inside the traced window
    work: dict              # the driver's work count per timestep
    device_kind: str

    def peaks(self) -> dict:
        return registry.peaks(self.device_kind)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window and report per-layer metrics")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="write the raw profile of a --trace 1 run under DIR")
    return ap.parse_args(argv)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    cell = registry.load_cell(ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devices[0].platform!r}; no result")
        return 2
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, JAX finds {len(devices)}; no result")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start, keep_trace=args.keep_trace)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


def _first_shard(state):
    return state.addressable_shards[0].data


def program_bytes(devices, run) -> list[int]:
    """What the timed program holds on each chip once warmed up: the
    buffers in use then (the state, the program's constants), plus what a
    call adds while it runs by XLA's analysis of the compiled program, per
    chip: its temporaries and any output not written over its donated
    input. A runner that is no compiled program adds nothing."""
    stats = run.memory_analysis() if hasattr(run, "memory_analysis") else None
    adds = 0 if stats is None else stats.temp_size_in_bytes + max(
        0, stats.output_size_in_bytes - stats.alias_size_in_bytes)
    return [(d.memory_stats() or {}).get("bytes_in_use", 0) + adds for d in devices]


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool = False,
             *, t_start: float | None = None, keep_trace: str | None = None) -> dict:
    """Run ``cell`` once and return its result line as a dict."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    t_start = time.perf_counter() if t_start is None else t_start
    cfg, traffic = cell.config, cell.traffic
    ref, drv_mod = cell.ref(), cell.driver()
    K, in_flight = traffic["steps_per_call"], traffic["in_flight"]
    devices = jax.devices()[:cell.chips]
    key = check.seed_key(seed)
    work = drv_mod.work(cfg)

    marks = [("backend", time.perf_counter())]
    with TraceAnnotation("setup"):
        drv = drv_mod.Driver(cfg, devices)
        with jax.default_device(devices[0]):
            init = check.initial_state(key, init_planes=ref.init_planes,
                                       box=tuple(drv.box), dtype=jnp.dtype(cfg["dtype"]))
        state = drv.load(init)
        del init
        jax.block_until_ready(state)
        marks.append(("init_and_load", time.perf_counter()))
        run = drv.compile(K, state)
        probe = jax.jit(lambda x: x.reshape(-1)[:1])
        marks.append(("compile", time.perf_counter()))
        for _ in range(traffic["warmup_calls"]):
            state = run(state)
            probe(_first_shard(state)).block_until_ready()
        marks.append(("warmup", time.perf_counter()))
        held = program_bytes(devices, run)
    log("setup: " + ", ".join(f"{name} {t - prev:.3f} s" for (name, t), prev in
                              zip(marks, [t_start] + [t for _, t in marks])))
    steps = K * traffic["warmup_calls"]

    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    calls, pending, ends = 0, deque(), []
    with TraceAnnotation("window"):
        while True:
            with TraceAnnotation("call"):
                state = run(state)
                pending.append(probe(_first_shard(state)))
            if len(pending) < in_flight:
                continue
            with TraceAnnotation("wait"):
                pending.popleft().block_until_ready()
            calls += 1
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= seconds:
                break
    t1 = time.perf_counter()
    # calls still queued finish outside the window; the check covers them
    drained = len(pending)
    while pending:
        pending.popleft().block_until_ready()
    if trace:
        jax.profiler.stop_trace()
    steps += K * (calls + drained)
    setup_s, window_s = t0 - t_start, t1 - t0

    runtime_peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    log(f"memory: the timed program holds {held} B per chip; the runtime's peaks, "
        f"set-up included, {runtime_peaks} B")
    peak = max(held)
    t2 = time.perf_counter()
    with TraceAnnotation("readback"):
        got = drv.readback(state)
        del state
        got.block_until_ready()     # the program's state is gone before the reference
    want = check.reference(ref, cfg, key, steps, drv.box, device=devices[0])
    values = check.readings(got, want)
    del got, want
    log(f"check: readback and reference of {steps} steps {time.perf_counter() - t2!r} s")
    limits = cfg["limits"]
    correct = check.judge(values, limits)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": calls,
              "failed": 0 if correct else calls}
    log(f"{cell.name}: seed {seed}, {calls} calls of {K} steps in {window_s!r} s, "
        f"{steps} steps in all, setup {setup_s!r} s, peak {peak} B")
    log("window: calls seen to end at " + " ".join(f"{t:.3f}" for t in ends) + " s")
    if trace:
        red = _reduce_trace(trace_dir, drv_mod.KERNEL)
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        reading = Reading(red, K * calls, work, dev.device_kind)
        metrics = {}
        for m in cell.per_layer:
            v = cell.metric(m["name"]).read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result.update(metrics=metrics, device=device,
                      breakdown={"device_ops": red.device_ops,
                                 "idle_gaps": red.idle_gaps})
    else:
        known = {"site_updates_per_s": work["sites"] * K * calls / window_s,
                 "setup_s": setup_s}
        metrics = {m["name"]: {"value": known[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        result.update(metrics=metrics, device=device)
    result["checks"] = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    return result


def _reduce_trace(trace_dir: str, kernel: str) -> tracered.Reduction:
    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {files}")
    return tracered.reduce_events(tracered.load_xplane(files[0]), kernel)

"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Modules:
  offset_hist     — Figs 5-7  (offset histograms)
  cache_misses    — Figs 16-20 (surface miss counts, model)
  stencil_update  — Figs 8-10/12-14 (update timings) + repack-vs-resident
  halo_pack       — Figs 11/15 (pack timings + DMA runs)
  kernel_bench    — Pallas schedules scored by the paper's LRU model
  roofline_table  — §Roofline rows from the dry-run artefacts
  roi             — ROI-query serving rows (range counts, bytes read)

Flags:
  --fast          smaller sizes (CI-friendly)
  --json PATH     additionally write {"git_rev": ..., "rows": [...]} where
                  rows is a list of {"name", "us_per_call", "fields",
                  "derived": {k: v}} objects — the machine-readable form
                  the perf trajectory tracking consumes (derived
                  "k=v;k=v" strings are split; numeric values are parsed;
                  git_rev stamps which revision produced the numbers;
                  "fields" is the row's channel count C, defaulting to 1
                  for rows that predate the multi-field store — the
                  schema dimension the modelled-bytes keys are pinned
                  under, DESIGN.md §9).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys


def _parse_derived(derived: str) -> dict:
    out: dict[str, object] = {}
    for part in derived.split(";"):
        if "=" not in part:
            if part:
                out[part] = True
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out


def git_rev() -> str:
    """Short rev of the benchmarked tree (``unknown`` outside a checkout)."""
    try:
        r = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        rev = r.stdout.strip()
        return rev if r.returncode == 0 and rev else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def collect(fast: bool = False) -> list[tuple[str, float, str]]:
    from . import (cache_misses, halo_pack, kernel_bench, offset_hist,
                   roi, roofline_table, stencil_update)

    sections = [
        offset_hist.rows(),
        cache_misses.rows(M=32 if fast else 64),
        stencil_update.rows(sizes=(32,) if fast else (32, 64),
                            stencils=(1,) if fast else (1, 2)),
        halo_pack.rows(sizes=(32,) if fast else (32, 64),
                       widths=(1,) if fast else (1, 2)),
        kernel_bench.rows(),
        roofline_table.rows(),
        roi.rows(sizes=(32,) if fast else (32, 64)),
    ]
    return [row for rows in sections for row in rows]


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    fast = "--fast" in sys.argv
    json_path = None
    if "--json" in sys.argv:
        i = sys.argv.index("--json")
        if i + 1 >= len(sys.argv):
            raise SystemExit("--json needs a path argument")
        json_path = sys.argv[i + 1]

    rows = collect(fast=fast)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    if json_path:
        def _row(name, us, derived):
            d = _parse_derived(derived)
            return {"name": name, "us_per_call": round(us, 1),
                    "fields": int(d.get("fields", 1)), "derived": d}

        payload = {
            "git_rev": git_rev(),
            "rows": [_row(name, us, derived) for name, us, derived in rows],
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(payload['rows'])} rows "
              f"(rev {payload['git_rev']}) to {json_path}", file=sys.stderr)


if __name__ == "__main__":
    main()

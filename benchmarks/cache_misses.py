"""Paper Figs 16–20: cache misses when buffering each surface.

The paper reads hardware counters (perf_event) on EPYC/Xeon; this host has
neither, so the numbers come from the paper's own cache model (Alg. 1,
§3.2 surface variant) — the model the paper uses to *explain* those
figures. Parameters model an L1-like cache: 64-item lines (b) × 512 lines
(c). The signature result must match Figs 11/16: row-major sr faces miss
orders of magnitude more; SFC faces are uniform.
"""

from __future__ import annotations

import time

from repro.core import HILBERT, MORTON, ROW_MAJOR, surface_cache_misses
from repro.core.surfaces import PAPER_SURFACE_NAMES


def rows(M: int = 64, g: int = 1, b: int = 64, c: int = 512):
    out = []
    for spec in (ROW_MAJOR, MORTON, HILBERT):
        for face in ("k0", "k1", "i0", "i1", "j0", "j1"):
            t0 = time.perf_counter()
            m = surface_cache_misses(spec, M, g, b, c, face)
            dt = (time.perf_counter() - t0) * 1e6
            out.append((
                f"fig16_19/misses_M{M}_{spec.name}_{PAPER_SURFACE_NAMES[face]}",
                dt, f"misses={m}"))
    return out


if __name__ == "__main__":
    for name, us, derived in rows():
        print(f"{name},{us:.1f},{derived}")

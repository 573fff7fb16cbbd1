"""ROI-query locality rows: the paper's claim restated as a serving win.

For each ordering × aligned-ROI pair over an M³/T=8 block store, time the
block-sparse extraction (serve/roi.extract_roi) and stamp the
deterministic model (serve/roi.roi_model, DESIGN.md §11): contiguous
curve-range count, blocks touched, bytes read, payload bytes, and
utilization. ``blocks``/``bytes_read``/``utilization`` are
curve-independent (the block box is geometry); ``ranges`` is the
locality signal — the number of separate contiguous reads a storage
tier must issue. The ROI suite is aligned power-of-two boxes, where
hilbert/morton collapse whole octree subtrees into single ranges:
hilbert is strictly below row-major on every row (asserted in
tests/test_serve_roi.py).
"""

from __future__ import annotations

import time

import numpy as np

from repro.serve import ROI, StoreLayout, extract_roi, roi_model

N_ITERS = 20
ORDERINGS = ("row_major", "column_major", "morton", "hilbert")


def roi_suite(M: int) -> list[tuple[str, ROI]]:
    """The benchmarked ROI suite: aligned power-of-two boxes (the regime
    where curve choice moves the range count — an aligned 2^a block cube
    is one octree subtree = one range on any bit-hierarchical curve)
    plus one unaligned ``viewport`` (the exemplar repo's map-client
    case, where utilization drops below 1 because edge blocks carry
    waste). Every entry has range-count(hilbert) strictly below
    range-count(row_major) at T=8 for M ∈ {32, 64} — the acceptance
    contract tests/test_serve_roi.py asserts row by row."""
    h = M // 2
    return [
        ("octant", ROI((0, 0, 0), (h, h, h))),
        ("octant_hi", ROI((h, h, h), (M, M, M))),
        ("slab", ROI((0, 0, 0), (M, h, h))),
        ("tile", ROI((0, h, 0), (h, M, h))),
        ("viewport", ROI((3, 5, 2), (h + 3, h + 5, h + 2))),
    ]


def rows(sizes=(32, 64), T: int = 8):
    out = []
    rng = np.random.default_rng(0)
    for M in sizes:
        nb = (M // T) ** 3
        store_flat = rng.standard_normal((nb, T, T, T)).astype(np.float32)
        for kind in ORDERINGS:
            layout = StoreLayout(M=M, T=T, kind=kind)
            for roi_name, roi in roi_suite(M):
                m = roi_model(layout, roi)
                # warm then time the block-sparse decode
                extract_roi(store_flat, layout, roi)
                t0 = time.perf_counter()
                for _ in range(N_ITERS):
                    extract_roi(store_flat, layout, roi)
                dt = time.perf_counter() - t0
                derived = (f"roi_ranges={m['ranges']};"
                           f"roi_blocks={m['blocks_touched']};"
                           f"roi_bytes_read={m['bytes_read']};"
                           f"roi_payload_bytes={m['payload_bytes']};"
                           f"utilization={m['utilization']:.4f};"
                           f"fields=1")
                out.append((f"roi/extract_M{M}_T{T}_{kind}_{roi_name}",
                            dt * 1e6 / N_ITERS, derived))
    return out


if __name__ == "__main__":
    for name, us, derived in rows():
        print(f"{name},{us:.1f},{derived}")

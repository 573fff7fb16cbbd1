"""Paper Figs 5–7: accumulated memory-offset histograms h_O(x).

Fig 5/6: g=1 and g=3 at M=32 for row-major/Morton/Hilbert.
Fig 7: Morton block-size sweep (levels ⇒ block sizes 1, 4, 16).
Reports summary statistics of each histogram (full histograms go to CSV
if --csv is passed); the paper's qualitative claims are asserted by
tests/test_cache_model.py.
"""

from __future__ import annotations

import time

from repro.core import HILBERT, MORTON, ROW_MAJOR, OrderingSpec, offset_summary


def rows():
    out = []
    M = 32
    for g in (1, 3):  # Fig 5 and Fig 6
        for spec in (ROW_MAJOR, MORTON, HILBERT):
            t0 = time.perf_counter()
            s = offset_summary(spec, M, g)
            dt = (time.perf_counter() - t0) * 1e6
            out.append((f"fig5_6/offsets_g{g}_{spec.name}", dt,
                        f"n_distinct={s.n_distinct};mean_abs={s.mean_abs:.1f};"
                        f"p99_abs={s.p99_abs:.0f};"
                        f"frac_line64={s.frac_within_line:.3f}"))
    # Fig 7: Morton block sizes 1, 4, 16 <=> levels m, m-2, m-4 (M=32, m=5)
    for block, r in ((1, 5), (4, 3), (16, 1)):
        spec = OrderingSpec("morton", level=r)
        t0 = time.perf_counter()
        s = offset_summary(spec, M, 1)
        dt = (time.perf_counter() - t0) * 1e6
        out.append((f"fig7/offsets_morton_block{block}", dt,
                    f"n_distinct={s.n_distinct};mean_abs={s.mean_abs:.1f};"
                    f"frac_line64={s.frac_within_line:.3f}"))
    return out


if __name__ == "__main__":
    for name, us, derived in rows():
        print(f"{name},{us:.1f},{derived}")

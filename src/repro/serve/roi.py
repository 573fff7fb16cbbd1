"""ROI → contiguous curve-range decomposition over the block store.

The paper's locality claim becomes a *serving-path* win here (DESIGN.md
§11): an axis-aligned region of interest (ROI) over the curve-ordered
``(C, nb, T³)`` block store decomposes into a handful of **contiguous**
curve-index ranges, so a bounding-box query is a few sequential reads
instead of nb scattered ones. Curves that preserve 3-D locality need
fewer ranges — an aligned power-of-two block cube is exactly *one*
hilbert/morton range (a complete octree subtree is a contiguous index
interval for any bit-hierarchical curve) where row-major needs one range
per (bk, bi) line. benchmarks/roi.py records the counts; the exemplar
repo this mirrors measured ~85% chunk utilisation under Hilbert vs ~40%
row-major for exactly this access pattern.

Pieces:

- :class:`ROI` — a half-open axis-aligned element box ``[lo, hi)``.
- :class:`StoreLayout` — the (M, T, kind, C) identity of a block store
  (``StoreLayout.from_pipeline`` lifts it off a ResidentPipeline).
- :func:`roi_to_ranges` — minimal sorted disjoint ``(start, stop)``
  curve-index ranges covering every block the ROI intersects.
- :func:`extract_roi` — decode *only* those blocks into a dense
  ``(C,) + roi.shape`` array, bit-identical to slicing the unblockized
  cube (asserted across orderings × boundaries × C in tests).
- :func:`roi_model` — blocks-touched / bytes-read / range-count
  accounting, the single source of truth behind the ``roi/`` benchmark
  rows (pinned exactly in CI).

Everything here is host-side numpy: the serving path reads a snapshot
of the store, it never traces.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core.layout import block_order
from repro.core.orderings import block_index_3d

__all__ = ["ROI", "StoreLayout", "roi_to_ranges", "ranges_to_blocks",
           "merge_blocks_to_ranges", "extract_roi", "roi_model"]


@dataclass(frozen=True)
class ROI:
    """Half-open axis-aligned element box ``[lo, hi)`` in cube coords."""
    lo: tuple[int, int, int]
    hi: tuple[int, int, int]

    def __post_init__(self):
        lo = tuple(int(v) for v in self.lo)
        hi = tuple(int(v) for v in self.hi)
        object.__setattr__(self, "lo", lo)
        object.__setattr__(self, "hi", hi)
        if len(lo) != 3 or len(hi) != 3:
            raise ValueError(f"ROI needs 3-D lo/hi, got {lo}, {hi}")
        if any(l < 0 or l >= h for l, h in zip(lo, hi)):
            raise ValueError(f"empty or negative ROI [{lo}, {hi})")

    @property
    def shape(self) -> tuple[int, int, int]:
        return tuple(h - l for l, h in zip(self.lo, self.hi))

    def items(self) -> int:
        s = self.shape
        return s[0] * s[1] * s[2]

    def clipped(self, M: int) -> "ROI":
        if any(h > M for h in self.hi):
            raise ValueError(f"ROI {self.lo}..{self.hi} exceeds cube edge {M}")
        return self


@dataclass(frozen=True)
class StoreLayout:
    """Identity of a curve-ordered block store: cube edge M, block edge
    T (T | M), block-grid curve ``kind``, channel count C (DESIGN.md §9).
    """
    M: int
    T: int
    kind: str = "morton"
    channels: int = 1

    def __post_init__(self):
        if self.M % self.T or self.M < self.T:
            raise ValueError(f"block edge T={self.T} does not tile "
                             f"cube edge M={self.M}")
        if self.channels < 1:
            raise ValueError(f"channels must be >= 1, got {self.channels}")

    @classmethod
    def from_pipeline(cls, pipeline) -> "StoreLayout":
        """Lift the layout off a ResidentPipeline (or anything with
        M/T/kind/channels)."""
        return cls(M=pipeline.M, T=pipeline.T, kind=pipeline.kind,
                   channels=pipeline.channels)

    @property
    def nt(self) -> int:
        return self.M // self.T

    @property
    def nb(self) -> int:
        return self.nt ** 3

    def block_bytes(self, itemsize: int = 4) -> int:
        """Payload bytes of one block across all channels — the unit of
        both the cache and the bytes-read model."""
        return self.channels * self.T ** 3 * itemsize

    def block_box(self, roi: ROI) -> tuple[tuple, tuple]:
        """Half-open block-coordinate box the ROI intersects."""
        roi.clipped(self.M)
        lo = tuple(l // self.T for l in roi.lo)
        hi = tuple((h + self.T - 1) // self.T for h in roi.hi)
        return lo, hi


def merge_blocks_to_ranges(indices: np.ndarray) -> list[tuple[int, int]]:
    """Sorted unique curve indices → minimal disjoint ``(start, stop)``
    half-open ranges (consecutive indices merge)."""
    idx = np.unique(np.asarray(indices, dtype=np.int64))
    if idx.size == 0:
        return []
    breaks = np.nonzero(np.diff(idx) != 1)[0]
    starts = np.concatenate(([0], breaks + 1))
    stops = np.concatenate((breaks, [idx.size - 1]))
    return [(int(idx[a]), int(idx[b]) + 1) for a, b in zip(starts, stops)]


def roi_to_ranges(layout: StoreLayout, roi: ROI) -> list[tuple[int, int]]:
    """Minimal sorted disjoint contiguous curve-index ranges covering
    every block the ROI intersects.

    Exactness contract (property-tested): the union of the returned
    ranges equals the set of curve indices of blocks whose T³ extent
    intersects ``roi`` — nothing missing, nothing extra — and no two
    returned ranges are adjacent (the decomposition is minimal).
    """
    (bk0, bi0, bj0), (bk1, bi1, bj1) = layout.block_box(roi)
    kk, ii, jj = np.meshgrid(np.arange(bk0, bk1), np.arange(bi0, bi1),
                             np.arange(bj0, bj1), indexing="ij")
    idx = block_index_3d(layout.kind, kk.ravel(), ii.ravel(), jj.ravel(),
                         layout.nt)
    return merge_blocks_to_ranges(idx)


def ranges_to_blocks(ranges) -> np.ndarray:
    """Flatten ``(start, stop)`` ranges back to sorted curve indices."""
    if not ranges:
        return np.empty(0, dtype=np.int64)
    return np.concatenate([np.arange(a, b, dtype=np.int64)
                           for a, b in ranges])


def _as_store5(store: np.ndarray, layout: StoreLayout) -> np.ndarray:
    """View any store as ``(C, nb, T, T, T)`` (C=1 stores are 4-D)."""
    store = np.asarray(store)
    if store.ndim == 4:
        store = store[None]
    C, nb, T = store.shape[0], store.shape[1], store.shape[2]
    if (C, nb, T) != (layout.channels, layout.nb, layout.T) or \
            store.shape[2:] != (T, T, T):
        raise ValueError(f"store shape {store.shape} does not match "
                         f"layout {layout}")
    return store


def extract_roi(store: np.ndarray, layout: StoreLayout, roi: ROI,
                ranges=None, *, fill_value: float = np.nan,
                skip_blocks=()) -> np.ndarray:
    """Decode only the ROI's blocks into a dense ``(C,) + roi.shape``
    array (C=1 inputs return the plain 3-D box).

    ``ranges`` (default: :func:`roi_to_ranges`) restricts which curve
    ranges are materialised; blocks listed in ``skip_blocks`` (or blocks
    absent from ``ranges``) leave their footprint at ``fill_value`` —
    this is the degraded-response path of serve/service.py, where the
    ``missing_ranges`` manifest names exactly the unfilled blocks.
    """
    squeeze = np.asarray(store).ndim == 4
    store = _as_store5(store, layout)
    if ranges is None:
        ranges = roi_to_ranges(layout, roi)
    skip = set(int(b) for b in skip_blocks)
    T = layout.T
    bo = block_order(layout.kind, layout.nt)
    out = np.full((layout.channels,) + roi.shape, fill_value,
                  dtype=store.dtype)
    for b in ranges_to_blocks(ranges):
        if int(b) in skip:
            continue
        ok, oi, oj = (int(c) * T for c in bo[b])  # block origin, elements
        sl_out, sl_blk = [], []
        for ax, o in enumerate((ok, oi, oj)):
            lo = max(roi.lo[ax], o)
            hi = min(roi.hi[ax], o + T)
            if lo >= hi:
                sl_out = None
                break
            sl_out.append(slice(lo - roi.lo[ax], hi - roi.lo[ax]))
            sl_blk.append(slice(lo - o, hi - o))
        if sl_out is None:  # range includes blocks outside the ROI box
            continue
        out[(slice(None), *sl_out)] = store[(slice(None), int(b), *sl_blk)]
    return out[0] if squeeze else out


def roi_model(layout: StoreLayout, roi: ROI, itemsize: int = 4) -> dict:
    """Deterministic accounting of one ROI query — the model the
    ``roi/`` rows of benchmarks/roi.py print.

    blocks_touched: blocks whose extent intersects the ROI (= the block
                    box volume — curve-independent)
    ranges:         contiguous curve ranges (curve-DEpendent: the
                    locality signal; hilbert needs strictly fewer than
                    row-major on aligned power-of-two ROIs)
    bytes_read:     blocks_touched · C · T³ · itemsize — a range read
                    always moves whole blocks
    payload_bytes:  C · |roi| · itemsize — the useful bytes
    utilization:    payload / read (the exemplar repo's ~85% vs ~40%)
    """
    (bk0, bi0, bj0), (bk1, bi1, bj1) = layout.block_box(roi)
    blocks = (bk1 - bk0) * (bi1 - bi0) * (bj1 - bj0)
    ranges = roi_to_ranges(layout, roi)
    bytes_read = blocks * layout.block_bytes(itemsize)
    payload = layout.channels * roi.items() * itemsize
    return {
        "blocks_touched": blocks,
        "ranges": len(ranges),
        "bytes_read": bytes_read,
        "payload_bytes": payload,
        "utilization": payload / bytes_read,
    }

"""Surface (face) index lists for halo pack/unpack (paper §3.2, §4).

The paper packs each of the six width-``g`` faces of the cube into a
contiguous buffer using *precomputed lists of path indices* (one initial
traversal, memory cost 6gM² integers). This module builds those lists for
any ordering, plus run-length statistics that quantify how contiguous the
pack reads are — the structural quantity behind Figs 11/15: row-major
layouts read the sr faces at stride M² (runs of length 1) while SFC
layouts read every face in runs of whole curve blocks.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from .orderings import OrderingSpec, path_positions

__all__ = ["FACES", "PAPER_SURFACE_NAMES", "face_coords", "surface_path_indices",
           "run_lengths", "RunStats", "run_stats", "surface_runs",
           "shell_slab_shapes"]

FACES = ("k0", "k1", "i0", "i1", "j0", "j1")

# paper's surface naming (Figs 11/15): rc = row-column, cs = column-slab,
# sr = slab-row; F/B = front/back.
PAPER_SURFACE_NAMES = {
    "k0": "rcF", "k1": "rcB",
    "i0": "csF", "i1": "csB",
    "j0": "srF", "j1": "srB",
}


def face_coords(face: str, M: int, g: int):
    """(k, i, j) int64 coordinates of one width-g face, each of the
    face's slab shape ((g,M,M), (M,g,M) or (M,M,g)), row-major over it —
    the same sites as ``cache_model.face_mask`` without an M³ pass."""
    if face not in FACES:
        raise ValueError(f"face must be one of {FACES}")
    ax = "kij".index(face[0])
    span = [np.arange(M, dtype=np.int64)] * 3
    span[ax] = np.arange(g, dtype=np.int64) + (0 if face[1] == "0" else M - g)
    return np.meshgrid(*span, indexing="ij")


@functools.lru_cache(maxsize=256)
def surface_path_indices(spec: OrderingSpec, M: int, g: int, face: str) -> np.ndarray:
    """Path indices (positions in the ordering) of one face, ascending.

    Ascending path order == the order in which the curve visits the face,
    which is the pack order used by the paper (p_t in §3.2). Length gM².
    """
    p = path_positions(spec, *face_coords(face, M, g), M).ravel()
    idx = np.sort(p).astype(np.int32)
    idx.setflags(write=False)
    return idx


def run_lengths(sorted_idx: np.ndarray) -> np.ndarray:
    """Lengths of maximal runs of consecutive integers in a sorted array."""
    if sorted_idx.size == 0:
        return np.zeros(0, dtype=np.int64)
    breaks = np.flatnonzero(np.diff(sorted_idx) != 1)
    starts = np.concatenate([[0], breaks + 1])
    ends = np.concatenate([breaks + 1, [sorted_idx.size]])
    return (ends - starts).astype(np.int64)


@dataclass(frozen=True)
class RunStats:
    face: str
    paper_name: str
    n_elems: int
    n_runs: int
    mean_run: float
    min_run: int
    max_run: int


def run_stats(spec: OrderingSpec, M: int, g: int, face: str) -> RunStats:
    idx = surface_path_indices(spec, M, g, face)
    rl = run_lengths(idx)
    return RunStats(
        face=face, paper_name=PAPER_SURFACE_NAMES[face],
        n_elems=int(idx.size), n_runs=int(rl.size),
        mean_run=float(rl.mean()) if rl.size else 0.0,
        min_run=int(rl.min()) if rl.size else 0,
        max_run=int(rl.max()) if rl.size else 0,
    )


def shell_slab_shapes(M: int, h: int) -> tuple[tuple[int, int, int], ...]:
    """Canonical shapes of the six exchanged shell slabs, width ``h``.

    Order is (k-lo, k-hi, i-lo, i-hi, j-lo, j-hi) — the axis-sequential
    corner-correct exchange: the k slabs span the bare M² face, the i
    slabs the k-extended face, the j slabs the fully extended face. Their
    union is exactly the shell of the (M+2h)³ extended cube.
    """
    e = M + 2 * h
    return ((h, M, M), (h, M, M), (e, h, M), (e, h, M), (e, e, h), (e, e, h))


def surface_runs(spec: OrderingSpec, M: int, g: int, face: str):
    """(starts, lengths) of contiguous path-index runs for one face.

    This is the compressed form of the paper's precomputed index lists:
    a pack is then ``concatenate(data[start:start+len] for runs)`` — each
    run is one contiguous DMA on TPU (kernels/sfc_gather.py).
    """
    idx = surface_path_indices(spec, M, g, face)
    rl = run_lengths(idx)
    ends = np.cumsum(rl)
    starts_in_list = ends - rl
    starts = idx[starts_in_list]
    return starts.astype(np.int64), rl

"""Precomputed SFC block-neighbour tables (DESIGN.md §3).

The resident-block pipeline (stencil/pipeline.py, kernels/stencil3d.py)
keeps the cube as an ``(nb, T, T, T)`` curve-ordered block store for the
whole multi-step loop — the paper's "reorder once, iterate many times"
discipline.  Halo assembly then needs, for the block at *path position*
``t``, the path positions of its 26 grid neighbours.  This module builds
those tables once per ``(ordering, nt)`` pair, as int32 (they ride the
TPU scalar-prefetch channel), with periodic and clamped variants.

Offsets are enumerated in row-major order of ``(dk+1, di+1, dj+1)`` so
that column ``(a·9 + b·3 + c)`` of a full table is the neighbour at
offset ``(a-1, b-1, c-1)`` — the same order the kernel assembles its
``(T+2g)³`` VMEM window in, and column :data:`SELF_COL` (= 13) is the
block itself.
"""

from __future__ import annotations

import functools

import numpy as np

from .layout import block_order
from .orderings import OrderingSpec

__all__ = [
    "OFFSETS_FULL", "OFFSETS_FACE", "FACE_COLS", "SELF_COL",
    "block_kind_of", "neighbor_table", "ring_perms", "boundary_face_table",
    "shell_block_count", "shell_block_index", "extended_neighbor_table",
]

OFFSETS_FULL = tuple((a - 1, b - 1, c - 1)
                     for a in range(3) for b in range(3) for c in range(3))
SELF_COL = OFFSETS_FULL.index((0, 0, 0))  # 13

# face (von-Neumann) neighbours in [k-, k+, i-, i+, j-, j+] order
OFFSETS_FACE = ((-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0),
                (0, 0, -1), (0, 0, 1))
FACE_COLS = tuple(OFFSETS_FULL.index(o) for o in OFFSETS_FACE)


def block_kind_of(spec: OrderingSpec | str) -> str:
    """Block-granularity curve induced by an ordering.

    Morton and Hilbert are hierarchical: the order in which the
    element-level curve visits T³ tiles *is* the same curve on the
    nt³ block grid (the top 3·log2(nt) bits of the index), so the
    element ordering's kind carries over directly. A hybrid ordering's
    block order is its ``outer`` curve; row/column-major likewise
    induce themselves.
    """
    if isinstance(spec, str):
        return spec
    if spec.kind == "hybrid":
        return spec.outer
    return spec.kind


def _periodic_axes(periodic) -> tuple[bool, bool, bool]:
    """Normalise the ``periodic`` knob: a bool applies to all three axes,
    a 3-sequence gives the per-axis wrap flags (mixed boundary contracts,
    core.boundary.axes_periodic — DESIGN.md §8)."""
    if isinstance(periodic, bool):
        return (periodic,) * 3
    per = tuple(bool(p) for p in periodic)
    if len(per) != 3:
        raise ValueError(f"periodic must be a bool or 3 flags, got {periodic!r}")
    return per


def neighbor_table(spec: OrderingSpec | str, nt: int, *,
                   connectivity: str = "full",
                   periodic=True) -> np.ndarray:
    """Path-position → neighbour path-positions, int32, read-only.

    spec:         OrderingSpec or block-kind string (see block_kind_of)
    nt:           blocks per cube edge (power of 2)
    connectivity: "full" → (nt³, 27) table over OFFSETS_FULL;
                  "face" → (nt³, 6) table over OFFSETS_FACE
    periodic:     wrap at the grid boundary; otherwise clamp to the edge
                  block (note: block-level clamping replicates *blocks*,
                  not elements — it matches jnp.pad(mode="edge") only for
                  the face-adjacent halo layer, which is what the
                  distributed exchange consumes). A per-axis 3-tuple of
                  flags realises mixed contracts (clamped k, periodic
                  i/j — core.boundary.MixedBoundary): each axis wraps or
                  clamps independently.

    ``table[t, o]`` is the path position of the block at offset
    ``OFFSETS[o]`` from the block the curve visits at position ``t``.
    """
    # normalise before the cache: lists/tuples of flags both hit one key
    # (and bad inputs raise the friendly ValueError, not lru_cache's)
    return _neighbor_table_cached(spec, nt, connectivity,
                                  _periodic_axes(periodic))


@functools.lru_cache(maxsize=128)
def _neighbor_table_cached(spec: OrderingSpec | str, nt: int,
                           connectivity: str,
                           periodic: tuple[bool, bool, bool]) -> np.ndarray:
    if connectivity not in ("full", "face"):
        raise ValueError(f"unknown connectivity {connectivity!r}")
    kind = block_kind_of(spec)
    full = _full_table(kind, nt, periodic)
    if connectivity == "face":
        face = full[:, FACE_COLS]
        face.setflags(write=False)
        return face
    return full


@functools.lru_cache(maxsize=128)
def _full_table(kind: str, nt: int,
                periodic: tuple[bool, bool, bool]) -> np.ndarray:
    bo = block_order(kind, nt)  # (nb, 3): path pos -> block coords
    nb = nt ** 3
    lin = bo[:, 0] * nt * nt + bo[:, 1] * nt + bo[:, 2]
    lin_to_path = np.empty(nb, dtype=np.int64)
    lin_to_path[lin] = np.arange(nb)
    offs = np.asarray(OFFSETS_FULL, dtype=np.int64)  # (27, 3)
    co = bo[:, None, :] + offs[None, :, :]           # (nb, 27, 3)
    for ax in range(3):
        if periodic[ax]:
            co[..., ax] %= nt
        else:
            np.clip(co[..., ax], 0, nt - 1, out=co[..., ax])
    tab = lin_to_path[(co[..., 0] * nt + co[..., 1]) * nt + co[..., 2]]
    tab = tab.astype(np.int32)
    tab.setflags(write=False)
    return tab


def shell_block_count(nt: int) -> int:
    """Blocks in the one-block-thick shell around an nt³ core grid."""
    return (nt + 2) ** 3 - nt ** 3


@functools.lru_cache(maxsize=128)
def shell_block_index(nt: int) -> np.ndarray:
    """Extended-grid block coords -> shell enumeration id (core = -1).

    The distributed pipeline (stencil/halo.py) appends the exchanged halo
    as *shell blocks* after the nt³ core store: a block at extended
    coords ``(bk, bi, bj) ∈ [-1, nt]³`` outside the core gets id
    ``shell_block_index(nt)[bk+1, bi+1, bj+1]`` and lives at store row
    ``nt³ + id``. Core coords map to -1 — core rows are addressed by the
    block curve's own path positions.

    The ids run slab by slab, in core/surfaces.shell_slab_shapes' order
    (k-lo, k-hi, i-lo, i-hi, j-lo, j-hi), and row-major over each slab's
    own block grid: ``(bi, bj) ∈ [0, nt)²`` for k, ``(bk, bj) ∈ [-1, nt] ×
    [0, nt)`` for i, ``(bk, bi) ∈ [-1, nt]²`` for j. So each received slab
    becomes a contiguous run of shell blocks by a pad, a reshape and a
    transpose (stencil/halo._shell_blocks).
    """
    e = nt + 2
    core, ext = slice(1, nt + 1), slice(None)
    idx = np.full((e, e, e), -1, dtype=np.int32)
    n = 0
    for region in ((0, core, core), (e - 1, core, core),
                   (ext, 0, core), (ext, e - 1, core),
                   (ext, ext, 0), (ext, ext, e - 1)):
        grid = idx[region]  # a view: the slab's 2-D block grid
        grid[...] = np.arange(n, n + grid.size).reshape(grid.shape)
        n += grid.size
    assert n == shell_block_count(nt), (n, nt)
    idx.setflags(write=False)
    return idx


@functools.lru_cache(maxsize=128)
def extended_neighbor_table(spec: OrderingSpec | str, nt: int) -> np.ndarray:
    """(nt³, 27) int32 neighbour table over the core+shell extended store.

    Row ``t`` (the core block the curve visits at path position ``t``)
    holds, per OFFSETS_FULL column, either the path position of a core
    neighbour or ``nt³ + shell_id`` of the shell block that carries the
    exchanged halo in that direction — the scalar-prefetch operand of the
    distributed fused step (stencil/halo.shard_substeps). Column
    :data:`SELF_COL` is ``t`` itself, as in :func:`neighbor_table`.
    """
    kind = block_kind_of(spec)
    bo = block_order(kind, nt)  # (nb, 3): path pos -> block coords
    nb = nt ** 3
    lin = bo[:, 0] * nt * nt + bo[:, 1] * nt + bo[:, 2]
    lin_to_path = np.empty(nb, dtype=np.int64)
    lin_to_path[lin] = np.arange(nb)
    offs = np.asarray(OFFSETS_FULL, dtype=np.int64)  # (27, 3)
    co = bo[:, None, :] + offs[None, :, :]           # (nb, 27, 3)
    inside = ((co >= 0) & (co < nt)).all(axis=-1)
    coc = np.clip(co, 0, nt - 1)
    core_ids = lin_to_path[(coc[..., 0] * nt + coc[..., 1]) * nt + coc[..., 2]]
    shell_ids = shell_block_index(nt)[co[..., 0] + 1, co[..., 1] + 1,
                                      co[..., 2] + 1]
    tab = np.where(inside, core_ids, nb + shell_ids).astype(np.int32)
    tab.setflags(write=False)
    return tab


def ring_perms(n: int, periodic: bool = True
               ) -> tuple[list[tuple[int, int]], list[tuple[int, int]]]:
    """(forward, backward) ppermute partner lists for a ring of n devices.

    The 1D special case of the face tables — device ``i``'s +axis
    neighbour is ``i+1 mod n`` — kept here so stencil/halo.py's exchange
    and the block tables share one source of neighbour conventions.
    (Direct formula: device meshes need not be powers of 2.)

    ``periodic=False`` is the clamped-boundary ring: the wrapping pairs
    ``(n-1, 0)`` / ``(0, n-1)`` are simply absent, so *no bytes move on
    the wrap link* — devices with no source receive zeros (``ppermute``
    semantics) and stencil/halo.exchange_shell substitutes boundary
    values there instead.
    """
    if not periodic:
        return ([(i, i + 1) for i in range(n - 1)],
                [(i, i - 1) for i in range(1, n)])
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    return fwd, bwd


@functools.lru_cache(maxsize=128)
def boundary_face_table(spec: OrderingSpec | str, nt: int) -> np.ndarray:
    """(nb, 6) int32 flags: which faces of each block lie on the domain edge.

    Columns follow :data:`OFFSETS_FACE` order — ``[k-, k+, i-, i+, j-, j+]``
    — so column ``2·axis + side`` matches the face the fused kernel's
    ghost refresh (kernels/rules.apply_window_bc) masks. Row ``t`` is the
    block the curve visits at path position ``t``, same indexing as
    :func:`neighbor_table`. On a clamped run the resident pipeline feeds
    this table straight to the kernel; the distributed pipeline first
    AND-masks it with the shard's mesh position (only mesh-edge shards
    own global domain faces — stencil/halo.shard_substeps).
    """
    kind = block_kind_of(spec)
    bo = block_order(kind, nt)  # (nb, 3): path pos -> block coords
    cols = []
    for ax in range(3):
        cols += [bo[:, ax] == 0, bo[:, ax] == nt - 1]
    tab = np.stack(cols, axis=1).astype(np.int32)
    tab.setflags(write=False)
    return tab

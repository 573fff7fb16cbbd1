"""Distributed halo exchange from the SFC block store (paper §3.2/§4, on mesh).

The paper's halo pattern — pack faces into contiguous buffers, exchange
with neighbours, unpack — mapped to JAX: ``shard_map`` over a 3D device
mesh, ``jax.lax.ppermute`` ring shifts per axis (axis-sequential,
corner-correct).

Communication-avoiding form (DESIGN.md §7): each shard keeps its state
as the resident curve-ordered ``(nb, T, T, T)`` block store for the
whole K-step loop. The curve orders whole blocks, so every face of the
shard is the outer h layers of the nt² blocks on that side: each face
packs by static slices of those blocks, found through a block table
read off ``layout.block_order`` (nt² entries, not a list of sites), and
one transpose into its canonical slab. One exchange moves *deep* faces
of width ``h = S·g`` and funds S fused substeps (same window-shrink math
as ``stencil_step_fused``): the received shell scatters into shell
blocks appended after the core store (core/neighbors.extended_neighbor_table
addresses them), and the fused kernel — or its jnp oracle — advances S
whole timesteps per HBM round-trip with no per-step
``undo_ordering``/``apply_ordering`` and no canonical-cube
materialisation, ever.

Multi-field stores (DESIGN.md §9): a C-channel workload keeps its state
as the stacked ``(C, nb, T, T, T)`` store. All C channels share one
block permutation and one set of face block tables, so a deep exchange
packs **every channel** into the same six messages — per-axis ICI
extents simply gain the ×C factor — and the shell scatter/extended
store carry the stacked axis through to the fused kernel unchanged.

On a TPU torus with Hilbert device ordering (launch/mesh.py) the six
ppermutes are single-hop ICI transfers.

Physical (clamped) boundaries — DESIGN.md §8: under a clamped
``core.boundary`` contract the rings are open (no wrap pairs, so no
ICI traffic across domain faces), mesh-edge shards fill their unserved
shell slabs with boundary values, and the fused substeps refresh ghost
layers per substep from the shard's mesh-masked block flags. A per-axis
``MixedBoundary`` opens only its clamped axes: periodic axes keep their
full rings, and the jaxpr carries ppermute pairs for those axes alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import OrderingSpec, path_to_rmo, rmo_to_path
from repro.core.boundary import (PERIODIC, BoundarySpec, MixedBoundary,
                                 as_boundary, axes_periodic)
from repro.core.layout import (apply_ordering, block_order,
                               needs_element_perm, store_spec, undo_ordering)
from repro.core.neighbors import (block_kind_of, boundary_face_table,
                                  extended_neighbor_table, ring_perms,
                                  shell_block_count)
from repro.core.surfaces import shell_slab_positions, shell_slab_shapes
from repro.kernels import ref as kref
from repro.kernels.ops import uniform_weights
from repro.kernels.rules import get_rule
from repro.kernels.stencil3d import SUBLANES, stencil_step_fused

from .domain import STENCIL_AXES

__all__ = ["exchange_shell", "shard_substeps",
           "shard_boundary_flags", "make_distributed_step",
           "stencil_block_kind", "shard_state", "unshard_state",
           "to_store", "from_store"]


def stencil_block_kind(spec: OrderingSpec) -> str:
    """Block-grid curve the stencil pipelines use for an element ordering:
    the ordering's own curve when it has one, else Morton (the pipelines
    are SFC-blocked even when the logical state ordering is row-major)."""
    kind = block_kind_of(spec)
    return kind if kind in ("morton", "hilbert") else "morton"


@functools.lru_cache(maxsize=256)
def _face_blocks(kind: str, nt: int, face: str) -> np.ndarray:
    """The face's block table: ``(nt, nt)`` store positions of the blocks
    on that side of the shard, indexed by their block coordinates along
    the face's two free axes in (k, i, j) order."""
    ax = "kij".index(face[0])
    bo = block_order(kind, nt)
    on = np.flatnonzero(bo[:, ax] == (0 if face[1] == "0" else nt - 1))
    free = [d for d in range(3) if d != ax]
    pos = np.empty((nt, nt), np.int64)
    pos[bo[on, free[0]], bo[on, free[1]]] = on
    pos.setflags(write=False)
    return pos


def _face_slab(store: jnp.ndarray, kind: str, T: int, h: int,
               face: str) -> jnp.ndarray:
    """One deep face of the ``(C, nb, T, T, T)`` block store, as its
    canonical slab: ``(C, h, M, M)``, ``(C, M, h, M)`` or ``(C, M, M, h)``.

    The face is the outer h planes, rows or lanes (h ≤ T) of the nt²
    whole blocks on that side; the face's block table says where the
    ``kind`` curve put each, a static slice cuts its layers, and one
    concatenate plus a transpose lays them out row-major over the face.
    Each cut is ``SUBLANES`` layers deep at least (then trimmed to h):
    a cut thinner than an (8, 128) tile makes XLA relayout the whole
    store on the TPU to serve it.
    """
    C, nb = store.shape[:2]
    nt = round(nb ** (1 / 3))
    assert nt ** 3 == nb and store.shape[2:] == (T,) * 3 and h <= T, \
        (store.shape, T, h)
    ax = "kij".index(face[0])
    depth = min(T, -(-h // SUBLANES) * SUBLANES)
    lo = 0 if face[1] == "0" else T - depth
    start, limit = [0] * 5, [C, 0, T, T, T]
    start[2 + ax], limit[2 + ax] = lo, lo + depth
    cuts = []
    for t in _face_blocks(kind, nt, face).ravel():
        start[1], limit[1] = int(t), int(t) + 1
        cuts.append(jax.lax.slice(store, start, limit))
    width = [T, T, T]
    width[ax] = depth
    x = jnp.concatenate(cuts, axis=1).reshape([C, nt, nt] + width)
    free = [d for d in range(3) if d != ax]
    perm = [0]
    for d in range(3):
        perm += [3 + d] if d == ax else [1 + free.index(d), 3 + d]
    slab = x.transpose(perm).reshape(
        [C] + [depth if d == ax else nt * T for d in range(3)])
    off = 0 if face[1] == "0" else depth - h
    return jax.lax.slice_in_dim(slab, off, off + h, axis=1 + ax)


def _bc_face_fill(face: jnp.ndarray, axis: int, side: str,
                  bc: BoundarySpec) -> jnp.ndarray:
    """Boundary values for one shell slab of a clamped domain face.

    ``face`` is the slab the shard *would* send outward on that side
    (own deep face, already carrying any previously-filled edge data,
    with the leading channel axis); ``axis`` indexes the *spatial* axis
    (0..2) and ``bc`` is that axis's own contract (mixed runs pass each
    axis's spec). The returned array is what a mesh-edge shard holds in
    the ghost slab instead of exchanged data: the dirichlet constant, or
    — neumann0 — the outermost in-domain plane of ``face`` replicated
    across the slab's ``h`` width (clamp-copy), per channel.
    """
    if bc.kind == "dirichlet":
        return jnp.full(face.shape, bc.value, face.dtype)
    ax = axis - 3  # spatial axes are the last three (leading C rides along)
    edge = 0 if side == "lo" else face.shape[ax] - 1
    plane = jax.lax.slice_in_dim(face, edge, edge + 1, axis=ax)
    return jnp.broadcast_to(plane, face.shape)


def exchange_shell(store_flat: jnp.ndarray, kind: str, M: int, T: int,
                   h: int, axis_names=STENCIL_AXES, bc=PERIODIC):
    """Deep (width-h) corner-correct shell exchange from the block store.

    ``store_flat`` is the shard's ``(nb·T³,)`` ravelled curve-ordered
    block store, viewed as its ``(nb, T, T, T)`` blocks: *all six* own
    faces pack from it by static slices of the blocks on each side
    (:func:`_face_slab`), none from a materialised cube and none through
    per-site index lists. A multi-field shard passes the stacked
    ``(C, nb·T³)`` store: every channel packs through the same block
    tables into the same six messages, so the per-axis ICI volume simply
    gains the ×C factor (DESIGN.md §9) and the returned slabs carry the
    leading channel axis. Axis-sequential scheme: the k faces are the
    bare M² surfaces; the i faces carry the k-received edges; the j faces
    carry both — after three ppermute rounds the six returned slabs tile
    the shell of the (M+2h)³ extended domain exactly (shapes:
    core/surfaces.shell_slab_shapes). Every message is a canonical slab,
    so a receiver uses it as it arrives.

    Per-axis ICI volume is C·2h·M², C·2h·(M+2h)·M, C·2h·(M+2h)² items —
    the ``exchange_items_per_exchange`` model in stencil/pipeline.py.

    Clamped boundaries (core.boundary, DESIGN.md §8): each clamped axis
    ring is *open* — ``ring_perms(n, periodic=False)`` omits the
    wrapping pairs, so no bytes ever cross a clamped domain face — and
    mesh-edge shards substitute boundary values into the unserved slabs
    (dirichlet constant or neumann0 clamp-copy of their own outermost
    plane) before the next axis forwards them, which keeps corner
    regions composed exactly like the padded-cube oracle. Interior
    shards are untouched. A per-axis ``MixedBoundary`` opens only its
    clamped axes: the periodic axes keep full rings and wrap as on the
    torus, so the jaxpr carries ppermute pairs for those axes alone.
    """
    bc = as_boundary(bc)
    periodic = axes_periodic(bc)
    ax_bcs = bc.axes
    squeeze = store_flat.ndim == 1
    if squeeze:
        store_flat = store_flat[None]
    store = store_flat.reshape(store_flat.shape[0], -1, T, T, T)
    assert store.shape[1] == (M // T) ** 3, (store.shape, M)
    _, _, shp_i, _, shp_j, _ = shell_slab_shapes(M, h)

    @jax.named_scope("sfc.pack")
    def _fill_edges(slab_lo, slab_hi, face_lo, face_hi, axis, ax_name):
        """On mesh-edge shards, replace received-zero slabs with BC data."""
        n = jax.lax.psum(1, ax_name)
        pos = jax.lax.axis_index(ax_name)
        slab_lo = jnp.where(pos == 0,
                            _bc_face_fill(face_lo, axis, "lo", ax_bcs[axis]),
                            slab_lo)
        slab_hi = jnp.where(pos == n - 1,
                            _bc_face_fill(face_hi, axis, "hi", ax_bcs[axis]),
                            slab_hi)
        return slab_lo, slab_hi

    @jax.named_scope("sfc.unpack")
    def _received(sl, *slabs):
        """The strips of received slabs that the next axis forwards."""
        return [s[sl] for s in slabs]

    # --- k axis: the bare deep faces, ring-shifted as canonical slabs
    with jax.named_scope("sfc.pack"):
        face_k0 = _face_slab(store, kind, T, h, "k0")
        face_k1 = _face_slab(store, kind, T, h, "k1")
    fwd, bwd = ring_perms(jax.lax.psum(1, axis_names[0]), periodic=periodic[0])
    slab_k_lo = jax.lax.ppermute(face_k1, axis_names[0], fwd)  # prev's high face
    slab_k_hi = jax.lax.ppermute(face_k0, axis_names[0], bwd)  # next's low face
    if not periodic[0]:
        slab_k_lo, slab_k_hi = _fill_edges(slab_k_lo, slab_k_hi,
                                           face_k0, face_k1, 0, axis_names[0])

    # --- i axis: core faces + k-received edges (corner-correct)
    def _i_face(mine, sl):
        k_lo, k_hi = _received((..., sl, slice(None)), slab_k_lo, slab_k_hi)
        return jnp.concatenate([k_lo, mine, k_hi], axis=-3)

    with jax.named_scope("sfc.pack"):
        face_i0 = _i_face(_face_slab(store, kind, T, h, "i0"), slice(0, h))
        face_i1 = _i_face(_face_slab(store, kind, T, h, "i1"), slice(M - h, M))
    fwd, bwd = ring_perms(jax.lax.psum(1, axis_names[1]), periodic=periodic[1])
    slab_i_lo = jax.lax.ppermute(face_i1, axis_names[1], fwd)
    slab_i_hi = jax.lax.ppermute(face_i0, axis_names[1], bwd)
    if not periodic[1]:
        slab_i_lo, slab_i_hi = _fill_edges(slab_i_lo, slab_i_hi,
                                           face_i0, face_i1, 1, axis_names[1])
    assert slab_i_lo.shape[-3:] == shp_i, (slab_i_lo.shape, shp_i)

    # --- j axis: core faces + both received edge sets
    def _j_face(mine, sl):
        k_lo, k_hi, i_lo, i_hi = _received(
            (..., sl), slab_k_lo, slab_k_hi, slab_i_lo, slab_i_hi)
        mid = jnp.concatenate([k_lo, mine, k_hi], axis=-3)
        return jnp.concatenate([i_lo, mid, i_hi], axis=-2)

    with jax.named_scope("sfc.pack"):
        face_j0 = _j_face(_face_slab(store, kind, T, h, "j0"), slice(0, h))
        face_j1 = _j_face(_face_slab(store, kind, T, h, "j1"), slice(M - h, M))
    fwd, bwd = ring_perms(jax.lax.psum(1, axis_names[2]), periodic=periodic[2])
    slab_j_lo = jax.lax.ppermute(face_j1, axis_names[2], fwd)
    slab_j_hi = jax.lax.ppermute(face_j0, axis_names[2], bwd)
    if not periodic[2]:
        slab_j_lo, slab_j_hi = _fill_edges(slab_j_lo, slab_j_hi,
                                           face_j0, face_j1, 2, axis_names[2])
    assert slab_j_lo.shape[-3:] == shp_j, (slab_j_lo.shape, shp_j)

    slabs = (slab_k_lo, slab_k_hi, slab_i_lo, slab_i_hi, slab_j_lo, slab_j_hi)
    return tuple(s[0] for s in slabs) if squeeze else slabs


def shard_boundary_flags(kind: str, nt: int,
                         axis_names=STENCIL_AXES) -> jnp.ndarray:
    """(nb, 6) clamped-domain-face flags for this shard's blocks.

    The base table (core.neighbors.boundary_face_table) marks blocks on
    the *local* grid edge; a face is a physical domain face only when
    the shard also sits on the mesh edge of that axis, so each column is
    AND-masked with the shard's position read off the shard_map axes
    (axis_names order (dx, dy, dz) ↔ face columns (k∓, i∓, j∓)). On
    mixed contracts the refresh (rules.apply_window_bc) skips periodic
    axes by itself, so the table needs no further bc masking.
    """
    base = jnp.asarray(boundary_face_table(kind, nt))
    edge = []
    for ax in axis_names:
        n = jax.lax.psum(1, ax)
        pos = jax.lax.axis_index(ax)
        edge += [pos == 0, pos == n - 1]
    return base * jnp.stack(edge).astype(jnp.int32)[None, :]


def shard_substeps(store: jnp.ndarray, *, kind: str, M: int, g: int, S: int,
                   rule: str = "gol", bc: BoundarySpec | MixedBoundary | str = PERIODIC,
                   use_kernel: bool = False,
                   axis_names=STENCIL_AXES) -> jnp.ndarray:
    """One deep exchange + S fused substeps on the resident shard store.

    store: (nb, T, T, T) curve-ordered local block store (shard_map
    body), or the stacked multi-field ``(C, nb, T, T, T)`` store when
    the rule declares C > 1 (DESIGN.md §9). Exchanges width S·g once —
    all C channels in the same six messages — scatters the shell into
    shell blocks appended after the core, and runs S whole timesteps
    through ``stencil_step_fused`` (or its jnp oracle) with the extended
    neighbour table — the distributed counterpart of one
    ResidentPipeline launch. S sequential S=1 calls are bit-identical
    (f32) to one S-deep call, same argument as the fused kernel.

    On clamped runs (``bc``, core.boundary — uniform or per-axis mixed)
    the exchange fills mesh-edge shell blocks with boundary values
    instead of ppermuted ghost data, and the fused substeps refresh
    those ghost layers per substep via the shard's mesh-masked face
    flags (:func:`shard_boundary_flags`) — so the deep rounds stay
    bit-identical to S sequential clamped steps.
    """
    multi = store.ndim == 5
    nb, T = store.shape[-4], store.shape[-3]
    nt = M // T
    assert nb == nt ** 3, (store.shape, M)
    bc = as_boundary(bc)
    h = S * g
    flat = store.reshape(store.shape[0], -1) if multi else store.reshape(-1)
    slabs = exchange_shell(flat, kind, M, T, h, axis_names, bc=bc)
    pos = shell_slab_positions(nt, T, h)
    with jax.named_scope("sfc.shell"):
        if multi:
            C = store.shape[0]
            vals = jnp.concatenate([s.reshape(C, -1) for s in slabs], axis=1)
            shell = jnp.zeros((C, shell_block_count(nt) * T ** 3), store.dtype
                              ).at[:, pos].set(vals).reshape(C, -1, T, T, T)
            ext = jnp.concatenate([store, shell], axis=1)
        else:
            vals = jnp.concatenate([s.reshape(-1) for s in slabs])
            shell = jnp.zeros((shell_block_count(nt) * T ** 3,), store.dtype
                              ).at[pos].set(vals).reshape(-1, T, T, T)
            ext = jnp.concatenate([store, shell], axis=0)
    nbr = extended_neighbor_table(kind, nt)
    bnd = shard_boundary_flags(kind, nt, axis_names) if bc.clamped else None
    w = uniform_weights(g)
    if use_kernel:
        return stencil_step_fused(ext, w, nbr, bnd, g=g, S=S, rule=rule,
                                  bc=bc)
    return kref.stencil_fused_ref(ext, w, nbr, S=S, rule=rule, bc=bc, bnd=bnd)


def _store_perm(spec: OrderingSpec, kind: str, T: int, M: int,
                inverse: bool) -> np.ndarray:
    """M³ permutation between spec-path-ordered state and the block store.

    Forward: ``store_flat = state_path[perm]``; inverse:
    ``state_path = store_flat[perm_inv]``. Composition of the two
    orderings' permutations — applied once per K-step run (the layout
    boundary), never per step, and only for orderings that
    core.layout.needs_element_perm names.
    """
    hspec = store_spec(kind, T)
    if inverse:
        return rmo_to_path(hspec, M)[path_to_rmo(spec, M)]
    return rmo_to_path(spec, M)[path_to_rmo(hspec, M)]


def to_store(state_path: jnp.ndarray, spec: OrderingSpec, kind: str,
             T: int, M: int) -> jnp.ndarray:
    """A shard's (1,1,1,[C,]M³) path state -> its ``([C,] nb, T, T, T)``
    block store (shard_map body). The store's own ordering
    (``store_spec(kind, T)``) is already the store; row-/column-major
    states go through the canonical cube by reshapes and an nb-sized
    block gather; any other ordering gathers through one M³
    permutation."""
    lead = state_path.shape[3:-1]
    flat = state_path.reshape(lead + (-1,))
    hspec = store_spec(kind, T)
    if needs_element_perm(spec):
        flat = jnp.take(flat, _store_perm(spec, kind, T, M, False), axis=-1)
    elif spec != hspec:
        flat = apply_ordering(undo_ordering(flat, spec, M), hspec)
    return flat.reshape(lead + (-1, T, T, T))


def from_store(store: jnp.ndarray, spec: OrderingSpec, kind: str,
               T: int, M: int) -> jnp.ndarray:
    """Inverse of :func:`to_store`: block store -> (1,1,1,[C,]M³)."""
    lead = store.shape[:-4]
    flat = store.reshape(lead + (-1,))
    hspec = store_spec(kind, T)
    if needs_element_perm(spec):
        flat = jnp.take(flat, _store_perm(spec, kind, T, M, True), axis=-1)
    elif spec != hspec:
        flat = apply_ordering(undo_ordering(flat, hspec, M), spec)
    return flat.reshape((1, 1, 1) + lead + (-1,))


def _state_pspec(channels: int) -> P:
    """shard_map spec of the public sharded state: (px, py, pz, M³) for
    C=1, (px, py, pz, C, M³) for a multi-field workload — the channel
    axis is replicated across the mesh (it lives inside every shard)."""
    return P(*STENCIL_AXES) if channels == 1 else P(*STENCIL_AXES, None)


def make_distributed_step(mesh: jax.sharding.Mesh, spec: OrderingSpec,
                          local_M: int, g: int, *, T: int | None = None,
                          rule: str = "gol", bc: BoundarySpec | MixedBoundary | str = PERIODIC,
                          use_kernel: bool = False):
    """jit'd distributed stencil step on a sharded (P·M)³ global state.

    Global state layout: (px, py, pz, M³) — device (a,b,c) owns row
    [a,b,c] holding its local path-ordered state under ``spec``
    (see :func:`shard_state`). A multi-field rule (C > 1) uses
    (px, py, pz, C, M³): the C channels ride inside every shard, each
    path-ordered under the same ``spec``. ``bc`` selects the boundary
    contract (core.boundary: periodic | dirichlet | neumann0 | mixed).
    Returns step(global_state) -> global_state.

    The legacy per-step reference for DistributedPipeline (which runs the
    same :func:`shard_substeps` round at depth S): no per-step full-cube
    repack — the state converts to the block store and back (one
    permutation gather each way), all six faces pack from the store by
    block slices, and the compute is the fused S=1 path. Bit-identical
    to the pipeline at every S (f32), and to the pre-rebuild slice-loop
    reference for integer-valued rules (gol).
    """
    if T is None:
        T = min(8, local_M)
    C = get_rule(rule).channels
    pspec = _state_pspec(C)
    kind = stencil_block_kind(spec)

    def local_step(state_path):  # (1,1,1,[C,]M³) per device
        store = to_store(state_path, spec, kind, T, local_M)
        store = shard_substeps(store, kind=kind, M=local_M, g=g, S=1,
                               rule=rule, bc=bc, use_kernel=use_kernel)
        return from_store(store, spec, kind, T, local_M)

    # check_vma=False: pallas_call has no shard_map replication rule
    step = jax.shard_map(local_step, mesh=mesh, in_specs=pspec,
                         out_specs=pspec, check_vma=False)
    return jax.jit(step)


# ----------------------------------------------------------------------
# Global-state layout helpers (tests, demos, Gol3d.run_distributed)
# ----------------------------------------------------------------------

def shard_state(cube: jnp.ndarray, spec: OrderingSpec,
                procs: tuple[int, int, int]) -> jnp.ndarray:
    """(Gk,Gi,Gj) canonical state -> (px,py,pz,M³) per-shard path state.

    Stacked multi-field input (C,Gk,Gi,Gj) -> (px,py,pz,C,M³): every
    channel shards identically and is path-ordered under ``spec``.
    The global box may be non-cubic (a 4×2×1 mesh decomposes a
    (4M, 2M, M) domain, DESIGN.md §10) — only the *local* shard must be
    a cubic power-of-2 block, because that is what the SFC machinery
    orders.
    """
    squeeze = cube.ndim == 3
    if squeeze:
        cube = cube[None]
    C = cube.shape[0]
    gk, gi, gj = cube.shape[1:]
    px, py, pz = procs
    if gk % px or gi % py or gj % pz:
        raise ValueError(f"global shape {(gk, gi, gj)} does not divide "
                         f"over procs {procs}")
    lk, li, lj = gk // px, gi // py, gj // pz
    if not (lk == li == lj):
        raise ValueError(f"local block must be cubic, got {(lk, li, lj)} "
                         f"from global {(gk, gi, gj)} over procs {procs}")
    parts = cube.reshape(C, px, lk, py, li, pz, lj) \
        .transpose(1, 3, 5, 0, 2, 4, 6)  # (px,py,pz,C,lk,li,lj)
    out = apply_ordering(parts, spec)
    return out[:, :, :, 0] if squeeze else out


def unshard_state(state: jnp.ndarray, spec: OrderingSpec,
                  global_M=None) -> jnp.ndarray:
    """Inverse of :func:`shard_state` (C-stacked state comes back as
    (C, Gk, Gi, Gj)). ``global_M`` — a cube edge or (Gk,Gi,Gj) triple —
    is optional: the global box is derivable from the state shape and
    the argument is only checked against it when given."""
    squeeze = state.ndim == 4
    if squeeze:
        state = state[:, :, :, None]
    px, py, pz, C = state.shape[:4]
    lk = round(state.shape[4] ** (1 / 3))
    lk = next(m for m in (lk - 1, lk, lk + 1) if m ** 3 == state.shape[4])
    shape = (px * lk, py * lk, pz * lk)
    if global_M is not None:
        want = (global_M,) * 3 if isinstance(global_M, int) else tuple(global_M)
        if want != shape:
            raise ValueError(f"state {state.shape} implies global {shape}, "
                             f"caller said {want}")
    parts = undo_ordering(state, spec, lk)  # (px,py,pz,C,lk,lk,lk)
    out = parts.transpose(3, 0, 4, 1, 5, 2, 6).reshape(C, *shape)
    return out[0] if squeeze else out

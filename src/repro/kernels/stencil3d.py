"""Pallas TPU kernel: the SFC-blocked 3D stencil, fused (DESIGN.md §2–§4).

``stencil_step_fused`` advances the resident block store: the
un-haloed ``(nb, T, T, T)`` array whose block order in HBM follows a
space-filling curve and which persists across timesteps
(stencil/pipeline.ResidentPipeline). The halo is assembled **inside the
kernel**: a precomputed SFC neighbour table (core/neighbors.py) rides
the scalar-prefetch channel, so the index map of each grid step points
every one of the 27 window pieces (6 faces, 12 edges, 8 corners, 1
centre) at the right slice of the right neighbour block. No halo store
exists in HBM and no per-step repack runs; because blocks are
curve-ordered, consecutive grid steps ask for overlapping neighbour
sets.

Temporal blocking (DESIGN.md §4): the update rule (kernels/rules.py)
is the kernel's epilogue, and one launch runs ``S`` whole substeps per
HBM round-trip: assemble a ``(T+2·S·g)³`` window from neighbour slices
of extent ``S·g``, then alternate tap-sum + rule in VMEM with the
window shrinking by ``g`` per side each substep, and write the next
``T³`` state tile once. K timesteps cost ``ceil(K/S)`` launches; per
substep the HBM stream is ``((T+2·S·g)³ + T³)/S`` — the
locality-for-bandwidth trade of Reissmann & Jahre, paid for with
redundant boundary flops. ``rule="identity"`` with S=1 returns the raw
tap sum.

Boundary contract (DESIGN.md §8): the kernel takes a ``core.boundary``
contract (uniform or per-axis mixed) plus a second scalar-prefetched
``(nb, 6)`` table of per-block clamped-face flags; before every substep
the flagged ghost layers are substituted with boundary values
(rules.apply_window_bc), so physical domains temporally block exactly
as deep as periodic ones.

Multi-field stores (DESIGN.md §9): a rule that declares C > 1 channels
(``wave``) rides the stacked ``(C, nb, T³)`` store — the 27 piece specs
gain a whole-store channel dimension, one grid step assembles C windows,
tap-sums every channel, applies the rule to the stacked fields, and
writes C tiles. C=1 stores keep the 4-D kernel program.

TPU layout (DESIGN.md §4): Mosaic refuses block shapes that cut the two
minor dimensions off the (8, 128) tiling, so the kernel runs a
``(nb, T/kc)`` grid of k-chunks whose pieces are whole along j and
whole sublane tiles along i, and cuts the i/j halos in VMEM. A tap at
an (i, j) offset off that tiling costs a rotate and a select per vreg,
so each substep touches every window plane once per pass and keeps the
result aligned in a VMEM ring that holds the 2g+1 planes in flight.

Two tap-sum forms (DESIGN.md §4), chosen at trace time from the
weights' values (rules.box_centre), share assembly, refresh, rule and
write:

- box, for concrete weights that are the (2g+1)³ box of ones with a
  centre of 0 or 1 (ops.uniform_weights, which every pipeline passes):
  each window plane is summed into one ij-partial per channel, a j
  pass of lane-offset slices then an i pass of sublane-offset slices
  (``_box_partial``); an output plane is the k fold of 2g+1 partials,
  less the centre for a zero-centre box. No multiplies.
- weighted, for any other weights or a traced array: each window plane
  is shifted once into its (2g+1)² - 1 offset copies (``_fused_shift``)
  and every one of the 27 taps is an aligned load of a copy, weighted
  and summed in (dk, di, dj) order.

Every fold runs left to right in offset order, and ref.tap_sum takes the
same form in the same order, so kernel and oracle sum in one order;
one S-substep launch is bit-identical to S single-substep launches in
either form. The two forms sum in different orders: exactly equal for
integer sums (gol), within f32 reassociation otherwise. VMEM per grid
step is the ``(C, kc+2h, T+2h, T+2h)`` window, the ring — ``(2g+2)·C``
planes of ``(T+2h, T+2h-2g)`` in the box form, ``(2g+1)·((2g+1)² -
1)·C`` in the weighted form — and the double-buffered pieces and output
slab (``fused_kernel_vmem_bytes``): 10.4 MiB (box) and 13.1 MiB
(weighted) at T=128, S=4, g=1; only T=128 makes the store lane-dense.
Correctness is checked against ref.stencil_fused_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.boundary import (PERIODIC, BoundarySpec, MixedBoundary,
                                 as_boundary)

from .backend import resolve_interpret
from .rules import apply_window_bc, box_centre, get_rule

__all__ = ["stencil_step_fused", "fused_geometry", "fused_kernel_vmem_bytes"]

# The fused kernel's two scalar-prefetch tables live in SMEM (1 MiB on a
# v5e core); leave room for Mosaic's own scalars.
SMEM_TABLE_BYTES = 3 * 2 ** 18
# Scoped VMEM the fused kernel may use (a v5e core has 128 MiB; the
# default scope is 16 MiB, which one T=128 window plus its double-
# buffered pieces outgrows).
VMEM_LIMIT_BYTES = 100 * 2 ** 20


SUBLANES = 8   # f32 rows in one (8, 128) vreg tile
LANES = 128    # f32 lanes in one (8, 128) vreg tile: the lane-dense T


def fused_geometry(T: int, h: int) -> tuple[int, int]:
    """(kc, hb) of ``stencil_step_fused`` for block edge T, halo depth h | T.

    kc: output planes per grid step — the smallest multiple of h that
    spans a sublane tile and divides T (else T). hb: rows fetched per
    i-halo piece — h rounded up to whole sublane tiles when that divides
    T (else the whole block edge). Both keep every block shape on the
    TPU's (8, 128) tiling (DESIGN.md §4).
    """
    kc = -(-SUBLANES // h) * h
    if kc > T or T % kc:
        kc = T
    hb = -(-h // SUBLANES) * SUBLANES
    if hb >= T or T % hb:
        hb = T
    return kc, hb


def _tile_bytes(rows: int, lanes: int) -> int:
    """VMEM of one f32 (rows, lanes) plane, padded to whole (8, 128) tiles."""
    return 4 * (-(-rows // SUBLANES) * SUBLANES) * (-(-lanes // LANES) * LANES)


def fused_kernel_vmem_bytes(T: int, h: int, fields: int = 1,
                            itemsize: int = 4, *, g: int = 1,
                            form: str = "weighted") -> int:
    """VMEM one grid step of ``stencil_step_fused`` allocates.

    Two f32 scratches, each plane padded to whole (8, 128) tiles: the
    ``(C, kc+2h, T+2h, T+2h)`` window and the ring of planes of
    ``(T+2h, T+2h-2g)`` — ``(2g+1)·((2g+1)² - 1)·C`` tap copies in the
    weighted form, ``(2g+2)·C`` in the box form (rules.tap_form). Then
    the 27 pieces (three j-columns of ``(kc+2h) x (T+2·hb) x T`` each)
    and the ``C·kc·T²`` output slab, both double-buffered.
    """
    kc, hb = fused_geometry(T, h)
    W = T + 2 * h
    s = 2 * g + 1
    window = fields * (kc + 2 * h) * _tile_bytes(W, W)
    planes = s + 1 if form == "box" else s * (s * s - 1)
    ring = fields * planes * _tile_bytes(W, W - 2 * g)
    pieces = 3 * (kc + 2 * h) * (T + 2 * hb) * T
    return window + ring + 2 * itemsize * fields * (pieces + kc * T * T)


def _fused_piece_index(i, z, nbr_ref, _bnd_ref, *, a: int, col: int,
                       irow: int, nz: int, kc_h: int, T_h: int,
                       channels: bool):
    # Piece (a, b, c) of grid step (i, z): b/c pick the i/j neighbour
    # column; along k the low (a=0) and high (a=2) halo slabs come from
    # the k-neighbour block only on the block's first/last chunk, and
    # from the block's own column otherwise. k is addressed in units of
    # the piece's k extent (h for halos, kc for the centre).
    mid = col - (a - 1) * 9  # same (b, c) column, k offset 0
    if a == 1:
        blk, kidx = nbr_ref[i, col], z
    elif nz == 1:
        blk, kidx = nbr_ref[i, col], (T_h - 1 if a == 0 else 0)
    elif a == 0:
        first = z == 0
        blk = jnp.where(first, nbr_ref[i, col], nbr_ref[i, mid])
        kidx = jnp.where(first, T_h - 1, z * kc_h - 1)
    else:
        last = z == nz - 1
        blk = jnp.where(last, nbr_ref[i, col], nbr_ref[i, mid])
        kidx = jnp.where(last, 0, (z + 1) * kc_h)
    idx = (blk, kidx, irow, 0)
    return (0,) + idx if channels else idx


def _fused_piece_specs(T: int, h: int, kc: int, hb: int,
                       channels: int | None) -> list:
    """The 27 BlockSpecs of one (kc+2h, T+2h, T+2h) window.

    Piece (a, b, c) spans (h | kc | h) planes along k, (hb | T | hb)
    rows along i, and the whole block edge T along j: no block shape
    cuts the lane axis, and the i cut is whole sublane tiles. The
    window's i/j halos are cut from these pieces in VMEM.
    """
    nz = T // kc
    ext_k, ext_i = (h, kc, h), (hb, T, hb)
    specs = []
    for a in range(3):
        for b in range(3):
            for c in range(3):
                shape = (1, ext_k[a], ext_i[b], T)
                if channels is not None:
                    shape = (channels,) + shape
                specs.append(pl.BlockSpec(shape, functools.partial(
                    _fused_piece_index, a=a, col=a * 9 + b * 3 + c,
                    irow=T // hb - 1 if b == 0 else 0, nz=nz, kc_h=kc // h,
                    T_h=T // h, channels=channels is not None)))
    return specs


def _fused_assemble(pieces, win, T: int, h: int, kc: int, hb: int):
    """Write the (C, kc+2h, T+2h, T+2h) window into the ``win`` scratch.

    Plane by plane: each window plane joins the matching planes of the 9
    pieces of its k-slab, with the i/j halos cut out of the fetched rows
    and the whole-edge lane span (the only unaligned work is in VMEM).
    """
    cut_i = (slice(hb - h, hb), slice(None), slice(0, h))
    cut_j = (slice(T - h, T), slice(None), slice(0, h))
    for a, (k0, ext) in enumerate(((0, h), (h, kc), (h + kc, h))):
        def put(p, carry, a=a, k0=k0):
            rows = []
            for b in range(3):
                row = []
                for c in range(3):
                    r = pieces[a * 9 + b * 3 + c]
                    v = r[0, p][None] if len(r.shape) == 4 else r[:, 0, p]
                    row.append(v.astype(jnp.float32)[:, cut_i[b], cut_j[c]])
                rows.append(jnp.concatenate(row, axis=-1))
            win[:, k0 + p] = jnp.concatenate(rows, axis=-2)
            return carry
        jax.lax.fori_loop(0, ext, put, 0)


def _fused_refresh(win, flags, d: int, Ek: int, Ei: int, bc):
    """Clamped ghost refresh of the current (Ek, Ei, Ei) window, in place.

    The same substitution as rules.apply_window_bc, axis by axis: whole k
    planes first (a k face is flagged only on the block's first or last
    chunk), then the i/j ghosts of every plane, which depend only on
    that plane.
    """
    kbc = bc.axes[0]
    if kbc.clamped:
        for t in range(d):
            for flag, dst, src in ((flags[0], t, d),
                                   (flags[1], Ek - 1 - t, Ek - 1 - d)):
                @pl.when(flag != 0)
                def _(dst=dst, src=src):
                    if kbc.kind == "dirichlet":
                        win[:, dst, :Ei, :Ei] = jnp.full(
                            (win.shape[0], Ei, Ei), kbc.value, jnp.float32)
                    else:
                        win[:, dst, :Ei, :Ei] = win[:, src, :Ei, :Ei]
    if bc.axes[1].clamped or bc.axes[2].clamped:
        def plane(p, carry):
            x = win[:, pl.ds(p, 1), :Ei, :Ei]
            win[:, pl.ds(p, 1), :Ei, :Ei] = apply_window_bc(
                x, flags, d, bc, axes=(1, 2))
            return carry
        jax.lax.fori_loop(0, Ek, plane, 0)


def _fused_shift(win, ring, q, slot, Ei: int, Oi: int, s: int):
    """Write the (2g+1)² - 1 shifted tap copies of window plane q into
    ring ``slot`` (q mod (2g+1)), every one at offset (0, 0) of its tile.

    Copy (di, dj) is ``win[c, q, di:di+Oi, dj:dj+Oi]``; (0, 0) is the
    window plane itself and is not copied. Axis by axis: the 2g lane
    shifts (0, dj) keep all Ei rows, so each (di, dj) is then a sublane
    shift of (0, dj) — or of the plane for dj = 0.
    """
    for c in range(win.shape[0]):
        for dj in range(1, s):
            ring[slot, dj - 1, c, :Ei, :Oi] = win[c, q, :Ei, dj:dj + Oi]
        for di in range(1, s):
            ring[slot, di * s - 1, c, :Oi, :Oi] = win[c, q, di:di + Oi, :Oi]
            for dj in range(1, s):
                ring[slot, di * s + dj - 1, c, :Oi, :Oi] = \
                    ring[slot, dj - 1, c, di:di + Oi, :Oi]


def _box_partial(win, ring, q, slot, Ei: int, Oi: int, s: int):
    """Write the ij-partial box sum of window plane q into ring ``slot``
    (q mod (2g+1)), aligned at offset (0, 0).

    The j pass folds the 2g+1 lane-offset slices ``win[c, q, :Ei,
    dj:dj+Oi]`` over all Ei rows and stages the sum aligned in ring slot
    2g+1; the i pass folds its 2g+1 sublane-offset row slices. Each fold
    is left to right in offset order, as ref.stencil_sum_ref's box form.
    """
    for c in range(win.shape[0]):
        r = win[c, q, :Ei, :Oi]
        for dj in range(1, s):
            r = r + win[c, q, :Ei, dj:dj + Oi]
        ring[s, c, :Ei, :Oi] = r
        part = ring[s, c, :Oi, :Oi]
        for di in range(1, s):
            part = part + ring[s, c, di:di + Oi, :Oi]
        ring[slot, c, :Oi, :Oi] = part


def _fused_kernel(nbr_ref, bnd_ref, *refs, T: int, s: int, g: int,
                  S: int, kc: int, hb: int, rule, bc, box: float | None):
    """S substeps of tap-sum + update rule on one k-chunk, in VMEM.

    Grid step (i, z) computes planes [z·kc, (z+1)·kc) of block i. Its
    window starts at (C, kc+2·S·g, T+2·S·g, T+2·S·g) and shrinks by g
    per side each substep — boundary sites are recomputed redundantly
    instead of re-read from HBM (DESIGN.md §4). Each substep walks the
    output planes in order and overwrites the window in place (output
    plane p needs input planes p..p+2g only, so slot p is free once it
    is computed); the last substep writes the C·kc·T² slab instead.
    Nothing intermediate (tap sums, partial states) ever touches HBM.
    Every substep tap-sums **all C channels** and hands the stacked
    fields to the rule (DESIGN.md §9).

    Tap-copy ring (DESIGN.md §4): a tap at an offset (di, dj) off the
    (8, 128) tiling costs a relayout of every vreg it reads, so each
    window plane is shifted once per substep into its (2g+1)² - 1
    offset copies, stored aligned in the ``ring`` scratch, and every
    tap is an aligned load of a copy — (0, 0) reads the window plane
    itself. Output plane p reads planes p..p+2g, so the ring holds 2g+1
    planes' copies in slots q mod (2g+1): iteration p first shifts
    plane p+2g into the slot plane p-1 held, then sums, then overwrites
    window plane p, whose copies (and whose (0, 0) read) are done by
    then. The taps are summed in the same (dk, di, dj) order from the
    same values as a sum of window slices, so every output is
    bit-identical to it.

    Box form (``box`` is the centre weight, rules.box_centre): the same
    walk, but plane q's ring slot holds one ij-partial sum per channel
    (``_box_partial``) instead of its tap copies, and output plane p is
    the k fold of slots p..p+2g — no weights, no multiplies. A zero
    centre is then taken off: ``tap = B - centre``, the centre read from
    window plane p+g before plane p is overwritten.

    Clamped runs (DESIGN.md §8): before every substep, the outer
    ``g·(S-u)`` ghost layers on faces flagged in ``bnd_ref`` (the second
    scalar-prefetch operand) are substituted with boundary values —
    dirichlet constants or the replicated domain-edge plane, per channel
    — so domain sites only ever consume valid taps and clamped faces
    temporally block exactly as deep as periodic ones. The ring is
    formed from the refreshed window.
    """
    if box is None:
        w_ref, refs = refs[0], refs[1:]
    pieces, o_ref, win, ring = refs[:27], refs[27], refs[28], refs[29]
    form_plane = _fused_shift if box is None else _box_partial
    multi = len(o_ref.shape) == 5
    C = win.shape[0]
    h = S * g
    _fused_assemble(pieces, win, T, h, kc, hb)
    i, z = pl.program_id(0), pl.program_id(1)
    nz = T // kc
    flags = (jnp.where(z == 0, bnd_ref[i, 0], 0),
             jnp.where(z == nz - 1, bnd_ref[i, 1], 0),
             *(bnd_ref[i, c] for c in range(2, 6)))
    for u in range(S):
        d = g * (S - u)                          # ghost depth of this substep
        Ek, Ei = kc + 2 * d, T + 2 * d           # current window extents
        Oi = Ei - 2 * g
        if bc.clamped:
            _fused_refresh(win, flags, d, Ek, Ei, bc)

        def first(q, carry, Ei=Ei, Oi=Oi):     # planes 0..2g-1 into the ring
            form_plane(win, ring, q, q, Ei, Oi, s)
            return carry
        jax.lax.fori_loop(0, 2 * g, first, 0)

        def plane(p, carry, Ei=Ei, Oi=Oi, last=u == S - 1):
            slots = [jax.lax.rem(p + dk, s) for dk in range(s)]
            form_plane(win, ring, p + 2 * g, slots[2 * g], Ei, Oi, s)

            taps = []
            if box is None:
                def tap(dk, di, dj, c):
                    if di == dj == 0:
                        return win[c, p + dk, :Oi, :Oi]
                    return ring[slots[dk], di * s + dj - 1, c, :Oi, :Oi]

                for c in range(C):
                    acc = jnp.zeros((Oi, Oi), jnp.float32)
                    for dk in range(s):
                        for di in range(s):
                            for dj in range(s):
                                acc = acc + w_ref[dk, di, dj].astype(
                                    jnp.float32) * tap(dk, di, dj, c)
                    taps.append(acc)
                centre = jnp.stack([tap(g, g, g, c) for c in range(C)])
            else:
                cs = [win[c, p + g, g:g + Oi, g:g + Oi] for c in range(C)]
                for c in range(C):
                    acc = ring[slots[0], c, :Oi, :Oi]
                    for dk in range(1, s):
                        acc = acc + ring[slots[dk], c, :Oi, :Oi]
                    taps.append(acc - cs[c] if box == 0.0 else acc)
                centre = jnp.stack(cs)
            new = rule.apply(centre, jnp.stack(taps), g)
            if not last:
                win[:, p, :Oi, :Oi] = new
            elif multi:
                o_ref[:, 0, p] = new.astype(o_ref.dtype)
            else:
                o_ref[0, p] = new[0].astype(o_ref.dtype)
            return carry
        jax.lax.fori_loop(0, Ek - 2 * g, plane, 0)


def stencil_step_fused(store: jnp.ndarray, weights: jnp.ndarray,
                       nbr: jnp.ndarray, bnd: jnp.ndarray | None = None,
                       *, g: int, S: int = 1, rule: str = "gol",
                       bc: BoundarySpec | MixedBoundary | str = PERIODIC,
                       interpret: bool | None = None) -> jnp.ndarray:
    """S fused timesteps over the resident store, one HBM round-trip.

    store:   (nb_src, T, T, T) — or the multi-field ``(C, nb_src, T³)``
             stacked store (DESIGN.md §9) when the rule declares C > 1 —
             SFC-ordered, no halo duplication, persists across launches
             (stencil/pipeline.ResidentPipeline). May hold *more* blocks
             than the grid computes: the distributed pipeline appends
             exchanged shell blocks after the core store
             (core/neighbors.extended_neighbor_table) and the kernel
             only writes the nbr-indexed core. All C channels share the
             one block permutation, neighbour table and grid: one grid
             step assembles C windows and writes C slabs.
    weights: (2g+1, 2g+1, 2g+1) tap weights (ops.uniform_weights for the
             classic neighbour-count rules), shared by every channel.
             Concrete box weights take the box form, any others the
             weighted form (rules.tap_form), decided here at trace time
    nbr:     (nb, 27) int32 neighbour table (core.neighbors — periodic,
             clamped, mixed, or extended), scalar-prefetched; nb ≤
             nb_src, and column SELF_COL must be the row index (the
             builders guarantee it)
    bnd:     (nb, 6) int32 clamped-domain-face flags per block, OFFSETS_FACE
             column order (core.neighbors.boundary_face_table; the
             distributed pipeline masks it by mesh position). Required
             when ``bc`` is clamped; ignored (may be None) for periodic.
    g:       stencil radius; S: substeps per launch; rule: kernels/rules.py
             registry key ("gol" | "jacobi" | "identity" | "wave") — the
             rule's declared ``channels`` must match the store's C
    bc:      boundary contract (core.boundary): "periodic" (default) |
             "dirichlet" | "neumann0" | a per-axis ``MixedBoundary``,
             applied to every channel alike
    interpret: None (default) compiles on a TPU and interprets on the
             CPU; True on a TPU raises (kernels/backend.py)
    returns: same shape as ``store``'s computed core, in store dtype —
             bit-identical (for f32 stores) to S sequential resident
             steps of the same rule and boundary.

    The grid is (nb, T/kc): one step per k-chunk of a block
    (``fused_geometry``). Halo pieces along k have extent S·g and are
    addressed in those units, so S·g must divide T (S·g ≤ T: the window
    may only reach into directly adjacent blocks). The two scalar-
    prefetch tables live in SMEM, so nb is capped on the TPU
    (:data:`SMEM_TABLE_BYTES`). Substeps
    run in f32; non-f32 stores would round once per launch instead of
    once per step, so bit-identity to the sequential path is f32-only.
    """
    return _fused_launch(store, weights, nbr, bnd, g=g, S=S, rule=rule,
                         bc=bc, interpret=interpret, box=box_centre(weights))


@functools.partial(jax.jit, static_argnames=("g", "S", "rule", "bc",
                                             "interpret", "box"))
def _fused_launch(store, weights, nbr, bnd, *, g: int, S: int, rule: str,
                  bc, interpret: bool | None, box: float | None):
    interpret = resolve_interpret(interpret)
    r = get_rule(rule)
    multi = store.ndim == 5
    C = store.shape[0] if multi else 1
    if C != r.channels:
        raise ValueError(
            f"rule {r.name!r} advances {r.channels} channel(s) but the store "
            f"carries {C} (shape {store.shape}); stack the fields on the "
            "leading axis (core.layout.blockize_fields)")
    nb_src, T = store.shape[-4], store.shape[-3]
    s = 2 * g + 1
    bc = as_boundary(bc)
    assert store.shape[-4:] == (nb_src, T, T, T), store.shape
    assert weights.shape == (s, s, s), (weights.shape, s)
    nb = nbr.shape[0]
    assert nbr.shape == (nb, 27) and nb <= nb_src, (nbr.shape, store.shape)
    h = S * g
    if S < 1 or h > T or T % h:
        raise ValueError(
            f"fused kernel needs 1 <= S and S*g | T, got T={T}, g={g}, S={S}")
    if bc.clamped and bnd is None:
        raise ValueError(f"bc={bc.kind!r} needs the (nb, 6) bnd flag table "
                         "(core.neighbors.boundary_face_table)")
    if bnd is None:
        bnd = jnp.zeros((nb, 6), jnp.int32)
    assert bnd.shape == (nb, 6), bnd.shape
    if not interpret and 4 * nb * (27 + 6) > SMEM_TABLE_BYTES:
        raise ValueError(
            f"{nb} blocks need {4 * nb * 33} B of scalar-prefetched tables, "
            f"over the {SMEM_TABLE_BYTES} B SMEM budget: use a larger block "
            f"edge than T={T}")
    kc, hb = fused_geometry(T, h)

    def fixed(i, z, nbr_ref, bnd_ref):
        return (0, 0, 0)

    in_specs = [] if box is not None else [pl.BlockSpec((s, s, s), fixed)]
    in_specs += _fused_piece_specs(T, h, kc, hb, C if multi else None)
    if multi:
        out_shape = jax.ShapeDtypeStruct((C, nb, T, T, T), store.dtype)
        out_spec = pl.BlockSpec((C, 1, kc, T, T),
                                lambda i, z, nbr_ref, bnd_ref: (0, i, z, 0, 0))
    else:
        out_shape = jax.ShapeDtypeStruct((nb, T, T, T), store.dtype)
        out_spec = pl.BlockSpec((1, kc, T, T),
                                lambda i, z, nbr_ref, bnd_ref: (i, z, 0, 0))
    kern = functools.partial(_fused_kernel, T=T, s=s, g=g, S=S, kc=kc, hb=hb,
                             rule=r, bc=bc, box=box)
    W = T + 2 * h
    ring = (s + 1, C) if box is not None else (s, s * s - 1, C)
    scratch = [pltpu.VMEM((C, kc + 2 * h, W, W), jnp.float32),
               pltpu.VMEM(ring + (W, W - 2 * g), jnp.float32)]
    operands = ([] if box is not None else [weights]) + [store] * 27
    return pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb, T // kc),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="stencil_step_fused",
        interpret=interpret,
    )(nbr.astype(jnp.int32), bnd.astype(jnp.int32), *operands)

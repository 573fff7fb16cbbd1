"""Pure-jnp oracles for every Pallas kernel (allclose targets for tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.boundary import PERIODIC, as_boundary, pad_cube

from .rules import apply_window_bc, box_centre, get_rule

__all__ = ["stencil_sum_ref", "tap_sum", "gol_rule_ref", "gol3d_step_ref",
           "assemble_halo_ref", "stencil_fused_ref", "fields_step_ref",
           "gather_rows_ref", "attention_ref"]


def stencil_sum_ref(blocks: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """Weighted (2g+1)³ stencil over halo-extended blocks.

    blocks:  (nb, T+2g, T+2g, T+2g)
    weights: (2g+1, 2g+1, 2g+1)
    returns: (nb, T, T, T) — acc[b, z] = sum_d w[d] * blocks[b, z+d],
             summed in the fused kernel's order (:func:`tap_sum`)
    """
    return tap_sum(blocks, weights)


def tap_sum(x: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """The (2g+1)³ tap sum over the last three axes of ``x`` (each
    shrinks by 2g), in f32 and in the fused kernel's order.

    Box weights (rules.box_centre) take the box form: a j pass, an i
    pass and a k pass, each a left fold of 2g+1 offset slices in offset
    order, and then, for a zero centre, ``- centre``. Any other weights
    take the weighted form: ``w·x`` terms folded in (dk, di, dj) order.
    """
    s = weights.shape[0]
    g = (s - 1) // 2
    Ok, Oi, Oj = (n - 2 * g for n in x.shape[-3:])
    box = box_centre(weights)
    if box is not None:
        x = x.astype(jnp.float32)
        r = x[..., :Oj]
        for dj in range(1, s):
            r = r + x[..., dj:dj + Oj]
        part = r[..., :Oi, :]
        for di in range(1, s):
            part = part + r[..., di:di + Oi, :]
        acc = part[..., :Ok, :, :]
        for dk in range(1, s):
            acc = acc + part[..., dk:dk + Ok, :, :]
        if box == 0.0:
            acc = acc - x[..., g:g + Ok, g:g + Oi, g:g + Oj]
        return acc
    acc = jnp.zeros(x.shape[:-3] + (Ok, Oi, Oj), dtype=jnp.float32)
    for dk in range(s):
        for di in range(s):
            for dj in range(s):
                acc = acc + weights[dk, di, dj].astype(jnp.float32) * (
                    x[..., dk:dk + Ok, di:di + Oi, dj:dj + Oj].astype(jnp.float32))
    return acc


def assemble_halo_ref(store: jnp.ndarray, nbr: jnp.ndarray, g: int) -> jnp.ndarray:
    """Resident halo assembly: gather each block's (T+2g)³ window from the
    un-haloed curve-ordered store via the SFC neighbour table.

    store: (nb_src, T, T, T) — or the stacked multi-field
    (C, nb_src, T, T, T) store (DESIGN.md §9), whose channels share the
    one neighbour table; nbr: (nb, 27) full table (core.neighbors),
    nb ≤ nb_src — the distributed extended store appends shell blocks
    after the core, so the table may index more blocks than it has rows;
    returns (nb, T+2g, T+2g, T+2g) (with the leading C kept for stacked
    input). With the periodic table of the same ordering every window
    is the block's slice of the wrap-padded cube — the jnp oracle of the
    in-kernel assembly in stencil3d.stencil_step_fused.
    """
    multi = store.ndim == 5
    T = store.shape[-3]
    assert g <= T, (g, T)
    nbr = jnp.asarray(nbr)
    lead = (slice(None),) if multi else ()
    own = store if store.shape[-4] == nbr.shape[0] \
        else store[lead + (slice(None, nbr.shape[0]),)]
    spans = (slice(T - g, T), slice(None), slice(0, g))  # lo, mid, hi
    slabs = []
    for a in range(3):
        planes = []
        for b in range(3):
            parts = []
            for c in range(3):
                col = a * 9 + b * 3 + c
                src = own if col == 13 \
                    else store[lead + (nbr[:, col],)]
                parts.append(src[lead + (slice(None), spans[a], spans[b],
                                         spans[c])])
            planes.append(jnp.concatenate(parts, axis=-1))
        slabs.append(jnp.concatenate(planes, axis=-2))
    return jnp.concatenate(slabs, axis=-3)


def stencil_fused_ref(store: jnp.ndarray, weights: jnp.ndarray,
                      nbr: jnp.ndarray, *, S: int = 1, rule: str = "gol",
                      bc=PERIODIC, bnd: jnp.ndarray | None = None) -> jnp.ndarray:
    """Oracle for stencil3d.stencil_step_fused: the temporal-blocked form.

    Assembles the wide (T+2·S·g)³ window once, then runs S substeps of
    tap-sum + rule with the window shrinking by g per side — the exact
    computation the fused kernel performs in VMEM, vectorised over nb.
    Bit-identical (f32 stores) to S sequential single-substep calls. Accepts
    the distributed extended store (shell blocks appended after the
    core, nbr rows = core only) like the kernel does, and the stacked
    multi-field ``(C, nb, T³)`` store (DESIGN.md §9): every substep
    tap-sums all C channels and hands the stacked fields to the rule,
    exactly as the kernel does.

    Clamped boundaries (DESIGN.md §8) mirror the kernel exactly: before
    every substep the ghost layers on faces flagged in ``bnd``
    ((nb, 6), core.neighbors.boundary_face_table column order) are
    substituted via rules.apply_window_bc — the same shared helper,
    applied per channel by broadcast.
    """
    g = (weights.shape[0] - 1) // 2
    bc = as_boundary(bc)
    r = get_rule(rule)
    if bc.clamped and bnd is None:
        raise ValueError(f"bc={bc.kind!r} needs the (nb, 6) bnd flag table")
    multi = store.ndim == 5
    C = store.shape[0] if multi else 1
    if C != r.channels:
        raise ValueError(
            f"rule {r.name!r} advances {r.channels} channel(s) but the store "
            f"carries {C} (shape {store.shape})")
    x = assemble_halo_ref(store, nbr, S * g).astype(jnp.float32)
    for u in range(S):
        x = apply_window_bc(x, jnp.asarray(bnd), g * (S - u), bc) \
            if bc.clamped else x
        if multi:
            tap = jnp.stack([stencil_sum_ref(x[c], weights) for c in range(C)])
            centre = x[:, :, g:-g, g:-g, g:-g]
        else:
            tap = stencil_sum_ref(x, weights)
            centre = x[:, g:-g, g:-g, g:-g]
        x = r.apply(centre, tap, g)
    return x.astype(store.dtype)


def fields_step_ref(fields: jnp.ndarray, weights: jnp.ndarray, g: int,
                    rule: str = "gol", bc=PERIODIC) -> jnp.ndarray:
    """One multi-field update on (C, M, M, M) canonical row-major fields.

    The ordering-independent sequential oracle of the C-channel stack
    (DESIGN.md §9): ghost-extend every channel under ``bc``
    (core.boundary.pad_cube — per-axis for mixed contracts), take the
    tap sum per channel **in the fused kernel's order** (:func:`tap_sum`,
    so f32 results match the blocked paths bitwise, not just
    numerically), then apply the registry rule to the stacked fields. A
    3-D input is treated as C=1 and returned 3-D.
    """
    r = get_rule(rule)
    squeeze = fields.ndim == 3
    if squeeze:
        fields = fields[None]
    C, M = fields.shape[0], fields.shape[1]
    assert fields.shape == (C, M, M, M), fields.shape
    if C != r.channels:
        raise ValueError(
            f"rule {r.name!r} advances {r.channels} channel(s), got {C}")
    s = weights.shape[0]
    assert s == 2 * g + 1, (weights.shape, g)
    xp = jnp.stack([pad_cube(fields[c], g, bc) for c in range(C)])
    tap = tap_sum(xp, weights)
    out = r.apply(fields.astype(jnp.float32), tap, g).astype(fields.dtype)
    return out[0] if squeeze else out


def gol_rule_ref(state: jnp.ndarray, neigh_sum: jnp.ndarray, g: int) -> jnp.ndarray:
    """Generalised Game-of-Life rule (paper's gol3d, stencil radius g).

    Thresholds per rules.gol_thresholds — for g=1 (n=26): survive 6..9,
    born 9, a standard 3D GoL variant. Kept as the stable oracle entry
    point; the logic itself lives in the kernels/rules.py registry so
    the fused kernel shares it verbatim.
    """
    return get_rule("gol").apply(state, neigh_sum, g).astype(state.dtype)


def gol3d_step_ref(cube: jnp.ndarray, g: int, bc=PERIODIC) -> jnp.ndarray:
    """One gol3d update on a (Gk,Gi,Gj) box in canonical row-major layout.

    The box is usually a cube (M,M,M); a mesh of cubic shards covers a
    non-cubic global box, which this oracle takes unchanged. ``bc`` is
    the boundary contract (core.boundary): the ghost extension is a wrap
    pad (periodic), a constant pad (dirichlet) or an edge-replication
    pad (neumann0) — the ordering-independent oracle every pipeline form
    is validated against, for every boundary kind.
    """
    s = 2 * g + 1
    xp = pad_cube(cube, g, bc)
    gk, gi, gj = cube.shape
    total = jnp.zeros_like(cube, dtype=jnp.float32)
    for dk in range(s):
        for di in range(s):
            for dj in range(s):
                total = total + xp[dk:dk + gk, di:di + gi,
                                   dj:dj + gj].astype(jnp.float32)
    neigh = total - cube.astype(jnp.float32)  # exclude centre
    return gol_rule_ref(cube, neigh, g)


def gather_rows_ref(src: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """src: (N, L); idx: (R,) int32 -> (R, L)."""
    return jnp.take(src, idx, axis=0)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True) -> jnp.ndarray:
    """Dense softmax attention oracle. q,k,v: (BH, S, D) (heads pre-folded)."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / np.sqrt(d)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        # align causal diagonal to the END (supports Sk > Sq: decode w/ cache)
        offs = sk - sq
        mask = np.tril(np.ones((sq, sk), dtype=bool), k=offs)
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)

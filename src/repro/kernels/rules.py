"""Update-rule registry + boundary tap substitution (DESIGN.md §4, §8, §9).

The temporal-blocked kernel (stencil3d.stencil_step_fused) applies
``fields' = rule(fields, tap_sums)`` after every in-VMEM tap sum, so the
rule is the only workload-specific piece of the pipeline. Registering it
here — one pure-jnp callable shared verbatim by the Pallas kernel, the
jnp oracles (kernels/ref.py) and the fused driver
(stencil/pipeline.ResidentPipeline) — keeps the three paths bit-identical
by construction and lets a new workload ride the whole resident
machinery by adding one entry.

Multi-field contract (DESIGN.md §9): a rule declares ``channels`` (C)
and its ``apply(fields_f32, tap_sums_f32, g)`` receives the C state
fields *stacked on a leading axis* — ``(C, ...)`` where ``...`` is the
spatial window in the kernel, ``(nb, ...)`` in the batched oracles, or
the canonical cube in the global reference — together with the weighted
tap sum of **every** channel, and returns the next stacked fields. The
classic C=1 rules (gol, jacobi, identity) are elementwise, so the same
callables serve the stacked form bit-identically; ``wave`` (C=2) is the
FDTD-style leapfrog workload that actually couples channels.

Rules compute in float32 (the kernels' accumulation dtype); callers cast
back to the store dtype at the step boundary. ``tap_sums`` is the
weighted (2g+1)³ tap sum of the *current* state per channel — with the
default zero-centre uniform weights (ops.uniform_weights) it is the
neighbour count/sum the classic rules expect.

:func:`apply_window_bc` is the rules' boundary companion (DESIGN.md §8):
on clamped runs every substep's tap sum must read *boundary* values —
not wrapped or stale data — from the ghost sites outside the physical
domain, so the kernel and the oracles call this one helper to substitute
them before each tap sum. Like the rules themselves it is a single
pure-jnp definition shared verbatim by the Pallas kernel (per-window,
scalar flags from the prefetch channel) and the batched jnp oracles,
which is what keeps fused-vs-sequential clamped runs bit-identical.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.boundary import BoundarySpec, MixedBoundary, as_boundary

__all__ = ["UpdateRule", "RULES", "get_rule", "gol_thresholds",
           "WAVE_KAPPA", "apply_window_bc", "box_centre", "tap_form"]


@dataclass(frozen=True)
class UpdateRule:
    """name: registry key; apply(fields_f32, tap_sums_f32, g) -> next_f32.

    ``channels`` (C) is the number of state fields the rule advances;
    ``apply`` sees them stacked on the leading axis (C=1 rules are
    elementwise and accept any shape unchanged). The store a rule rides
    is ``(C, nb, T, T, T)`` — one shared block permutation, C channels
    (DESIGN.md §9).
    """
    name: str
    apply: Callable[[jnp.ndarray, jnp.ndarray, int], jnp.ndarray]
    doc: str = ""
    channels: int = 1


def gol_thresholds(g: int) -> tuple[int, int, int]:
    """(survive_lo, survive_hi, born) for the generalised GoL rule.

    With n = (2g+1)³ - 1 neighbours, thresholds scale with the classic
    2D 8-neighbour rule: survive in [2,3]·n/8, born at exactly round(3n/8).
    For g=1 (n=26): survive 6..9, born 9 — a standard 3D GoL variant.
    """
    n = (2 * g + 1) ** 3 - 1
    lo = (2 * n) // 8
    hi = (3 * n) // 8
    return lo, hi, hi


def _gol(centre: jnp.ndarray, tap: jnp.ndarray, g: int) -> jnp.ndarray:
    # Float selects only: Mosaic cannot select between boolean vectors.
    lo, hi, born = gol_thresholds(g)
    one, zero = jnp.float32(1.0), jnp.float32(0.0)
    survive = jnp.where(tap >= lo, jnp.where(tap <= hi, one, zero), zero)
    birth = jnp.where(tap == born, one, zero)
    return jnp.where(centre > 0.5, survive, birth)


def _jacobi(centre: jnp.ndarray, tap: jnp.ndarray, g: int) -> jnp.ndarray:
    # Jacobi relaxation / explicit heat step: box-filter mean over the
    # (2g+1)³ cube (centre + the zero-centre-weighted neighbour sum).
    n = (2 * g + 1) ** 3 - 1
    return (centre + tap) / jnp.float32(n + 1)


def _identity(centre: jnp.ndarray, tap: jnp.ndarray, g: int) -> jnp.ndarray:
    return tap


# Courant-like coupling of the wave leapfrog. A power of two, so the
# κ·lap product is an *exact* f32 scaling — FMA contraction of
# ``v + κ·lap`` cannot shift the rounding between compiled programs —
# and small enough that κ·λ_max < 4 for the 26-neighbour Laplacian
# (λ_max ≤ 2n with n = 26): the leapfrog stays stable, state bounded.
WAVE_KAPPA = 0.03125  # 2**-5


def _wave(fields: jnp.ndarray, taps: jnp.ndarray, g: int) -> jnp.ndarray:
    """FDTD-style 2-field wave leapfrog (DESIGN.md §9): u is the
    displacement, v the velocity. The Laplacian comes from the uniform
    zero-centre tap sum: lap u = Σ_neigh u - n·u; then

        v' = v + κ · lap u        (kick)
        u' = u + v'               (drift)

    — symplectic Euler on the semi-discrete wave equation. v's tap sum
    arrives (the kernel computes all C channels, the ×C bytes model
    counts it) but the rule does not consume it.

    ``n·u`` is subtracted as a sum of power-of-two multiples (16u, 8u,
    2u for g=1): every product is an exact f32 scaling, so XLA's FMA
    contraction cannot shift a rounding between compiled programs and
    the rule stays bit-identical across every pipeline form — the same
    reproducibility contract the integer-valued gol rule gets for free.
    """
    n = (2 * g + 1) ** 3 - 1
    u, v = fields[0], fields[1]
    lap = taps[0]
    bit = 1 << (n.bit_length() - 1)
    rem = n
    while bit:
        if rem >= bit:
            lap = lap - jnp.float32(bit) * u
            rem -= bit
        bit >>= 1
    v2 = v + jnp.float32(WAVE_KAPPA) * lap
    u2 = u + v2
    return jnp.stack([u2, v2])


RULES: dict[str, UpdateRule] = {
    "gol": UpdateRule("gol", _gol, "generalised 3D Game of Life (paper §4)"),
    "jacobi": UpdateRule("jacobi", _jacobi, "Jacobi/heat box-filter relaxation"),
    "identity": UpdateRule("identity", _identity, "raw weighted stencil sum"),
    "wave": UpdateRule("wave", _wave,
                       "FDTD-style 2-field wave leapfrog (u, v)", channels=2),
}


def _plane(x: jnp.ndarray, axis: int, i: int) -> jnp.ndarray:
    """Size-1 static slice at index ``i`` along one of the last 3 axes."""
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(i, i + 1)
    return x[tuple(idx)]


def apply_window_bc(x: jnp.ndarray, flags, depth: int,
                    bc: BoundarySpec | MixedBoundary | str,
                    axes: tuple[int, ...] = (0, 1, 2)) -> jnp.ndarray:
    """Substitute boundary values into a window's ghost layers.

    x:      a stencil window whose last three axes span the spatial
            extent — ``(Ek, E, E)`` or ``(C, Ek, E, E)`` inside the fused
            kernel (one k-chunk of a block), ``(nb, E, E, E)`` /
            ``(C, nb, E, E, E)`` in the batched jnp oracles. All leading axes (channels, blocks)
            broadcast: the contract applies to every channel alike.
    flags:  which of the window's six faces are clamped *domain* faces,
            in ``core.neighbors.OFFSETS_FACE`` order [k-,k+,i-,i+,j-,j+]
            — a ``(6,)``/``(nb, 6)`` int array, or a sequence of six
            scalars (the kernel reads them off the scalar-prefetch ref).
    depth:  ghost width to refresh: the outer ``depth`` layers of each
            flagged face are outside the physical domain.
    bc:     the contract (core.boundary): dirichlet writes the constant,
            neumann0 replicates the adjacent domain-edge plane; periodic
            is a no-op (ghost data arrives by wrap/exchange instead). A
            ``MixedBoundary`` applies its own spec per axis — periodic
            axes are skipped entirely, so their ghost layers keep the
            wrapped/exchanged data.
    axes:   the spatial axes to refresh (0=k, 1=i, 2=j), in this order.
            The fused kernel refreshes whole k planes itself and passes
            one plane at a time with ``axes=(1, 2)``.

    Axes are refreshed sequentially (k, then i, then j) so corner ghost
    regions compose exactly like ``jnp.pad``'s per-axis semantics — the
    invariant that keeps every pipeline form equal to the padded-cube
    oracle (ref.gol3d_step_ref). The fused kernel calls this before
    *every* substep with the shrinking ghost depth ``g·(S-u)``
    (DESIGN.md §8): the refresh re-derives ghost layers from the current
    in-window state, which is what lets clamped faces temporally block
    as deep as periodic ones.
    """
    bc = as_boundary(bc)
    if not bc.clamped or depth == 0:
        return x
    batch = x.ndim > 3

    def flag(col):
        if isinstance(flags, (list, tuple)):
            f = flags[col] != 0
        else:
            f = flags[..., col] != 0
        return f[..., None, None, None] if batch else f

    for ax in axes:
        ax_bc = bc.axes[ax]
        if not ax_bc.clamped:
            continue
        axis = ax - 3
        E = x.shape[axis]
        iota = jax.lax.broadcasted_iota(jnp.int32, x.shape[-3:], ax)
        if ax_bc.kind == "dirichlet":
            lo_fill = hi_fill = jnp.asarray(ax_bc.value, x.dtype)
        else:  # neumann0: replicate the nearest in-domain plane
            lo_fill = _plane(x, axis, depth)
            hi_fill = _plane(x, axis, E - 1 - depth)
        x = jnp.where((iota < depth) & flag(2 * ax), lo_fill, x)
        x = jnp.where((iota >= E - depth) & flag(2 * ax + 1), hi_fill, x)
    return x


def box_centre(weights) -> float | None:
    """The centre weight if ``weights`` are the (2g+1)³ box, else None.

    The box: concrete weights, every off-centre one exactly 1.0 and the
    centre 0.0 or 1.0 (ops.uniform_weights is the zero-centre box). Its
    tap sum needs no multiply and is separable, so the fused kernel and
    the oracles sum it as three (2g+1)-tap passes (the box form,
    stencil3d). Any other weights, or a traced array whose values are
    unknown when the kernel is traced, take the weighted form.
    """
    if isinstance(weights, jax.core.Tracer):
        return None
    w = np.asarray(weights, np.float32)
    mid = w.size // 2                      # the centre of a (2g+1)³ cube
    centre = float(w.flat[mid])
    off = np.delete(w.ravel(), mid)
    return centre if centre in (0.0, 1.0) and np.all(off == 1.0) else None


def tap_form(weights) -> str:
    """``"box"`` or ``"weighted"``: the tap sum the fused kernel and its
    oracles run for these weights (:func:`box_centre`)."""
    return "weighted" if box_centre(weights) is None else "box"


def get_rule(rule: str | UpdateRule) -> UpdateRule:
    if isinstance(rule, UpdateRule):
        return rule
    try:
        return RULES[rule]
    except KeyError:
        raise ValueError(
            f"unknown update rule {rule!r}; known: {sorted(RULES)}") from None

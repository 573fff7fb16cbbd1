"""Physical boundary conditions for the stencil pipelines (DESIGN.md §8).

The paper's experiments — and the SEM locality study (arXiv:2104.08416)
it builds on — run on *physical* domains whose edges do not wrap. This
module is the one definition of that contract, shared by every pipeline
form (resident, distributed) and their jnp oracles:

- ``periodic``         — wrap at the domain edge (the torus default);
- ``dirichlet(value)`` — ghost sites hold a fixed value at all times;
- ``neumann0``         — zero normal gradient: ghost sites replicate the
  nearest in-domain plane (clamp-copy, ``jnp.pad(mode="edge")``).

A :class:`BoundarySpec` is frozen and hashable so it can ride jit static
arguments and cache keys exactly like an ``OrderingSpec``. Everything
downstream — the clamped neighbour tables (core/neighbors.py), the
in-window ghost refresh (kernels/rules.apply_window_bc), the mesh-edge
shell fill (stencil/halo.exchange_shell) and the exchange-surface
accounting (stencil/pipeline.py) — keys off the one ``kind`` string
defined here.

Per-face **mixed contracts** (DESIGN.md §8): a physical channel or slab
domain is clamped along one axis and periodic along the others (e.g. a
duct: clamped k, periodic i/j). :class:`MixedBoundary` carries one
:class:`BoundarySpec` per grid axis in ``(k, i, j)`` order; every
consumer reads the per-axis contract through the shared ``axes``
property — a plain :class:`BoundarySpec` exposes itself three times —
so uniform and mixed runs flow through identical code. On a multi-field
store (DESIGN.md §9) the contract applies to **every channel alike**.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

__all__ = ["BoundarySpec", "MixedBoundary", "PERIODIC", "NEUMANN0",
           "dirichlet", "mixed", "as_boundary", "axes_periodic", "pad_cube"]

_KINDS = ("periodic", "dirichlet", "neumann0")


@dataclass(frozen=True)
class BoundarySpec:
    """The boundary-condition contract of one stencil run.

    kind:  "periodic" | "dirichlet" | "neumann0"
    value: the fixed ghost value for dirichlet (ignored otherwise)

    ``clamped`` is the property every consumer branches on: clamped runs
    use the non-wrapping neighbour tables, refresh ghost layers per
    substep, and skip the wrapping ppermute links of the exchange.
    ``axes`` is the per-axis view shared with :class:`MixedBoundary`:
    a uniform contract is the same spec on all three axes.
    """
    kind: str = "periodic"
    value: float = 0.0

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(
                f"unknown boundary kind {self.kind!r}; known: {_KINDS}")

    @property
    def clamped(self) -> bool:
        return self.kind != "periodic"

    @property
    def axes(self) -> tuple["BoundarySpec", "BoundarySpec", "BoundarySpec"]:
        return (self, self, self)


PERIODIC = BoundarySpec("periodic")
NEUMANN0 = BoundarySpec("neumann0")


@dataclass(frozen=True)
class MixedBoundary:
    """Per-axis boundary contract: one :class:`BoundarySpec` per grid axis.

    ``k``/``i``/``j`` follow the cube's axis order (the same order the
    exchange rings and ``apply_window_bc`` traverse). Frozen + hashable
    like :class:`BoundarySpec`, so it rides jit static arguments; the
    duck-typed ``kind``/``clamped``/``axes`` surface lets every existing
    ``bc`` knob accept a mixed contract unchanged. Build with
    :func:`mixed`, which collapses a uniform triple back to the plain
    spec (keeping cache keys canonical).
    """
    k: BoundarySpec = PERIODIC
    i: BoundarySpec = PERIODIC
    j: BoundarySpec = PERIODIC

    def __post_init__(self):
        for ax in (self.k, self.i, self.j):
            if not isinstance(ax, BoundarySpec):
                raise ValueError(
                    f"MixedBoundary axes must be BoundarySpec, got {ax!r}")

    @property
    def kind(self) -> str:
        return "mixed"

    @property
    def clamped(self) -> bool:
        return any(ax.clamped for ax in self.axes)

    @property
    def axes(self) -> tuple[BoundarySpec, BoundarySpec, BoundarySpec]:
        return (self.k, self.i, self.j)


def dirichlet(value: float = 0.0) -> BoundarySpec:
    """Fixed-value boundary: ghost sites hold ``value`` at every step."""
    return BoundarySpec("dirichlet", float(value))


def mixed(k: "BoundarySpec | str" = PERIODIC,
          i: "BoundarySpec | str" = PERIODIC,
          j: "BoundarySpec | str" = PERIODIC):
    """Per-axis contract, e.g. ``mixed(k="neumann0")`` for a clamped-k slab.

    Coerces kind strings per axis and collapses a uniform triple to the
    plain :class:`BoundarySpec` so ``mixed(k=bc, i=bc, j=bc) == bc``
    (one canonical cache key per contract).
    """
    k, i, j = as_boundary(k), as_boundary(i), as_boundary(j)
    if k == i == j:
        return k
    return MixedBoundary(k, i, j)


def as_boundary(bc: "BoundarySpec | MixedBoundary | str"):
    """Coerce a registry-style string ("periodic" | "neumann0" |
    "dirichlet", the latter with value 0.0) to a :class:`BoundarySpec`;
    :class:`MixedBoundary` passes through unchanged."""
    if isinstance(bc, (BoundarySpec, MixedBoundary)):
        return bc
    return BoundarySpec(bc)


def axes_periodic(bc) -> tuple[bool, bool, bool]:
    """Per-axis wrap flags — the neighbour-table / exchange-ring view."""
    return tuple(not ax.clamped for ax in as_boundary(bc).axes)


def _pad_axis(cube: jnp.ndarray, axis: int, g: int,
              bc: BoundarySpec) -> jnp.ndarray:
    pad = [(0, 0)] * cube.ndim
    pad[axis] = (g, g)
    if bc.kind == "periodic":
        return jnp.pad(cube, pad, mode="wrap")
    if bc.kind == "dirichlet":
        return jnp.pad(cube, pad, constant_values=bc.value)
    return jnp.pad(cube, pad, mode="edge")


def pad_cube(cube: jnp.ndarray, g: int, bc) -> jnp.ndarray:
    """Ghost-extend an (M,M,M) cube by ``g`` per side under ``bc``.

    The oracle-side realisation of the contract (kernels/ref.py): wrap
    for periodic, constant fill for dirichlet, edge replication for
    neumann0. The corner semantics (per-axis sequential application in
    k, i, j order) match ``apply_window_bc`` exactly — np.pad applies
    axes in order, and a :class:`MixedBoundary` pads each axis under its
    own spec in that same order.
    """
    bc = as_boundary(bc)
    axes = bc.axes
    if axes[0] == axes[1] == axes[2]:  # uniform contract: one fused pad
        a = axes[0]
        if a.kind == "periodic":
            return jnp.pad(cube, g, mode="wrap")
        if a.kind == "dirichlet":
            return jnp.pad(cube, g, constant_values=a.value)
        return jnp.pad(cube, g, mode="edge")
    out = cube
    for ax in range(3):
        out = _pad_axis(out, ax - 3, g, axes[ax])
    return out

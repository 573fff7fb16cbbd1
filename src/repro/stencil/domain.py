"""3D domain decomposition over a device mesh."""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

__all__ = ["Decomposition3D", "make_stencil_mesh"]

STENCIL_AXES = ("dx", "dy", "dz")


def make_stencil_mesh(shape: tuple[int, int, int]) -> jax.sharding.Mesh:
    """Mesh for the stencil app. Axis order (dx,dy,dz) = (slab,row,col).

    Elasticity (DESIGN.md §10): ``shape`` may cover *fewer* devices than
    the process has — a resumed run that lost part of its machine builds
    its smaller mesh from a prefix of ``jax.devices()`` — so a 2×2×1
    mesh is valid on an 8-device host. When the shape covers the whole
    machine this defers to ``jax.make_mesh`` (which picks an
    ICI-friendly device order on real hardware). Both branches give
    every axis the ``Auto`` type: the state is placed by shard_map
    specs, and layout reshapes of sharded arrays stay legal.
    """
    n = int(np.prod(shape))
    devices = jax.devices()
    auto = (jax.sharding.AxisType.Auto,) * len(shape)
    if n == len(devices):
        return jax.make_mesh(shape, STENCIL_AXES, axis_types=auto)
    if n > len(devices):
        raise ValueError(f"mesh shape {shape} needs {n} devices, "
                         f"have {len(devices)}")
    return jax.sharding.Mesh(np.asarray(devices[:n]).reshape(shape),
                             STENCIL_AXES, axis_types=auto)


def _as_shape3(global_shape) -> tuple[int, int, int]:
    """Coerce a cube edge or per-axis extent triple to a 3-tuple."""
    if isinstance(global_shape, (int, np.integer)):
        return (int(global_shape),) * 3
    gk, gi, gj = (int(x) for x in global_shape)
    return (gk, gi, gj)


@dataclass(frozen=True)
class Decomposition3D:
    """Global domain split into P = px·py·pz local blocks.

    ``global_M`` is a cube edge (the paper's M³ domain) or a per-axis
    ``(Gk, Gi, Gj)`` extent triple — a non-cubic process grid such as
    4×2×1 decomposes a non-cubic global box into *cubic* local shards
    (the SFC machinery needs cubic power-of-2 local blocks; the global
    box may be any multiple of them, DESIGN.md §10).
    """
    global_M: "int | tuple[int, int, int]"
    procs: tuple[int, int, int]

    @property
    def global_shape(self) -> tuple[int, int, int]:
        return _as_shape3(self.global_M)

    @property
    def local_shape(self) -> tuple[int, int, int]:
        gk, gi, gj = self.global_shape
        px, py, pz = self.procs
        assert gk % px == 0 and gi % py == 0 and gj % pz == 0, \
            (self.global_shape, self.procs)
        return (gk // px, gi // py, gj // pz)

    def check_local_pow2_cube(self) -> int:
        """SFC orderings need the local block to be a 2^m cube."""
        lx, ly, lz = self.local_shape
        if not (lx == ly == lz):
            raise ValueError(f"local block must be cubic, got {self.local_shape}")
        m = int(lx).bit_length() - 1
        if (1 << m) != lx:
            raise ValueError(f"local edge must be a power of 2, got {lx}")
        return lx

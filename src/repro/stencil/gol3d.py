"""gol3d — the paper's stencil application (§4), in JAX.

Extends Game of Life to 3D with a runtime-selectable stencil radius g
(the paper's cube of size 2g+1). State is stored under a selectable
ordering; the update walks the cube along the ordering's path, realised
on TPU as the SFC-blocked kernel pipeline (kernels/stencil3d.py) whose
grid order follows the curve because the blocks are laid out along it.

``run`` blockizes once, runs K steps on the persistent curve-ordered
store with in-kernel halo streaming (stencil/pipeline.py, DESIGN.md
§3), and unblockizes once. ``substeps`` (S) temporal-blocks the run —
S whole updates per HBM round-trip (DESIGN.md §4); ``substeps=0`` lets
the pipeline's ``plan()`` autotuner pick (T, S) under the VMEM budget.
``run_distributed`` runs the same update on a device mesh.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (OrderingSpec, PERIODIC, ROW_MAJOR, BoundarySpec,
                        apply_ordering, as_boundary, undo_ordering)
from repro.kernels import backend
from repro.kernels import ref as kref

from .domain import Decomposition3D, STENCIL_AXES
from .halo import stencil_block_kind
from .pipeline import DistributedPipeline, ResidentPipeline, _default_kernel

__all__ = ["Gol3dConfig", "Gol3d"]


@dataclass(frozen=True)
class Gol3dConfig:
    """Static configuration of one gol3d run (hashable: rides jit keys).

    M:          cube edge (power of 2)
    g:          stencil radius — the update reads a (2g+1)³ tap cube
    ordering:   storage ordering of the public path state (core.orderings)
    block_T:    SFC block edge of the kernel pipelines (T | M); None
                (default) lets the platform decide — the lane-dense
                min(M, 128) for the compiled TPU kernel, else 8
    substeps:   S fused timesteps per HBM round-trip (temporal blocking,
                DESIGN.md §4); 0 delegates (T, S) to the plan() autotuners
    use_kernel: Pallas kernels vs jnp oracles; None (default) lets the
                platform decide — the compiled kernels on a TPU, the
                oracles on the CPU
    bc:         boundary contract (core.boundary.BoundarySpec or kind
                string): "periodic" wraps like a torus; "dirichlet" /
                "neumann0" clamp the domain edges physically
                (DESIGN.md §8) — the single-device and mesh runs
                honour the same contract
    density:    initial live fraction of the random seed state
    seed:       RNG seed of the initial state
    """
    M: int = 64                      # cube edge (power of 2)
    g: int = 1                       # stencil radius
    ordering: OrderingSpec = ROW_MAJOR
    block_T: int | None = None       # None: 128 for the TPU kernel, else 8
    substeps: int = 1                # S per fused launch; 0 = autotune (T, S)
    use_kernel: bool | None = None   # None: kernel on TPU, jnp oracle on CPU
    density: float = 0.3             # initial live fraction
    seed: int = 0
    bc: BoundarySpec = PERIODIC      # boundary contract (core.boundary)

    def __post_init__(self):
        object.__setattr__(self, "bc", as_boundary(self.bc))
        _default_kernel(self, edge="block_T")


@dataclass
class Gol3d:
    """One gol3d run. ``state_path`` is the (M³,) state in ``cfg.ordering``
    order; None (default) draws the seeded random cube."""
    cfg: Gol3dConfig
    state_path: jnp.ndarray | None = None

    def __post_init__(self):
        if self.state_path is not None:
            return
        rng = np.random.default_rng(self.cfg.seed)
        cube = (rng.random((self.cfg.M,) * 3) < self.cfg.density).astype(np.float32)
        self.state_path = apply_ordering(jnp.asarray(cube), self.cfg.ordering)

    @property
    def cube(self) -> jnp.ndarray:
        return undo_ordering(self.state_path, self.cfg.ordering, self.cfg.M)

    @property
    def block_kind(self) -> str:
        """Block-grid curve for the kernel pipelines: the ordering's own
        curve when it has one, else Morton (the pipeline is SFC-blocked
        even when the logical state ordering is row/column-major)."""
        return stencil_block_kind(self.cfg.ordering)

    def resident_pipeline(self) -> ResidentPipeline:
        """The fused driver over this app's block layout (DESIGN.md §3–§4).

        ``cfg.substeps`` threads straight through as the pipeline's S;
        ``substeps=0`` delegates (T, S) to the ``plan()`` autotuner.
        """
        cfg = self.cfg
        if cfg.substeps == 0:
            return ResidentPipeline.plan(cfg.M, g=cfg.g, kind=self.block_kind,
                                         bc=cfg.bc, use_kernel=cfg.use_kernel)
        return ResidentPipeline(M=cfg.M, T=cfg.block_T, g=cfg.g,
                                kind=self.block_kind, S=cfg.substeps,
                                bc=cfg.bc, use_kernel=cfg.use_kernel)

    def resident_fn(self, n_steps: int):
        """jit'd (state_path -> state_path) fused n_steps run: one program
        from the path-ordered state through the block store and back,
        with the state donated on a TPU — a chip holds the state and
        the store, not every intermediate of the layout boundary (and a
        reference kept to the old state is invalidated there)."""
        pipe = self.resident_pipeline()
        ordering, M = self.cfg.ordering, self.cfg.M
        donate = (0,) if backend.on_tpu() else ()

        @functools.partial(jax.jit, donate_argnums=donate)
        def run(state_path):
            cube = pipe.run(undo_ordering(state_path, ordering, M), n_steps)
            return apply_ordering(cube, ordering)

        return run

    def run(self, n_steps: int) -> jnp.ndarray:
        """Fused multi-step run: the curve-ordered block store is the
        resident state for all n_steps; layout conversions happen once at
        each end."""
        self.state_path = jax.block_until_ready(
            self.resident_fn(n_steps)(self.state_path))
        return self.state_path

    def distributed_pipeline(self, mesh: jax.sharding.Mesh) -> DistributedPipeline:
        """Communication-avoiding mesh pipeline over this app's layout.

        Decomposes the cfg.M cube onto ``mesh`` (cubic power-of-2 local
        blocks, Decomposition3D), threads ``cfg.substeps`` through as the
        exchange depth S (``substeps=0`` delegates (T, S) to the
        exchange-aware ``DistributedPipeline.plan``).
        """
        cfg = self.cfg
        procs = tuple(mesh.shape[a] for a in STENCIL_AXES)
        local = Decomposition3D(cfg.M, procs).check_local_pow2_cube()
        if cfg.substeps == 0:
            return DistributedPipeline.plan(mesh, cfg.ordering, local,
                                            g=cfg.g, bc=cfg.bc,
                                            use_kernel=cfg.use_kernel)
        T = min(cfg.block_T, local)
        return DistributedPipeline(mesh=mesh, spec=cfg.ordering, M=local,
                                   T=T, g=cfg.g, S=cfg.substeps, bc=cfg.bc,
                                   use_kernel=cfg.use_kernel)

    def run_distributed(self, mesh: jax.sharding.Mesh, n_steps: int) -> jnp.ndarray:
        """Shard the cube over the mesh, run K deep-exchange rounds, and
        gather back into this app's path-ordered state. Bit-identical to
        ``run`` on one device (same rule, f32 state)."""
        pipe = self.distributed_pipeline(mesh)
        cube = pipe.run_cube(self.cube, n_steps)
        self.state_path = jax.block_until_ready(
            apply_ordering(cube, self.cfg.ordering))
        return self.state_path

    def reference_run(self, n_steps: int) -> jnp.ndarray:
        """Ordering-independent oracle on the canonical cube (same bc)."""
        cube = self.cube
        for _ in range(n_steps):
            cube = kref.gol3d_step_ref(cube, self.cfg.g, bc=self.cfg.bc)
        return cube

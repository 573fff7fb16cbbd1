"""Fused resident-block-store stencil driver (DESIGN.md §3–§4, §9).

The paper's central claim is that SFC orderings pay off only when the
curve order *is* the storage order — reorder once, iterate many times
(§2, §4 of the paper). This driver enforces that discipline for the
stencil workloads:

    blockize once  →  K timesteps entirely in curve-ordered block form
                      (halo assembled in-kernel from the neighbour
                      tables, never materialised in HBM)
                   →  unblockize once.

The per-step state is exactly one ``(C, nb, T, T, T)`` block store — C
channels of M³ elements, one shared block permutation, no
``((T+2g)/T)³`` halo duplication (C=1 workloads keep the plain
``(nb, T, T, T)`` form) — and consecutive launches ping-pong between
two such stores: the K-step runner is jit'd with the input store
donated, so XLA aliases the output of launch k as the input of launch
k+1 (classic double buffering) instead of allocating per step.

Temporal blocking (DESIGN.md §4): with ``S`` substeps per launch the
kernel assembles a ``(T+2·S·g)³`` window per channel and runs S whole
tap-sum + update-rule substeps in VMEM before writing the C·T³ tiles
once — K timesteps become ``ceil(K/S)`` HBM round-trips. ``plan()``
autotunes (T, S) by minimising the modelled bytes/substep under the
VMEM budget, with every term carrying the rule's channel count.

The ``*_items_per_*`` helpers are the one HBM-traffic model that
``plan()`` ranks (T, S) by; their ``fields`` keyword is the ×C factor
of the multi-field store (DESIGN.md §9).

Both pipelines carry a boundary contract (``bc``, core.boundary —
DESIGN.md §8): clamped runs swap in the non-wrapping neighbour tables,
refresh ghost layers per substep, open the exchange rings (the clamped
keywords of the exchange-bytes helpers model the smaller surface), and
stay bit-identical (f32) between the S-deep and sequential forms
exactly like the periodic case. A per-axis ``MixedBoundary`` (clamped k,
periodic i/j, …) threads through identically: only its clamped axes
open.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.core.boundary import (PERIODIC, BoundarySpec, MixedBoundary,
                                 as_boundary, axes_periodic)
from repro.core.layout import (blockize, blockize_fields, unblockize,
                               unblockize_fields)
from repro.core.neighbors import boundary_face_table, neighbor_table
from repro.core.orderings import OrderingSpec
from repro.kernels import ref as kref
from repro.kernels import backend
from repro.kernels.ops import uniform_weights
from repro.kernels.rules import get_rule, tap_form
from repro.kernels.stencil3d import (LANES, VMEM_LIMIT_BYTES,
                                     fused_kernel_vmem_bytes,
                                     stencil_step_fused)

from .domain import STENCIL_AXES
from .halo import (from_store, shard_substeps, shard_state,
                   stencil_block_kind, to_store, unshard_state, _state_pspec)

__all__ = [
    "ResidentPipeline", "DistributedPipeline", "VMEM_BUDGET_BYTES",
    "fused_vmem_bytes", "fused_items_per_launch", "resident_bytes_per_step",
    "exchange_face_items", "exchange_items_per_exchange",
    "exchange_bytes_per_step", "distributed_bytes_per_step",
    "checkpoint_bytes_per_interval", "checkpoint_traffic_fraction",
]

# Conservative per-core VMEM working-set budget the autotuner plans
# against (real TPU cores have ~16 MiB; leave half for Pallas' pipeline
# buffers, metadata, and the scalar-prefetch tables).
VMEM_BUDGET_BYTES = 8 * 2 ** 20


@dataclass(frozen=True)
class ResidentPipeline:
    """Stencil updates over a persistent curve-ordered block store.

    M:          cube edge (power of 2)
    T:          block edge (T | M; S·g | T for the kernel path). None
                (default) lets the platform decide: the lane-dense
                min(M, 128) for the compiled TPU kernel, else 8
    g:          stencil radius
    kind:       block-grid curve — "morton" | "hilbert" | "row_major" |
                "column_major" (core.neighbors.block_kind_of maps an
                OrderingSpec here)
    S:          substeps fused into one kernel launch (temporal blocking)
    rule:       update rule registry key (kernels/rules.py). The rule's
                declared ``channels`` (C) selects the store form: C=1
                rules run the plain ``(nb, T³)`` store, multi-field
                rules (``wave``) the stacked ``(C, nb, T³)`` store
                (DESIGN.md §9) — same curve, same neighbour tables.
    bc:         boundary contract (core.boundary.BoundarySpec, a kind
                string, or a per-axis MixedBoundary): "periodic"
                (default, torus) | "dirichlet" | "neumann0". Clamped
                runs use the non-wrapping neighbour table (per axis for
                mixed contracts) and refresh ghost layers per substep —
                temporal blocking stays exactly as deep at domain edges
                (DESIGN.md §8).
    use_kernel: Pallas fused kernel vs the jnp oracle. None (default)
                lets the platform decide: the compiled kernel on a TPU,
                the oracle on the CPU (where a kernel runs interpreted)

    Every knob is a static (hashable) field: a pipeline instance is both
    the configuration and the jit cache key of its runners.
    """
    M: int
    T: int | None = None
    g: int = 1
    kind: str = "morton"
    use_kernel: bool | None = None
    S: int = 1
    rule: str = "gol"
    bc: BoundarySpec | MixedBoundary = PERIODIC

    def __post_init__(self):
        object.__setattr__(self, "bc", as_boundary(self.bc))
        _default_kernel(self)
        assert self.M % self.T == 0, (self.M, self.T)
        if not self._valid_S(self.S):
            raise ValueError(
                f"temporal blocking needs 1 <= S*g <= T and S*g | T, "
                f"got T={self.T}, g={self.g}, S={self.S}")

    def _valid_S(self, S: int) -> bool:
        h = S * self.g
        return S >= 1 and h <= self.T and self.T % h == 0

    @property
    def nt(self) -> int:
        return self.M // self.T

    @property
    def nb(self) -> int:
        return self.nt ** 3

    @property
    def channels(self) -> int:
        """C of the rule's store — the ×C factor of every byte model."""
        return get_rule(self.rule).channels

    @property
    def tap_form(self) -> str:
        """The tap sum every launch runs: ``"box"`` for the uniform
        weights every pipeline passes (rules.tap_form)."""
        return tap_form(uniform_weights(self.g))

    # -- autotuner ---------------------------------------------------------
    @classmethod
    def plan(cls, M: int, g: int = 1, kind: str = "morton",
             rule: str = "gol", n_steps: int = 10, *,
             bc: BoundarySpec | MixedBoundary | str = PERIODIC,
             vmem_limit: int = VMEM_BUDGET_BYTES, max_S: int = 8,
             use_kernel: bool | None = None,
             itemsize: int = 4) -> "ResidentPipeline":
        """Pick (T, S) minimising modelled HBM bytes/substep under VMEM.

        Searches power-of-two block edges T | M (with g | T) and substep
        counts S ≤ max_S (with S·g | T), keeps candidates whose fused
        working set fits ``vmem_limit``, and minimises
        ``resident_bytes_per_step(M, T, g, n_steps, S=S, fields=C)``.
        The cost is non-monotone in S at fixed T — window inflation
        (T+2·S·g)³/S eventually out-grows the S× amortisation — so this
        is a real search, not "largest S that fits". Ties break toward
        smaller windows. A multi-field rule scales both the stream and
        the VMEM working set by its C, so the same budget admits
        shallower windows (DESIGN.md §9). ``bc`` threads through to the
        pipeline unchanged: the single-device HBM stream is
        boundary-independent (clamped runs trade wrapped halo reads for
        in-window substitution, same window), so the plan itself does
        not shift. For the compiled TPU kernel only the lane-dense T is
        searched (``_plan_search``).
        """
        C = get_rule(rule).channels
        T, S = _plan_search(
            M, g, max_S, vmem_limit, itemsize,
            lambda T, S: resident_bytes_per_step(M, T, g, n_steps,
                                                 itemsize, S=S, fields=C),
            fields=C, compiled=_compiled(use_kernel))
        return cls(M=M, T=T, g=g, kind=kind, S=S, rule=rule, bc=bc,
                   use_kernel=use_kernel)

    # -- layout boundary (paid once per K-step run, not per step) ---------
    def to_blocks(self, cube: jnp.ndarray) -> jnp.ndarray:
        """Blockize the canonical state: an (M,M,M) cube for C=1 rules,
        stacked (C,M,M,M) fields for multi-field rules — one shared
        block permutation either way."""
        if cube.ndim == 3:
            return blockize(cube, self.T, kind=self.kind)
        return blockize_fields(cube, self.T, kind=self.kind)

    def to_cube(self, store: jnp.ndarray) -> jnp.ndarray:
        if store.ndim == 4:
            return unblockize(store, self.M, kind=self.kind)
        return unblockize_fields(store, self.M, kind=self.kind)

    # -- the resident step -------------------------------------------------
    def step_fn(self, substeps: int | None = None):
        """(store -> store): ``substeps`` (default S) fused updates.

        Kernel mode is one ``stencil_step_fused`` launch; oracle mode is
        the same math as sequential jnp substeps — bit-identical for f32
        stores (substeps accumulate in f32 on both paths). Clamped runs
        feed the non-wrapping neighbour table (per-axis for mixed
        contracts) plus the block boundary flags; the per-substep ghost
        refresh lives in the shared kernels/rules.apply_window_bc helper
        on both paths.
        """
        S = self.S if substeps is None else substeps
        assert self._valid_S(S), (self.T, self.g, S)
        g, bc, w = self.g, self.bc, uniform_weights(self.g)
        nbr = neighbor_table(self.kind, self.nt, periodic=axes_periodic(bc))
        bnd = boundary_face_table(self.kind, self.nt) if bc.clamped else None
        rule = get_rule(self.rule)
        use_kernel = self.use_kernel

        def step(store):
            if use_kernel:
                return stencil_step_fused(store, w, nbr, bnd, g=g, S=S,
                                          rule=rule.name, bc=bc)
            out = store
            for _ in range(S):
                out = kref.stencil_fused_ref(out, w, nbr, S=1,
                                             rule=rule, bc=bc, bnd=bnd)
            return out

        return step

    def run_fn(self, n_steps: int):
        """jit'd K-step runner: ceil(K/S) fused launches over the donated
        (double-buffered) store; a K % S remainder runs as one smaller
        fused launch when S·g-divisibility allows, else step by step."""
        full, rem = divmod(n_steps, self.S)
        step = self.step_fn()
        if rem and self._valid_S(rem):
            tail_steps, tail = 1, self.step_fn(rem)
        else:
            tail_steps, tail = rem, (self.step_fn(1) if rem else None)
        donate = (0,) if jax.default_backend() != "cpu" else ()

        @functools.partial(jax.jit, donate_argnums=donate)
        def run(store):
            if full:
                store = jax.lax.fori_loop(0, full, lambda _, s: step(s), store)
            if tail is not None:
                store = jax.lax.fori_loop(0, tail_steps,
                                          lambda _, s: tail(s), store)
            return store

        return run

    def run(self, cube: jnp.ndarray, n_steps: int) -> jnp.ndarray:
        """blockize once → n_steps fused curve-ordered updates → unblockize.

        ``cube`` is (M,M,M) for C=1 rules, stacked (C,M,M,M) for
        multi-field rules; the return matches.
        """
        store = self.to_blocks(cube)
        store = self.run_fn(n_steps)(store)
        return self.to_cube(store)

    # -- modelled HBM traffic --------------------------------------------
    def bytes_per_step(self, n_steps: int, itemsize: int = 4) -> float:
        return resident_bytes_per_step(self.M, self.T, self.g, n_steps,
                                       itemsize, S=self.S,
                                       fields=self.channels)

    def vmem_bytes(self, itemsize: int = 4) -> int:
        return fused_vmem_bytes(self.T, self.g, self.S, itemsize,
                                fields=self.channels)


def _plan_search(M: int, g: int, max_S: int, vmem_limit: int, itemsize: int,
                 cost_fn, fields: int = 1, compiled: bool = False
                 ) -> tuple[int, int]:
    """Enumerate valid power-of-two (T, S) under the VMEM budget and pick
    the ``cost_fn(T, S)``-cheapest pair (ties toward smaller windows) —
    the one search behind both the resident and the distributed plan.
    ``fields`` scales the modelled working set (multi-field stores keep
    C windows live).

    ``compiled`` (the fused kernel compiled for a TPU) searches only
    the lane-dense edge min(M, 128) — a smaller T pads every vreg's
    lanes and, at chip sizes, overflows the SMEM tables (DESIGN.md §4)
    — and holds S to what the kernel's own VMEM allocation
    (``fused_kernel_vmem_bytes``) fits in its scoped limit.
    """
    best = None
    edges = ([min(M, LANES)] if compiled
             else [1 << e for e in range(M.bit_length())])
    for T in edges:
        if M % T or T % g:
            continue
        S = 1
        while S <= max_S:
            h = S * g
            if h <= T and T % h == 0:
                if compiled:
                    vm = fused_kernel_vmem_bytes(
                        T, h, fields, itemsize, g=g,
                        form=tap_form(uniform_weights(g)))
                    fits = vm <= VMEM_LIMIT_BYTES
                else:
                    vm = fused_vmem_bytes(T, g, S, itemsize, fields=fields)
                    fits = vm <= vmem_limit
                if fits:
                    cost = cost_fn(T, S)
                    if best is None or (cost, vm) < best[0]:
                        best = ((cost, vm), T, S)
            S *= 2
    if best is None:
        raise ValueError(
            f"no (T, S) fits vmem_limit={vmem_limit} for M={M}, g={g}, "
            f"fields={fields}")
    return best[1], best[2]


def fused_vmem_bytes(T: int, g: int, S: int, itemsize: int = 4, *,
                     fields: int = 1) -> int:
    """Modelled VMEM working set of one fused-kernel grid step.

    Two window-sized live arrays per channel (the assembled window plus
    the tap/rule temporary), the C·T³ output tile double-buffered, and
    the tap weights (shared across channels).
    """
    W3 = (T + 2 * S * g) ** 3
    return itemsize * (fields * (2 * W3 + 2 * T ** 3) + (2 * g + 1) ** 3)


def _compiled(use_kernel: bool | None) -> bool:
    """True when the fused kernel will run compiled, i.e. on a TPU."""
    return use_kernel is not False and backend.on_tpu()


def _default_kernel(pipe, edge: str = "T") -> None:
    """Resolve the platform defaults of a pipeline (or Gol3dConfig).

    ``use_kernel=None``: the compiled kernel on a TPU, the jnp oracle on
    the CPU (the only backend where a kernel runs interpreted). A block
    edge (field ``edge``) of None: the lane-dense min(M, 128) for the
    compiled kernel, else 8.
    """
    if pipe.use_kernel is None:
        object.__setattr__(pipe, "use_kernel", backend.on_tpu())
    if getattr(pipe, edge) is None:
        T = min(pipe.M, LANES) if _compiled(pipe.use_kernel) else 8
        object.__setattr__(pipe, edge, T)


# ---------------------------------------------------------------------------
# HBM-traffic accounting — the model plan() ranks (T, S) by.
# ``*_items_per_*`` count array elements; ``*_bytes_per_step`` scale by
# itemsize and amortise the one-off layout boundary over the run. The
# ``fields`` keyword is the multi-field ×C factor (DESIGN.md §9): a
# C-channel store streams C windows in and C tiles out per block, packs C
# channels per exchanged face, and blockizes C cubes at the run boundary.
# ---------------------------------------------------------------------------

def fused_items_per_launch(M: int, T: int, g: int, S: int, *,
                           fields: int = 1) -> int:
    """HBM items of one fused launch: read C·(T+2·S·g)³ + write C·T³ per
    block — every channel streams its window and tile (DESIGN.md §9).

    No tap-sum array, no rule pass — S substeps ride one round-trip.
    """
    nb = (M // T) ** 3
    return fields * (nb * (T + 2 * S * g) ** 3 + nb * T ** 3)


def resident_bytes_per_step(M: int, T: int, g: int, n_steps: int,
                            itemsize: int = 4, *, S: int = 1,
                            fields: int = 1) -> float:
    """Modelled HBM bytes per timestep of the fused resident pipeline.

    The unit is one whole timestep of the workload (a "substep" of a
    fused launch is a full timestep; a multi-field timestep advances all
    C channels, hence the ×C stream). One launch
    advances S of them, so the per-launch stream amortises by S; the
    one-off blockize/unblockize (read C·M³ + write C·M³ each) amortises
    over the whole K-step run.
    """
    per_substep = fused_items_per_launch(M, T, g, S, fields=fields) / S
    return itemsize * (per_substep
                       + fields * _boundary_items(M) / max(n_steps, 1))


def _boundary_items(M: int) -> int:
    # blockize + unblockize: read M³ + write M³ each, once per run
    return 4 * M ** 3


def checkpoint_bytes_per_interval(M, *, fields: int = 1,
                                  itemsize: int = 4) -> int:
    """Bytes one checkpoint writes: the canonical (curve-independent)
    C-channel state of an M³ cube — or a non-cubic (Gk,Gi,Gj) box —
    once per interval (stencil/runner.CheckpointedRun, DESIGN.md §10).

    The snapshot is the *logical* state, so its size is ordering-, T-,
    S- and mesh-independent: exactly ``C · ∏(shape) · itemsize`` payload
    bytes (the npz container and manifest add O(KiB), not modelled).
    """
    gk, gi, gj = (M, M, M) if isinstance(M, int) else M
    return fields * gk * gi * gj * itemsize


def checkpoint_traffic_fraction(M: int, T: int, g: int, interval: int, *,
                                S: int = 1, fields: int = 1,
                                itemsize: int = 4) -> float:
    """Modelled fraction of per-interval data movement spent on the
    checkpoint: snapshot bytes (plus the unblockize read that produces
    the canonical state) over snapshot + the interval's fused HBM
    stream, by the same fused-launch model as the pipelines."""
    snap = checkpoint_bytes_per_interval(M, fields=fields, itemsize=itemsize) \
        + fields * M ** 3 * itemsize  # unblockize read of the store
    compute = interval * fused_items_per_launch(M, T, g, S, fields=fields) \
        / S * itemsize
    return snap / (snap + compute)


def exchange_face_items(M: int, g: int, S: int = 1) -> tuple[int, int, int]:
    """Per-axis items of ONE sent face at exchange depth h = S·g (single
    channel — the exchange helpers apply the ×C ``fields`` factor).

    Axis-sequential corner-correct extents (stencil/halo.exchange_shell):
    the k faces are bare h·M² slabs, the i faces carry the k-received
    edges (h·(M+2h)·M), the j faces both (h·(M+2h)²). These are exactly
    the packed slab shapes (core/surfaces.shell_slab_shapes) — asserted
    equal in tests — so the model *is* the wire format.
    """
    h = S * g
    e = M + 2 * h
    return (h * M * M, h * e * M, h * e * e)


def exchange_items_per_exchange(M: int, g: int, S: int = 1, *,
                                bc: BoundarySpec | MixedBoundary | str = PERIODIC,
                                procs: tuple[int, int, int] | None = None,
                                coords: tuple[int, int, int] | None = None,
                                fields: int = 1) -> float:
    """ICI items one shard moves per deep halo exchange (h = S·g).

    Periodic (default): every shard sends both faces on all three axes —
    ``C·2h·[M² + (M+2h)·M + (M+2h)²]`` items (C = ``fields``: every
    channel packs into the same messages, DESIGN.md §9). Deep halos
    therefore move *slightly more* bytes in total (the corner terms grow
    with h) — what S buys is S× fewer exchanges (latency/launch
    amortisation) and the fused kernel's HBM amortisation, the
    communication-avoiding trade.

    Clamped (``bc`` dirichlet/neumann0, or a per-axis mixed contract):
    clamped-axis rings are open, so a send happens only where a
    neighbour exists — pass the mesh shape ``procs`` and either a
    shard's mesh ``coords`` (that shard's exact items: each clamped axis
    contributes its face size once per existing neighbour, so mesh-edge
    shards move strictly fewer bytes than the periodic torus) or
    ``coords=None`` for the mesh-wide mean (``2(p-1)/p`` faces per
    clamped axis — the smaller exchange surface
    DistributedPipeline.plan() minimises). Periodic axes of a mixed
    contract keep the full 2-face volume.
    """
    sizes = exchange_face_items(M, g, S)
    periodic = axes_periodic(bc)
    total = 0.0
    for ax, sz in enumerate(sizes):
        if periodic[ax]:
            total += 2 * sz
            continue
        if procs is None:
            raise ValueError("clamped exchange accounting needs the mesh "
                             "shape (procs=(px, py, pz))")
        p = procs[ax]
        if coords is None:
            total += sz * 2 * (p - 1) / p
        else:
            total += sz * ((coords[ax] > 0) + (coords[ax] < p - 1))
    return fields * total


def exchange_bytes_per_step(M: int, g: int, S: int = 1, itemsize: int = 4, *,
                            bc: BoundarySpec | MixedBoundary | str = PERIODIC,
                            procs: tuple[int, int, int] | None = None,
                            coords: tuple[int, int, int] | None = None,
                            fields: int = 1) -> float:
    """Modelled ICI bytes per *timestep*: one width-S·g exchange funds S
    (clamped/mixed keyword accounting as in exchange_items_per_exchange;
    ``fields`` is the multi-field ×C factor)."""
    items = exchange_items_per_exchange(M, g, S, bc=bc, procs=procs,
                                        coords=coords, fields=fields)
    return itemsize * items / S


def distributed_bytes_per_step(M: int, T: int, g: int, n_steps: int,
                               itemsize: int = 4, *, S: int = 1,
                               bc: BoundarySpec | MixedBoundary | str = PERIODIC,
                               procs: tuple[int, int, int] | None = None,
                               coords: tuple[int, int, int] | None = None,
                               fields: int = 1) -> float:
    """Total modelled data movement per timestep of one mesh shard:
    HBM (fused resident model) + ICI (deep-exchange model) — the number
    DistributedPipeline.plan() minimises, with both terms carrying the
    multi-field ×C ``fields`` factor. The HBM term is boundary-independent;
    the ICI term shrinks on clamped meshes (edge shards skip faces)."""
    return (resident_bytes_per_step(M, T, g, n_steps, itemsize, S=S,
                                    fields=fields)
            + exchange_bytes_per_step(M, g, S, itemsize, bc=bc, procs=procs,
                                      coords=coords, fields=fields))


# ---------------------------------------------------------------------------
# Communication-avoiding distributed pipeline (DESIGN.md §7)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DistributedPipeline:
    """K-step distributed stencil over a mesh of resident block stores.

    The communication-avoiding composition of the resident store and the
    fused kernel with the halo exchange: every shard keeps its local state
    as the curve-ordered ``(nb, T, T, T)`` block store — stacked
    ``(C, nb, T, T, T)`` for a multi-field rule (DESIGN.md §9) — for the
    whole K-step loop (one permutation gather in, one out — never per
    step), packs *deep* width-S·g faces of every channel straight from
    that store by static slices of its face blocks, and advances S whole
    timesteps per exchange through the fused kernel path
    (halo.shard_substeps). Bit-identical (f32) to S sequential
    :func:`repro.stencil.halo.make_distributed_step` steps.

    mesh:  3D device mesh over STENCIL_AXES (domain.make_stencil_mesh)
    spec:  element ordering of the public sharded state (shard_state)
    M:     local shard edge (power of 2); T: block edge (T | M, S·g | T;
           None picks as ResidentPipeline does)
    g:     stencil radius; S: substeps per exchange; rule: rules.py key
           (its ``channels`` selects the C of the store and state layout)
    bc:    boundary contract (core.boundary): "periodic" (torus wrap,
           default) | "dirichlet" | "neumann0" | a per-axis
           ``MixedBoundary``. Clamped runs open the exchange rings on
           their clamped axes (mesh-edge shards move no bytes across
           domain faces; their shell blocks carry boundary values
           instead) and refresh ghost layers per substep — S-deep rounds
           stay bit-identical (f32) to S sequential clamped steps
           (DESIGN.md §8).
    """
    mesh: jax.sharding.Mesh = field(compare=False)
    spec: OrderingSpec = field(default=None)  # type: ignore[assignment]
    M: int = 16
    T: int | None = None
    g: int = 1
    S: int = 1
    rule: str = "gol"
    use_kernel: bool | None = None
    bc: BoundarySpec | MixedBoundary = PERIODIC

    def __post_init__(self):
        object.__setattr__(self, "bc", as_boundary(self.bc))
        _default_kernel(self)
        assert self.spec is not None, "DistributedPipeline needs an OrderingSpec"
        assert self.M % self.T == 0, (self.M, self.T)
        if not self._valid_S(self.S):
            raise ValueError(
                f"distributed temporal blocking needs 1 <= S*g <= T and "
                f"S*g | T, got T={self.T}, g={self.g}, S={self.S}")

    _valid_S = ResidentPipeline._valid_S

    @property
    def kind(self) -> str:
        return stencil_block_kind(self.spec)

    @property
    def channels(self) -> int:
        return get_rule(self.rule).channels

    tap_form = ResidentPipeline.tap_form

    @property
    def procs(self) -> tuple[int, int, int]:
        return tuple(self.mesh.shape[a] for a in STENCIL_AXES)

    @property
    def global_shape(self) -> tuple[int, int, int]:
        """Per-axis global extents: the mesh may be non-cubic (4×2×1 …,
        DESIGN.md §10) as long as every *local* shard is a cubic
        power-of-2 block."""
        px, py, pz = self.procs
        return (px * self.M, py * self.M, pz * self.M)

    @property
    def global_M(self) -> int:
        px, py, pz = self.procs
        assert px == py == pz, self.procs
        return px * self.M

    # -- autotuner ---------------------------------------------------------
    @classmethod
    def plan(cls, mesh, spec: OrderingSpec, M: int, g: int = 1,
             rule: str = "gol", n_steps: int = 10, *,
             bc: BoundarySpec | MixedBoundary | str = PERIODIC,
             vmem_limit: int = VMEM_BUDGET_BYTES, max_S: int = 8,
             use_kernel: bool | None = None,
             itemsize: int = 4) -> "DistributedPipeline":
        """Pick (T, S) minimising modelled HBM **plus ICI** bytes/step.

        Same enumeration as ResidentPipeline.plan, but the cost now
        carries the exchange term: S trades window inflation against
        both HBM amortisation and exchange frequency (the corner terms
        of a deep exchange grow with S·g), so the optimum can shift
        versus the single-device plan. Both terms carry the rule's ×C
        channel factor. Clamped ``bc`` shrinks the exchange term to the
        mesh-wide mean surface (edge shards skip faces on open rings),
        computed for this mesh's shape; a mixed contract shrinks only
        its clamped axes.
        """
        procs = tuple(mesh.shape[a] for a in STENCIL_AXES)
        C = get_rule(rule).channels
        T, S = _plan_search(
            M, g, max_S, vmem_limit, itemsize,
            lambda T, S: distributed_bytes_per_step(M, T, g, n_steps,
                                                    itemsize, S=S, bc=bc,
                                                    procs=procs, fields=C),
            fields=C, compiled=_compiled(use_kernel))
        return cls(mesh=mesh, spec=spec, M=M, T=T, g=g, S=S, rule=rule,
                   bc=bc, use_kernel=use_kernel)

    # -- the K-step runner -------------------------------------------------
    def run_fn(self, n_steps: int):
        """jit'd (px,py,pz,[C,]M³) -> same: ceil(K/S) exchange+compute
        rounds.

        A K % S remainder runs as one shallower round when S·g-divisibility
        allows, else step by step — mirroring ResidentPipeline.run_fn.
        """
        full, rem = divmod(n_steps, self.S)
        if rem and not self._valid_S(rem):
            tail_rounds, tail_S = rem, 1
        else:
            tail_rounds, tail_S = (1, rem) if rem else (0, 0)
        pspec = _state_pspec(self.channels)
        spec, kind, M, T = self.spec, self.kind, self.M, self.T
        round_kw = dict(kind=kind, M=M, g=self.g, rule=self.rule, bc=self.bc,
                        use_kernel=self.use_kernel)

        def local_run(state_path):  # (1,1,1,[C,]M³) per device
            store = to_store(state_path, spec, kind, T, M)
            if full:
                store = jax.lax.fori_loop(
                    0, full,
                    lambda _, st: shard_substeps(st, S=self.S, **round_kw),
                    store)
            if tail_rounds:
                store = jax.lax.fori_loop(
                    0, tail_rounds,
                    lambda _, st: shard_substeps(st, S=tail_S, **round_kw),
                    store)
            return from_store(store, spec, kind, T, M)

        # check_vma=False: pallas_call has no shard_map replication rule
        return jax.jit(jax.shard_map(local_run, mesh=self.mesh,
                                     in_specs=pspec, out_specs=pspec,
                                     check_vma=False))

    def run(self, state: jnp.ndarray, n_steps: int) -> jnp.ndarray:
        """Advance a (px,py,pz,[C,]M³) sharded path-ordered state K steps."""
        return self.run_fn(n_steps)(state)

    def run_cube(self, cube: jnp.ndarray, n_steps: int) -> jnp.ndarray:
        """Convenience: shard a canonical global state — (Gk,Gi,Gj), or
        stacked (C,Gk,Gi,Gj) fields for a multi-field rule — run, gather
        back. Non-cubic meshes decompose a non-cubic global box into
        cubic M³ shards (DESIGN.md §10)."""
        st = shard_state(cube, self.spec, self.procs)
        st = self.run(st, n_steps)
        return unshard_state(st, self.spec, self.global_shape)

    # -- modelled traffic --------------------------------------------------
    def bytes_per_step(self, n_steps: int, itemsize: int = 4,
                       coords: tuple[int, int, int] | None = None) -> float:
        """HBM + ICI bytes per timestep: the mesh-wide mean shard by
        default, or the shard at mesh ``coords`` (clamped runs only
        differ per shard — edge shards skip faces)."""
        return distributed_bytes_per_step(self.M, self.T, self.g, n_steps,
                                          itemsize, S=self.S, bc=self.bc,
                                          procs=self.procs, coords=coords,
                                          fields=self.channels)

    def exchange_bytes_per_step(self, itemsize: int = 4,
                                coords: tuple[int, int, int] | None = None
                                ) -> float:
        return exchange_bytes_per_step(self.M, self.g, self.S, itemsize,
                                       bc=self.bc, procs=self.procs,
                                       coords=coords, fields=self.channels)

    def vmem_bytes(self, itemsize: int = 4) -> int:
        return fused_vmem_bytes(self.T, self.g, self.S, itemsize,
                                fields=self.channels)

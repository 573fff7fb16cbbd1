"""Physical boundary conditions end-to-end (DESIGN.md §8).

Coverage layers, mirroring the periodic suites:

- contract + table units: BoundarySpec parsing, pad_cube vs np.pad,
  boundary_face_table flag counts (faces/edges/corners), the shared
  in-window ghost refresh (kernels/rules.apply_window_bc) against the
  padded-cube corner semantics;
- resident matrix: clamped ResidentPipeline — kernel and oracle, fused
  S-deep vs sequential bit-identity, gol exact against the clamped
  global oracle — including the M == T single-block grid where every
  face of the only block is clamped;
- exchange: open-ring ppermute partner lists, the clamped bytes model
  (edge shards strictly fewer bytes; extents == packed slab shapes),
  exchange_shell on a 1×1×1 mesh against pad_cube (no ppermute pairs at
  all on a clamped single-shard mesh — asserted on the jaxpr);
- the ≥8-device clamped acceptance matrix: DistributedPipeline S-deep
  vs S sequential clamped make_distributed_step, all four orderings ×
  {gol, jacobi}, plus the no-wrap-traffic jaxpr assert — in-process on
  the multi-device CI job, subprocess under tier-1.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (COLUMN_MAJOR, HILBERT, MORTON, NEUMANN0, PERIODIC,
                        ROW_MAJOR, BoundarySpec, MixedBoundary, apply_ordering,
                        as_boundary, axes_periodic, blockize,
                        boundary_face_table, dirichlet, mixed, pad_cube,
                        unblockize)
from repro.core.neighbors import neighbor_table, ring_perms
from repro.kernels import ref as kref
from repro.kernels.ops import uniform_weights
from repro.kernels.rules import apply_window_bc
from repro.kernels.stencil3d import stencil_step_fused
from repro.stencil import (DistributedPipeline, Gol3d, Gol3dConfig,
                           ResidentPipeline, distributed_bytes_per_step,
                           exchange_bytes_per_step, exchange_face_items,
                           exchange_items_per_exchange, make_stencil_mesh,
                           resident_bytes_per_step)
from repro.stencil.halo import exchange_shell, shard_substeps

rng = np.random.default_rng(23)

ORDERINGS = (ROW_MAJOR, COLUMN_MAJOR, MORTON, HILBERT)
CLAMPED = (NEUMANN0, dirichlet(0.0))


def _cube(M, rule="gol"):
    if rule == "gol":
        return (rng.random((M, M, M)) < 0.3).astype(np.float32)
    return rng.normal(size=(M, M, M)).astype(np.float32)


def _oracle_run(cube, g, bc, steps):
    want = jnp.asarray(cube)
    for _ in range(steps):
        want = kref.gol3d_step_ref(want, g, bc=bc)
    return np.asarray(want)


# ------------------------------------------------------------- contract units
def test_boundary_spec_contract():
    assert as_boundary("periodic") == PERIODIC and not PERIODIC.clamped
    assert as_boundary("neumann0") == NEUMANN0 and NEUMANN0.clamped
    assert as_boundary(NEUMANN0) is NEUMANN0
    d = dirichlet(1.5)
    assert d.clamped and d.value == 1.5
    assert hash(d) == hash(BoundarySpec("dirichlet", 1.5))  # jit-static key
    with pytest.raises(ValueError):
        BoundarySpec("reflect")


def test_pad_cube_matches_numpy_pad():
    c = _cube(4, "jacobi")
    np.testing.assert_array_equal(np.asarray(pad_cube(jnp.asarray(c), 2, PERIODIC)),
                                  np.pad(c, 2, mode="wrap"))
    np.testing.assert_array_equal(np.asarray(pad_cube(jnp.asarray(c), 2, NEUMANN0)),
                                  np.pad(c, 2, mode="edge"))
    np.testing.assert_array_equal(
        np.asarray(pad_cube(jnp.asarray(c), 1, dirichlet(3.0))),
        np.pad(c, 1, constant_values=3.0))


def test_boundary_face_table_flag_counts():
    """Blocks adjacent to 0/1/2/3 clamped faces: interior, face, edge,
    corner — the multi-clamped-face population the refresh must handle."""
    nt = 4
    tab = boundary_face_table("hilbert", nt)
    assert tab.shape == (nt ** 3, 6)
    nflags = tab.sum(axis=1)
    assert (nflags == 0).sum() == (nt - 2) ** 3          # interior
    assert (nflags == 1).sum() == 6 * (nt - 2) ** 2      # face blocks
    assert (nflags == 2).sum() == 12 * (nt - 2)          # edge blocks
    assert (nflags == 3).sum() == 8                      # corner blocks
    # single-block grid: the one block owns all six domain faces
    np.testing.assert_array_equal(boundary_face_table("morton", 1),
                                  np.ones((1, 6), np.int32))
    # opposite columns never both set for nt >= 2
    assert not ((tab[:, 0] & tab[:, 1]).any())


@pytest.mark.parametrize("bc", CLAMPED, ids=lambda b: b.kind)
def test_apply_window_bc_matches_pad(bc):
    """Refreshing a fully-flagged scrambled window reproduces pad_cube —
    including the per-axis-sequential corner composition."""
    T, h = 4, 2
    core = _cube(T, "jacobi")
    want = np.asarray(pad_cube(jnp.asarray(core), h, bc))
    scr = want.copy()
    scr[:h], scr[-h:] = 9.0, 9.0                    # poison every ghost site
    scr[:, :h], scr[:, -h:] = 9.0, 9.0
    scr[:, :, :h], scr[:, :, -h:] = 9.0, 9.0
    flags = np.ones((1, 6), np.int32)
    got = apply_window_bc(jnp.asarray(scr)[None], flags, h, bc)
    np.testing.assert_array_equal(np.asarray(got)[0], want)
    # partially flagged: only the k-lo ghost refreshes (over the spans
    # the other faces would deliver by exchange); everything else —
    # including the k-hi ghost — keeps its existing content
    flags = np.array([[1, 0, 0, 0, 0, 0]], np.int32)
    got = np.asarray(apply_window_bc(jnp.asarray(scr)[None], flags, h, bc))[0]
    np.testing.assert_array_equal(got[:h, h:-h, h:-h], want[:h, h:-h, h:-h])
    np.testing.assert_array_equal(got[-h:], scr[-h:])    # k-hi untouched
    np.testing.assert_array_equal(got[h:-h], scr[h:-h])  # interior untouched


# ----------------------------------------------------------- resident matrix
@pytest.mark.parametrize("kind", ["morton", "hilbert"])
@pytest.mark.parametrize("rule", ["gol", "jacobi"])
@pytest.mark.parametrize("bc", CLAMPED, ids=lambda b: b.kind)
def test_resident_clamped_fused_matches_sequential(kind, rule, bc):
    """Clamped fused S=4 (kernel) == 4 sequential S=1 steps (kernel and
    oracle families), and gol == the clamped padded-cube global oracle."""
    M, T, g, S = 16, 8, 1, 4
    cube = _cube(M, rule)
    deep = ResidentPipeline(M=M, T=T, g=g, kind=kind, S=S, rule=rule, bc=bc,
                            use_kernel=True)
    seq = ResidentPipeline(M=M, T=T, g=g, kind=kind, S=1, rule=rule, bc=bc,
                           use_kernel=True)
    a = np.asarray(deep.run(jnp.asarray(cube), S))
    np.testing.assert_array_equal(a, np.asarray(seq.run(jnp.asarray(cube), S)))
    ora = ResidentPipeline(M=M, T=T, g=g, kind=kind, S=S, rule=rule, bc=bc)
    b = np.asarray(ora.run(jnp.asarray(cube), S))
    if rule == "gol":  # integer-valued sums: exact across families
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(b, _oracle_run(cube, g, bc, S))
    else:
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("S", [1, 2, 4, 8])
def test_single_block_grid_clamped(S):
    """M == T: the store is one block with all six faces clamped — the
    deepest temporal blocking the kernel admits still matches the
    oracle (acceptance: M==T single-block grids)."""
    M = T = 8
    g = 1
    cube = _cube(M)
    for bc in CLAMPED:
        pipe = ResidentPipeline(M=M, T=T, g=g, kind="morton", S=S, bc=bc,
                                use_kernel=True)
        got = np.asarray(pipe.run(jnp.asarray(cube), S))
        np.testing.assert_array_equal(got, _oracle_run(cube, g, bc, S),
                                      err_msg=f"{bc.kind} S={S}")


def test_multi_clamped_face_blocks_against_oracle():
    """nt=4 grid (face/edge/corner/interior block mix) under neumann0:
    blocks adjacent to ≥2 clamped faces refresh both axes correctly."""
    M, T, g, S = 32, 8, 1, 2
    cube = _cube(M)
    pipe = ResidentPipeline(M=M, T=T, g=g, kind="hilbert", S=S, bc=NEUMANN0)
    got = np.asarray(pipe.run(jnp.asarray(cube), 2 * S))
    np.testing.assert_array_equal(got, _oracle_run(cube, g, NEUMANN0, 2 * S))


def test_fused_kernel_requires_flags_when_clamped():
    store = jnp.zeros((8, 8, 8, 8), jnp.float32)
    nbr = neighbor_table("morton", 2, periodic=False)
    with pytest.raises(ValueError):
        stencil_step_fused(store, uniform_weights(1), nbr, None,
                           g=1, S=1, rule="gol", bc=NEUMANN0)


def test_gol3d_config_threads_bc():
    """The app-level knob: the resident and reference runs agree under a
    clamped config (string form accepted)."""
    app = Gol3d(Gol3dConfig(M=16, g=1, ordering=MORTON, block_T=8,
                            substeps=2, bc="neumann0"))
    assert app.cfg.bc == NEUMANN0
    want = np.asarray(app.reference_run(2))
    app.run(2)
    np.testing.assert_array_equal(np.asarray(app.cube), want)


# ------------------------------------------------- exchange: rings and model
def test_ring_perms_open_rings_have_no_wrap_pairs():
    fwd, bwd = ring_perms(4, periodic=False)
    assert fwd == [(0, 1), (1, 2), (2, 3)] and bwd == [(1, 0), (2, 1), (3, 2)]
    assert ring_perms(1, periodic=False) == ([], [])
    # periodic keeps the wrap links (and the legacy pair order)
    fwd_p, bwd_p = ring_perms(4)
    assert (3, 0) in fwd_p and (0, 3) in bwd_p


def test_clamped_exchange_model():
    """Acceptance: clamped exchange bytes match packed extents exactly,
    and edge shards exchange strictly fewer bytes than periodic."""
    from repro.core.surfaces import shell_slab_shapes

    M, g, S = 16, 1, 4
    h = S * g
    sizes = exchange_face_items(M, g, S)
    shp = shell_slab_shapes(M, h)
    # the model's per-face extents ARE the packed slab shapes
    assert sizes == tuple(int(np.prod(s)) for s in (shp[0], shp[2], shp[4]))
    per = exchange_items_per_exchange(M, g, S)
    assert per == 2 * sum(sizes)
    procs = (2, 2, 2)
    corner = exchange_items_per_exchange(M, g, S, bc=NEUMANN0, procs=procs,
                                         coords=(0, 0, 0))
    assert corner == sum(sizes)          # one neighbour per axis
    assert corner < per                  # strictly fewer than periodic
    # interior shard of a 4³ mesh: both neighbours exist -> periodic volume
    interior = exchange_items_per_exchange(M, g, S, bc=NEUMANN0,
                                           procs=(4, 4, 4), coords=(1, 2, 1))
    assert interior == per
    # mesh mean: 2(p-1)/p faces per axis, equals the coords average
    mean = exchange_items_per_exchange(M, g, S, bc=NEUMANN0, procs=procs)
    allc = [exchange_items_per_exchange(M, g, S, bc=NEUMANN0, procs=procs,
                                        coords=(a, b, c))
            for a in range(2) for b in range(2) for c in range(2)]
    assert mean == pytest.approx(sum(allc) / len(allc))
    assert mean < per
    # bytes-per-step and the distributed total decompose consistently
    assert exchange_bytes_per_step(M, g, S, bc=NEUMANN0, procs=procs) \
        == pytest.approx(4 * mean / S)
    assert distributed_bytes_per_step(M, 8, g, 10, S=S, bc=NEUMANN0,
                                      procs=procs) == pytest.approx(
        resident_bytes_per_step(M, 8, g, 10, S=S) + 4 * mean / S)
    with pytest.raises(ValueError):
        exchange_items_per_exchange(M, g, S, bc=NEUMANN0)  # needs procs


def test_clamped_plan_minimises_joint_cost():
    """plan(bc=clamped) optimises against the smaller exchange surface
    and never exceeds an enumerable candidate."""
    mesh = make_stencil_mesh((1, 1, 1))
    pipe = DistributedPipeline.plan(mesh, HILBERT, 16, g=1, bc=NEUMANN0,
                                    vmem_limit=256 * 1024)
    assert pipe.bc == NEUMANN0
    best = pipe.bytes_per_step(10)
    T = 1
    while T <= 16:
        if 16 % T == 0:
            S = 1
            while S <= 8:
                if S <= T and T % S == 0:
                    from repro.stencil import fused_vmem_bytes
                    if fused_vmem_bytes(T, 1, S) <= 256 * 1024:
                        assert best <= distributed_bytes_per_step(
                            16, T, 1, 10, S=S, bc=NEUMANN0, procs=pipe.procs)
                S *= 2
        T *= 2
    # per-shard view: the corner shard of a real mesh models fewer ICI
    # bytes than the periodic torus, the mean sits between
    p222 = DistributedPipeline(mesh=mesh, spec=HILBERT, M=16, T=8, g=1, S=2,
                               bc=NEUMANN0)
    per = exchange_bytes_per_step(16, 1, 2)
    assert p222.exchange_bytes_per_step(coords=(0, 0, 0)) < per


BCS = {"periodic": PERIODIC, "dirichlet": dirichlet(0.0),
       "neumann0": NEUMANN0, "mixed": mixed(i="neumann0")}


@pytest.mark.parametrize("C", [1, 2])
@pytest.mark.parametrize("bc", sorted(BCS))
def test_clamped_benchmark_rows_share_accounting(bc, C):
    """The per-shard and mesh-mean exchange surfaces of one contract:
    each clamped axis sends one face per neighbour that exists, each
    periodic axis two; the mean is the average over the mesh; the mesh
    model's HBM term does not depend on the boundary."""
    M_, T_, g, S, K, procs = 32, 8, 1, 4, 10, (2, 2, 2)
    bcs = BCS[bc]
    sizes = exchange_face_items(M_, g, S)
    periodic = axes_periodic(bcs)
    torus = exchange_items_per_exchange(M_, g, S, fields=C)
    shards = {}
    for coords in [(a, b, c) for a in range(2) for b in range(2)
                   for c in range(2)]:
        items = exchange_items_per_exchange(M_, g, S, bc=bcs, procs=procs,
                                            coords=coords, fields=C)
        faces = [2 if periodic[ax] else
                 (coords[ax] > 0) + (coords[ax] < procs[ax] - 1)
                 for ax in range(3)]
        assert items == C * sum(f * sz for f, sz in zip(faces, sizes))
        assert items <= torus
        shards[coords] = items
    mean = exchange_items_per_exchange(M_, g, S, bc=bcs, procs=procs,
                                       fields=C)
    assert mean == pytest.approx(sum(shards.values()) / len(shards))
    assert (mean < torus) == bcs.clamped
    assert distributed_bytes_per_step(
        M_, T_, g, K, S=S, bc=bcs, procs=procs, fields=C) == pytest.approx(
        resident_bytes_per_step(M_, T_, g, K, S=S, fields=C) + 4 * mean / S)


# ----------------------------------------- exchange semantics (1×1×1 mesh)
def _collect_ppermute_perms(jaxpr):
    """All ppermute partner lists anywhere in a (closed) jaxpr."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "ppermute":
            out.append(tuple(eqn.params["perm"]))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    out += _collect_ppermute_perms(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    out += _collect_ppermute_perms(sub)
    return out


@pytest.mark.parametrize("bc", CLAMPED, ids=lambda b: b.kind)
def test_exchange_shell_clamped_single_shard_matches_pad(bc):
    """On a 1×1×1 clamped mesh every ring is empty — zero ppermute pairs
    in the jaxpr — and the six slabs must equal the pad_cube ghost."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    M, T, h = 16, 8, 2
    mesh = make_stencil_mesh((1, 1, 1))
    cube = _cube(M, "jacobi")
    store = blockize(jnp.asarray(cube), T, kind="hilbert")
    fn = jax.jit(shard_map(
        lambda st: exchange_shell(st.reshape(-1), "hilbert", M, T, h, bc=bc),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    perms = [p for p in _collect_ppermute_perms(jax.make_jaxpr(fn)(store).jaxpr)
             if p]
    assert perms == []  # clamped single-shard mesh: no pairs anywhere
    k_lo, k_hi, i_lo, i_hi, j_lo, j_hi = map(np.asarray, fn(store))
    xp = np.asarray(pad_cube(jnp.asarray(cube), h, bc))
    e = M + 2 * h
    np.testing.assert_array_equal(k_lo, xp[:h, h:h + M, h:h + M])
    np.testing.assert_array_equal(k_hi, xp[e - h:, h:h + M, h:h + M])
    np.testing.assert_array_equal(i_lo, xp[:, :h, h:h + M])
    np.testing.assert_array_equal(i_hi, xp[:, e - h:, h:h + M])
    np.testing.assert_array_equal(j_lo, xp[:, :, :h])
    np.testing.assert_array_equal(j_hi, xp[:, :, e - h:])


@pytest.mark.parametrize("use_kernel", [False, True])
def test_shard_substeps_clamped_single_shard_matches_oracle(use_kernel):
    """One clamped deep round on a 1×1×1 mesh == S clamped oracle steps."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    M, T, g, S = 16, 8, 1, 4
    mesh = make_stencil_mesh((1, 1, 1))
    for bc in CLAMPED:
        cube = _cube(M)
        store = blockize(jnp.asarray(cube), T, kind="morton")
        fn = jax.jit(shard_map(
            lambda st: shard_substeps(st, kind="morton", M=M, g=g, S=S,
                                      bc=bc, use_kernel=use_kernel),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
        got = np.asarray(unblockize(fn(store), M, kind="morton"))
        np.testing.assert_array_equal(got, _oracle_run(cube, g, bc, S),
                                      err_msg=bc.kind)


# --------------------------------------------- per-face mixed contracts (§8)
def test_mixed_boundary_contract():
    """mixed() coerces strings per axis, collapses uniform triples, and
    exposes the shared per-axis view every consumer reads."""
    duct = mixed(k="neumann0")
    assert isinstance(duct, MixedBoundary) and duct.kind == "mixed"
    assert duct.clamped and [a.kind for a in duct.axes] == \
        ["neumann0", "periodic", "periodic"]
    assert axes_periodic(duct) == (False, True, True)
    assert mixed(k=NEUMANN0, i=NEUMANN0, j=NEUMANN0) == NEUMANN0  # collapse
    assert mixed() == PERIODIC
    assert as_boundary(duct) is duct
    assert axes_periodic(PERIODIC) == (True, True, True)
    assert axes_periodic(NEUMANN0) == (False, False, False)
    assert PERIODIC.axes == (PERIODIC,) * 3  # uniform specs self-expose
    assert hash(duct) == hash(mixed(k="neumann0"))  # jit-static key
    with pytest.raises(ValueError):
        MixedBoundary("neumann0", PERIODIC, PERIODIC)  # specs, not strings


def test_mixed_pad_cube_per_axis():
    """pad_cube under a mixed contract pads each axis under its own spec
    in k,i,j order — wrap on periodic axes includes clamped ghosts."""
    c = _cube(4, "jacobi")
    duct = mixed(k=dirichlet(2.0))
    got = np.asarray(pad_cube(jnp.asarray(c), 1, duct))
    want = np.pad(c, [(1, 1), (0, 0), (0, 0)], constant_values=2.0)
    want = np.pad(want, [(0, 0), (1, 1), (1, 1)], mode="wrap")
    np.testing.assert_array_equal(got, want)


def test_mixed_neighbor_table_per_axis():
    """The block table wraps on periodic axes and clamps on clamped ones
    — per axis, from one periodic=(…) knob."""
    from repro.core.neighbors import neighbor_table

    nt = 4
    per = neighbor_table("row_major", nt, periodic=True)
    cla = neighbor_table("row_major", nt, periodic=False)
    mix = neighbor_table("row_major", nt, periodic=(False, True, True))
    # row_major path position == linear block id, so rows index directly
    np.testing.assert_array_equal(mix[:, 13], per[:, 13])
    # a k-edge, i/j-interior block: k-offsets clamp, i/j offsets wrap
    k_lo_col = 4       # offset (-1, 0, 0): column 0*9 + 1*3 + 1
    blk = 0 * nt * nt + 2 * nt + 2   # (k=0, i=2, j=2)
    assert mix[blk, k_lo_col] == cla[blk, k_lo_col] != per[blk, k_lo_col]
    j_lo_col = 12      # offset (0, 0, -1): column 1*9 + 1*3 + 0
    blk_j = 2 * nt * nt + 2 * nt + 0  # (k=2, i=2, j=0): j wraps under mix
    assert mix[blk_j, j_lo_col] == per[blk_j, j_lo_col] \
        != cla[blk_j, j_lo_col]
    assert not np.array_equal(mix, per)


@pytest.mark.parametrize("kind", ["morton", "hilbert"])
def test_resident_mixed_matches_oracle(kind):
    """Acceptance: clamped k + periodic i/j through the fused resident
    pipeline (kernel and oracle) == the per-axis padded-cube oracle,
    bit-identical, S-deep."""
    M, T, g, S = 16, 8, 1, 4
    duct = mixed(k=NEUMANN0)
    cube = _cube(M)
    deep = ResidentPipeline(M=M, T=T, g=g, kind=kind, S=S, bc=duct,
                            use_kernel=True)
    seq = ResidentPipeline(M=M, T=T, g=g, kind=kind, S=1, bc=duct)
    a = np.asarray(deep.run(jnp.asarray(cube), S))
    np.testing.assert_array_equal(a, np.asarray(seq.run(jnp.asarray(cube), S)))
    np.testing.assert_array_equal(a, _oracle_run(cube, g, duct, S))


def test_mixed_exchange_model_per_axis():
    """Only the clamped axis shrinks: periodic axes keep the full 2-face
    volume, the clamped axis counts existing neighbours."""
    M, g, S = 16, 1, 4
    sizes = exchange_face_items(M, g, S)
    duct = mixed(k=NEUMANN0)
    per = exchange_items_per_exchange(M, g, S)
    corner = exchange_items_per_exchange(M, g, S, bc=duct, procs=(2, 2, 2),
                                         coords=(0, 0, 0))
    # k contributes 1 face (one neighbour), i/j the full 2 faces each
    assert corner == sizes[0] + 2 * sizes[1] + 2 * sizes[2]
    assert corner < per
    mean = exchange_items_per_exchange(M, g, S, bc=duct, procs=(2, 2, 2))
    assert mean == sizes[0] * 2 * (2 - 1) / 2 + 2 * sizes[1] + 2 * sizes[2]
    # a fully periodic mixed spec never needs procs
    assert exchange_items_per_exchange(M, g, S, bc=mixed()) == per
    with pytest.raises(ValueError):
        exchange_items_per_exchange(M, g, S, bc=duct)  # clamped k needs procs


@pytest.mark.parametrize("use_kernel", [False, True])
def test_shard_substeps_mixed_single_shard_matches_oracle(use_kernel):
    """One mixed deep round on a 1×1×1 mesh == S mixed oracle steps, and
    the jaxpr carries ppermute pairs for the periodic axes only."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    M, T, g, S = 16, 8, 1, 4
    duct = mixed(k=NEUMANN0)
    mesh = make_stencil_mesh((1, 1, 1))
    cube = _cube(M)
    store = blockize(jnp.asarray(cube), T, kind="hilbert")
    fn = jax.jit(shard_map(
        lambda st: shard_substeps(st, kind="hilbert", M=M, g=g, S=S,
                                  bc=duct, use_kernel=use_kernel),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    got = np.asarray(unblockize(fn(store), M, kind="hilbert"))
    np.testing.assert_array_equal(got, _oracle_run(cube, g, duct, S))
    # structural: the clamped k ring is empty, the periodic i/j rings
    # keep their (self-send) pairs — ppermute pairs on periodic axes only
    perms = [p for p in
             _collect_ppermute_perms(jax.make_jaxpr(fn)(store).jaxpr) if p]
    assert len(perms) == 4  # 2 ppermutes × 2 periodic axes; k's are empty


# --------------------------------------- clamped acceptance matrix (≥ 8 dev)
def _run_clamped_matrix():
    """Acceptance: clamped DistributedPipeline S-deep run == S sequential
    clamped make_distributed_step steps, bit-identical, for all four
    orderings × {gol, jacobi}; gol also equals the clamped global
    oracle. Structural: the clamped step's jaxpr has open rings only —
    every ppermute pair is a ±1 hop, no wrap pair, and each axis carries
    one pair fewer than the periodic step.
    """
    from repro.stencil import make_distributed_step, shard_state, unshard_state

    mesh = make_stencil_mesh((2, 2, 2))
    local_M, g, GM = 8, 1, 16
    r = np.random.default_rng(5)
    data = {
        "gol": (r.random((GM, GM, GM)) < 0.35).astype(np.float32),
        "jacobi": r.normal(size=(GM, GM, GM)).astype(np.float32),
    }
    cases = [(NEUMANN0, (1, 2, 4)), (dirichlet(0.0), (2,))]
    for spec in ORDERINGS:
        for rule, gcube in data.items():
            for bc, depths in cases:
                st0 = shard_state(jnp.asarray(gcube), spec, (2, 2, 2))
                step = make_distributed_step(mesh, spec, local_M, g,
                                             rule=rule, bc=bc)
                for S in depths:
                    pipe = DistributedPipeline(mesh=mesh, spec=spec,
                                               M=local_M, T=8, g=g, S=S,
                                               rule=rule, bc=bc)
                    got = np.asarray(jax.block_until_ready(pipe.run(st0, S)))
                    want = st0
                    for _ in range(S):
                        want = step(want)
                    want = np.asarray(jax.block_until_ready(want))
                    assert np.array_equal(got, want), \
                        (spec.name, rule, bc.kind, S)
                if rule == "gol":
                    # the per-step reference itself against the clamped
                    # global padded-cube oracle (two steps)
                    ora = jnp.asarray(gcube)
                    w2 = st0
                    for _ in range(2):
                        ora = kref.gol3d_step_ref(ora, g, bc=bc)
                        w2 = step(w2)
                    got2 = np.asarray(unshard_state(jnp.asarray(
                        jax.block_until_ready(w2)), spec, GM))
                    assert np.array_equal(got2, np.asarray(ora)), \
                        (spec.name, bc.kind)
    # structural: no ppermute traffic on clamped faces
    clamped_step = make_distributed_step(mesh, HILBERT, local_M, g,
                                         bc=NEUMANN0)
    periodic_step = make_distributed_step(mesh, HILBERT, local_M, g)
    st = shard_state(jnp.asarray(data["gol"]), HILBERT, (2, 2, 2))
    perms_c = _collect_ppermute_perms(jax.make_jaxpr(clamped_step)(st).jaxpr)
    perms_p = _collect_ppermute_perms(jax.make_jaxpr(periodic_step)(st).jaxpr)
    assert len(perms_c) == len(perms_p) == 6  # two ppermutes per axis
    for perm in perms_c:   # open ring on a 2-device axis: only (0,1)/(1,0)
        assert len(perm) == 1 and abs(perm[0][0] - perm[0][1]) == 1, perm
    for perm in perms_p:   # periodic ring keeps the wrap link: n pairs
        assert len(perm) == 2, perm
    assert sum(len(p) for p in perms_c) < sum(len(p) for p in perms_p)
    return True


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs >=8 devices (multi-device CI job)")
def test_clamped_matrix_inprocess():
    assert _run_clamped_matrix()


_SUBPROC = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, %r)
from test_boundary import _run_clamped_matrix
assert _run_clamped_matrix()
print("CLAMPED_MATRIX_OK")
"""


def test_clamped_matrix_subprocess():
    """Tier-1 form of the clamped acceptance matrix (8 host devices in a
    subprocess; the main pytest process keeps seeing 1 device)."""
    if jax.device_count() >= 8:
        pytest.skip("in-process variant already covers this")
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    r = subprocess.run([sys.executable, "-c", _SUBPROC % here],
                       capture_output=True, text=True, env=env, timeout=1200)
    assert "CLAMPED_MATRIX_OK" in r.stdout, r.stdout + r.stderr

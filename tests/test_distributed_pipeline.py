"""Communication-avoiding distributed pipeline (DESIGN.md §7) + satellites.

Three layers of coverage:

- pure-local tests (any device count): deep face slabs cut from the
  block store at h = S·g ∈ {1,2,3,4}, slab-by-slab shell block placement,
  extended neighbour tables, the exchange-aware bytes model and plan();
- 1×1×1-mesh tests (any device count): the full exchange+compute round
  with every ppermute a self-send — periodic wrap and clamped fills,
  checked against the padded cube and the global oracle in-process, and
  the compiled round free of element gathers (face packing) and of
  scatters and sorts (shell placement);
- the acceptance matrix on a ≥8-device mesh: DistributedPipeline with S
  substeps per exchange vs S sequential make_distributed_step steps,
  bit-identical, for all four orderings × {gol, jacobi} × S ∈ {1, 2, 4}.
  Runs in-process when the interpreter already has ≥8 devices (the
  multi-device CI job forces a host-platform mesh), else in a
  subprocess, so the shard_map paths are exercised in tier-1 everywhere.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (COLUMN_MAJOR, HILBERT, MORTON, ROW_MAJOR,
                        OrderingSpec, apply_ordering)
from repro.core.layout import store_spec
from repro.core.neighbors import (SELF_COL, extended_neighbor_table,
                                  neighbor_table, shell_block_count,
                                  shell_block_index)
from repro.core.surfaces import FACES, shell_slab_shapes
from repro.core.boundary import NEUMANN0, dirichlet, pad_cube
from repro.kernels import ref as kref
from repro.stencil import (DistributedPipeline, distributed_bytes_per_step,
                           exchange_bytes_per_step,
                           exchange_items_per_exchange, fused_vmem_bytes,
                           make_distributed_step, make_stencil_mesh,
                           resident_bytes_per_step, shard_state,
                           unshard_state, VMEM_BUDGET_BYTES)
from repro.stencil.halo import (_face_slab, _shell_blocks, exchange_shell,
                                shard_substeps)

rng = np.random.default_rng(7)

ORDERINGS = (ROW_MAJOR, COLUMN_MAJOR, MORTON, HILBERT)
FACE_SLICES = {
    "k0": lambda c, h: c[:h], "k1": lambda c, h: c[-h:],
    "i0": lambda c, h: c[:, :h, :], "i1": lambda c, h: c[:, -h:, :],
    "j0": lambda c, h: c[:, :, :h], "j1": lambda c, h: c[:, :, -h:],
}
FACE_SHAPES = {
    "k": lambda M, h: (h, M, M), "i": lambda M, h: (M, h, M),
    "j": lambda M, h: (M, M, h),
}


# ------------------------------------------ deep face slabs (satellite)
def _store(cube, kind, T):
    """(C, M, M, M) cubes -> the (C, nb, T, T, T) block store."""
    from repro.core import blockize_fields

    return blockize_fields(jnp.asarray(cube), T, kind=kind)


@pytest.mark.parametrize("spec", ORDERINGS, ids=lambda s: s.name)
@pytest.mark.parametrize("h", [1, 2, 3, 4])
def test_deep_pack_scatter_roundtrip(spec, h):
    """A store whose blocks follow the ordering's curve packs every deep
    face of width h = S·g into the canonical face slice exactly."""
    M, T = 8, 4
    cube = rng.normal(size=(1, M, M, M)).astype(np.float32)
    store = _store(cube, spec.kind, T)
    for face, take in FACE_SLICES.items():
        slab = _face_slab(store, spec.kind, T, h, face)
        assert slab.shape == (1,) + FACE_SHAPES[face[0]](M, h), face
        np.testing.assert_array_equal(np.asarray(slab)[0],
                                      take(cube[0], h), err_msg=face)


@pytest.mark.parametrize("kind", ["morton", "hilbert", "row_major"])
def test_deep_pack_from_block_store(kind):
    """The block store is path-ordered state under store_spec(kind, T):
    deep faces pack straight from the ravelled store, viewed as blocks."""
    from repro.core import blockize

    M, T, h = 16, 8, 4
    cube = rng.normal(size=(M, M, M)).astype(np.float32)
    store = blockize(jnp.asarray(cube), T, kind=kind)
    hspec = store_spec(kind, T)
    flat = np.asarray(store).ravel()
    np.testing.assert_array_equal(
        flat, np.asarray(apply_ordering(jnp.asarray(cube), hspec)))
    blocks = jnp.asarray(flat).reshape(1, -1, T, T, T)
    for face, take in FACE_SLICES.items():
        np.testing.assert_array_equal(
            np.asarray(_face_slab(blocks, kind, T, h, face))[0],
            take(cube, h), err_msg=face)


@pytest.mark.parametrize("T", [4, 8])
@pytest.mark.parametrize("h", [1, 2, 4])
@pytest.mark.parametrize("C", [1, 2])
@pytest.mark.parametrize("kind", ["row_major", "column_major", "morton",
                                  "hilbert"])
def test_face_slab_matches_canonical_faces(kind, C, h, T):
    """Every face of a C-channel store, cut by block slices, equals the
    canonical face slice of each channel's cube."""
    M = 16
    cube = rng.normal(size=(C, M, M, M)).astype(np.float32)
    store = _store(cube, kind, T)
    for face, take in FACE_SLICES.items():
        got = np.asarray(_face_slab(store, kind, T, h, face))
        want = np.stack([take(c, h) for c in cube])
        np.testing.assert_array_equal(got, want, err_msg=face)


def _slab_regions(nt):
    """Each shell slab's block grid inside the (nt+2)³ extended block
    grid, in core/surfaces.shell_slab_shapes' order."""
    e = nt + 2
    core, ext = slice(1, nt + 1), slice(None)
    return ((0, core, core), (e - 1, core, core), (ext, 0, core),
            (ext, e - 1, core), (ext, ext, 0), (ext, ext, e - 1))


@pytest.mark.parametrize("nt", [1, 2, 3, 4])
def test_shell_block_index_runs_slab_by_slab(nt):
    """The shell ids number each slab's block grid row-major, one
    contiguous run per slab in slab order, every shell id once; core
    coords map to -1."""
    sid = shell_block_index(nt)
    inner = sid[1:nt + 1, 1:nt + 1, 1:nt + 1]
    np.testing.assert_array_equal(inner, -1)
    n = 0
    for region in _slab_regions(nt):
        grid = sid[region]
        np.testing.assert_array_equal(
            grid, np.arange(n, n + grid.size).reshape(grid.shape))
        n += grid.size
    assert n == shell_block_count(nt)
    np.testing.assert_array_equal(np.sort(sid[sid >= 0]), np.arange(n))


@pytest.mark.parametrize("C", [1, 2])
@pytest.mark.parametrize("nt,T,h", [(2, 8, 3), (4, 8, 4), (3, 4, 2)])
def test_shell_blocks_hold_skin(nt, T, h, C):
    """Placed slab by slab, every shell block holds the wrap-padded
    cube's sites at its extended coords inside the h-deep skin, and
    zeros outside it (a C=1 slab without its channel axis, too)."""
    M, e = nt * T, nt + 2
    cube = rng.normal(size=(C, M, M, M)).astype(np.float32)
    xp = np.pad(cube, [(0, 0)] + [(h, h)] * 3, mode="wrap")
    E, core = M + 2 * h, slice(h, h + M)
    slabs = (xp[:, :h, core, core], xp[:, E - h:, core, core],
             xp[:, :, :h, core], xp[:, :, E - h:, core],
             xp[:, :, :, :h], xp[:, :, :, E - h:])
    placed = [_shell_blocks(jnp.asarray(s if C > 1 else s[0]), nt, T, h, f)
              for s, f in zip(slabs, FACES)]
    shell = np.concatenate([np.asarray(p) for p in placed], axis=-4)
    shell = shell.reshape(C, -1, T, T, T)
    assert shell.shape[1] == shell_block_count(nt)
    # the oracle: the skin inside whole blocks of the (nt+2)³ block grid
    whole = np.zeros((C,) + (e * T,) * 3, np.float32)
    skin = slice(T - h, T + M + h)
    whole[:, skin, skin, skin] = xp
    sid = shell_block_index(nt)
    for bk, bi, bj in zip(*np.nonzero(sid >= 0)):
        want = whole[:, bk * T:(bk + 1) * T, bi * T:(bi + 1) * T,
                     bj * T:(bj + 1) * T]
        np.testing.assert_array_equal(shell[:, sid[bk, bi, bj]], want,
                                      err_msg=str((bk, bi, bj)))


def test_extended_neighbor_table_core_and_shell():
    """Core offsets match the clamped-free interior; boundary offsets
    address the appended shell blocks; SELF_COL is the row index."""
    from repro.core.layout import block_order
    from repro.core.neighbors import OFFSETS_FULL

    nt = 2
    nb = nt ** 3
    ext = extended_neighbor_table("morton", nt)
    per = neighbor_table("morton", nt, periodic=True)
    assert ext.shape == per.shape == (nb, 27)
    np.testing.assert_array_equal(ext[:, SELF_COL], np.arange(nb))
    # brute force: in-core offsets agree with the periodic table's
    # non-wrapping entries, out-of-core offsets address the right shell id
    bo = block_order("morton", nt)
    sid = shell_block_index(nt)
    for t in range(nb):
        for o, (a, b, c) in enumerate(OFFSETS_FULL):
            co = bo[t] + (a, b, c)
            if ((co >= 0) & (co < nt)).all():
                assert ext[t, o] == per[t, o], (t, o)
            else:
                assert ext[t, o] == nb + sid[tuple(co + 1)], (t, o)
    assert ext.max() < nb + shell_block_count(nt)
    # larger grid: interior block's full neighbourhood stays in-core
    ext4 = extended_neighbor_table("hilbert", 4)
    per4 = neighbor_table("hilbert", 4, periodic=True)
    interior = (ext4 < 64).all(axis=1)
    assert interior.sum() == 2 ** 3  # the 2³ interior blocks of a 4³ grid
    np.testing.assert_array_equal(ext4[interior], per4[interior])


# --------------------------------------- exchange on a 1×1×1 mesh (periodic)
def test_exchange_shell_self_wrap_matches_pad():
    """On a 1-device mesh every ppermute is a self-send, so the shell
    must equal the periodic wrap-pad of the local cube."""
    from repro.core import blockize

    M, T, h = 16, 8, 2
    mesh = make_stencil_mesh((1, 1, 1))
    cube = rng.normal(size=(M, M, M)).astype(np.float32)
    store = blockize(jnp.asarray(cube), T, kind="hilbert")
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fn = jax.jit(shard_map(
        lambda st: exchange_shell(st.reshape(-1), "hilbert", M, T, h),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    k_lo, k_hi, i_lo, i_hi, j_lo, j_hi = map(np.asarray, fn(store))
    xp = np.pad(cube, h, mode="wrap")
    e = M + 2 * h
    np.testing.assert_array_equal(k_lo, xp[:h, h:h + M, h:h + M])
    np.testing.assert_array_equal(k_hi, xp[e - h:, h:h + M, h:h + M])
    np.testing.assert_array_equal(i_lo, xp[:, :h, h:h + M])
    np.testing.assert_array_equal(i_hi, xp[:, e - h:, h:h + M])
    np.testing.assert_array_equal(j_lo, xp[:, :, :h])
    np.testing.assert_array_equal(j_hi, xp[:, :, e - h:])


def _self_exchange(kind, M, T, h, bc):
    """exchange_shell on a 1×1×1 mesh, jit'd: every ppermute a self-send."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    return jax.jit(shard_map(
        lambda st: exchange_shell(st.reshape(st.shape[0], -1), kind, M, T,
                                  h, bc=bc),
        mesh=make_stencil_mesh((1, 1, 1)), in_specs=P(), out_specs=P(),
        check_vma=False))


@pytest.mark.parametrize("kind", ["morton", "hilbert"])
@pytest.mark.parametrize("bc", [dirichlet(0.5), NEUMANN0], ids=lambda b: b.kind)
def test_exchange_shell_self_clamped_matches_pad(bc, kind):
    """On a 1-device mesh under a clamped contract no ppermute pair
    exists, so every slab is the boundary fill: the six slabs of a
    two-channel store equal each channel's cube padded by ``bc``."""
    M, T, h, C = 16, 8, 3, 2
    cube = rng.normal(size=(C, M, M, M)).astype(np.float32)
    slabs = _self_exchange(kind, M, T, h, bc)(_store(cube, kind, T))
    xp = np.stack([np.asarray(pad_cube(jnp.asarray(c), h, bc)) for c in cube])
    e, core = M + 2 * h, slice(h, h + M)
    want = (xp[:, :h, core, core], xp[:, e - h:, core, core],
            xp[:, :, :h, core], xp[:, :, e - h:, core],
            xp[:, :, :, :h], xp[:, :, :, e - h:])
    for got, w in zip(slabs, want):
        np.testing.assert_array_equal(np.asarray(got), w)


@pytest.mark.parametrize("bc", ["periodic", "neumann0"])
def test_exchange_packs_without_element_gathers(bc):
    """The compiled exchange cuts its faces by block slices: no gather or
    scatter op carries a pack scope (the index-list pack was an element
    gather of every face site plus a scatter into its slab)."""
    import re

    M, T, h = 16, 4, 2
    store = jax.ShapeDtypeStruct((1, (M // T) ** 3, T, T, T), jnp.float32)
    text = _self_exchange("hilbert", M, T, h, bc).lower(store).compile().as_text()
    ops = [line for line in text.splitlines()
           if re.search(r"= \S+ (gather|scatter)\(", line)]
    assert not [op for op in ops if re.search(r'op_name="[^"]*sfc\.(un)?pack', op)]
    assert "sfc.pack" in text


@pytest.mark.parametrize("bc", ["periodic", "neumann0"])
def test_shell_places_without_scatter_or_sort(bc):
    """The compiled deep round places the received slabs by pads,
    reshapes and transposes: no scatter or sort op, and no int32 constant
    of one entry per shell site (the site-list placement scattered
    through such a list, behind a sort of it on the TPU)."""
    import re
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    M, T, g, S = 16, 4, 1, 2
    skin = (M + 2 * S * g) ** 3 - M ** 3
    fn = jax.jit(shard_map(
        lambda st: shard_substeps(st, kind="hilbert", M=M, g=g, S=S,
                                  rule="jacobi", bc=bc),
        mesh=make_stencil_mesh((1, 1, 1)), in_specs=P(), out_specs=P(),
        check_vma=False))
    store = jax.ShapeDtypeStruct(((M // T) ** 3, T, T, T), jnp.float32)
    lowered = fn.lower(store)
    text = lowered.compile().as_text()
    assert not re.findall(r"= \S+ (?:scatter|sort)\(", text)
    assert "sfc.shell" in text
    assert f"tensor<{skin}xi32>" not in lowered.as_text()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_shard_substeps_self_wrap_matches_oracle(use_kernel):
    """One deep round on a 1×1×1 mesh == S periodic oracle steps (gol)."""
    from repro.core import blockize, unblockize
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    M, T, g, S = 16, 8, 1, 4
    mesh = make_stencil_mesh((1, 1, 1))
    cube = (rng.random((M, M, M)) < 0.3).astype(np.float32)
    store = blockize(jnp.asarray(cube), T, kind="morton")
    fn = jax.jit(shard_map(
        lambda st: shard_substeps(st, kind="morton", M=M, g=g, S=S,
                                  use_kernel=use_kernel),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    got = np.asarray(unblockize(fn(store), M, kind="morton"))
    want = jnp.asarray(cube)
    for _ in range(S):
        want = kref.gol3d_step_ref(want, g)
    np.testing.assert_array_equal(got, np.asarray(want))


# ------------------------------------------------- sharded-state round trip
def test_shard_unshard_roundtrip():
    GM = 16
    cube = rng.normal(size=(GM, GM, GM)).astype(np.float32)
    for spec in (HILBERT, ROW_MAJOR):
        st = shard_state(jnp.asarray(cube), spec, (2, 2, 2))
        assert st.shape == (2, 2, 2, 8 ** 3)
        back = unshard_state(st, spec, GM)
        np.testing.assert_array_equal(np.asarray(back), cube)


# ----------------------------------------------- bytes model + plan (accept)
def test_exchange_model_matches_slab_shapes():
    """The ICI model is exactly the six exchanged slab volumes — one
    accounting between the exchange code and the model."""
    for M, g, S in [(16, 1, 1), (16, 1, 4), (64, 1, 4), (64, 2, 2)]:
        h = S * g
        slabs = sum(int(np.prod(s)) for s in shell_slab_shapes(M, h))
        assert exchange_items_per_exchange(M, g, S) == slabs
        assert exchange_bytes_per_step(M, g, S) == 4.0 * slabs / S


def test_distributed_bytes_acceptance():
    """Acceptance: at the PR-2 reference point (local M=64, T=8, g=1)
    total modelled bytes/step (HBM + exchange) at S=4 is strictly below
    S=1 — asserted from the shared helpers."""
    lo = distributed_bytes_per_step(64, 8, 1, 8, S=4)
    hi = distributed_bytes_per_step(64, 8, 1, 8, S=1)
    assert lo < hi
    # decomposition: the HBM term is the resident fused model, the ICI
    # term the exchange model — nothing else
    assert lo == resident_bytes_per_step(64, 8, 1, 8, S=4) + \
        exchange_bytes_per_step(64, 1, 4)
    # deep exchanges move slightly MORE wire bytes (corner growth): the
    # win is HBM amortisation + fewer messages, not fewer halo bytes
    assert exchange_bytes_per_step(64, 1, 4) > exchange_bytes_per_step(64, 1, 1)


def test_distributed_plan_minimises_joint_cost():
    """plan() optimises HBM+ICI over the same (T, S) grid as the
    resident plan, never exceeding any enumerable candidate."""
    mesh = make_stencil_mesh((1, 1, 1))
    for M, g, lim in [(16, 1, VMEM_BUDGET_BYTES), (64, 1, 64 * 1024),
                      (64, 2, 256 * 1024)]:
        pipe = DistributedPipeline.plan(mesh, HILBERT, M, g=g,
                                        vmem_limit=lim)
        assert fused_vmem_bytes(pipe.T, g, pipe.S) <= lim
        best = pipe.bytes_per_step(10)
        T = 1
        while T <= M:
            if M % T == 0 and T % g == 0:
                S = 1
                while S <= 8:
                    h = S * g
                    if h <= T and T % h == 0 and \
                            fused_vmem_bytes(T, g, S) <= lim:
                        assert best <= distributed_bytes_per_step(
                            M, T, g, 10, S=S)
                    S *= 2
            T *= 2


def test_pipeline_rejects_bad_S():
    mesh = make_stencil_mesh((1, 1, 1))
    with pytest.raises(ValueError):
        DistributedPipeline(mesh=mesh, spec=MORTON, M=16, T=8, g=1, S=3)
    with pytest.raises(ValueError):
        DistributedPipeline(mesh=mesh, spec=MORTON, M=16, T=8, g=2, S=8)


# ------------------------------------------------- acceptance matrix (≥ 8 dev)
def _run_acceptance_matrix():
    """DistributedPipeline S-deep run == S sequential make_distributed_step
    steps, bit-identical, all four orderings × {gol, jacobi} × S ∈ {1,2,4}.

    Shared by the in-process ≥8-device test (multi-device CI job) and the
    tier-1 subprocess runner.
    """
    mesh = make_stencil_mesh((2, 2, 2))
    local_M, g, GM = 8, 1, 16
    r = np.random.default_rng(3)
    data = {
        "gol": (r.random((GM, GM, GM)) < 0.35).astype(np.float32),
        "jacobi": r.normal(size=(GM, GM, GM)).astype(np.float32),
    }
    for spec in ORDERINGS:
        for rule, gcube in data.items():
            st0 = shard_state(jnp.asarray(gcube), spec, (2, 2, 2))
            step = make_distributed_step(mesh, spec, local_M, g, rule=rule)
            for S in (1, 2, 4):
                pipe = DistributedPipeline(mesh=mesh, spec=spec, M=local_M,
                                           T=8, g=g, S=S, rule=rule)
                got = np.asarray(jax.block_until_ready(pipe.run(st0, S)))
                want = st0
                for _ in range(S):
                    want = step(want)
                want = np.asarray(jax.block_until_ready(want))
                assert np.array_equal(got, want), (spec.name, rule, S)
    # and the gol column against the global periodic oracle
    want = jnp.asarray(data["gol"])
    for _ in range(4):
        want = kref.gol3d_step_ref(want, g)
    pipe = DistributedPipeline(mesh=mesh, spec=HILBERT, M=local_M, g=g, S=4)
    got = np.asarray(pipe.run_cube(jnp.asarray(data["gol"]), 4))
    assert np.array_equal(got, np.asarray(want))
    return True


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs >=8 devices (multi-device CI job)")
def test_acceptance_matrix_inprocess():
    assert _run_acceptance_matrix()


_SUBPROC = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, %r)
from test_distributed_pipeline import _run_acceptance_matrix
assert _run_acceptance_matrix()
print("MATRIX_OK")
"""


def test_acceptance_matrix_subprocess():
    """Tier-1 form of the acceptance matrix: forces 8 host devices in a
    subprocess (the main pytest process must keep seeing 1 device)."""
    if jax.device_count() >= 8:
        pytest.skip("in-process variant already covers this")
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    r = subprocess.run([sys.executable, "-c", _SUBPROC % here],
                       capture_output=True, text=True, env=env, timeout=1200)
    assert "MATRIX_OK" in r.stdout, r.stdout + r.stderr

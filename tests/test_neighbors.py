"""Resident-block layer: neighbour tables, block round-trips, fused pipeline."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HILBERT, MORTON, PERIODIC, ROW_MAJOR, OrderingSpec,
                        blockize, pad_cube, unblockize)
from repro.core.neighbors import (FACE_COLS, OFFSETS_FACE, OFFSETS_FULL,
                                  SELF_COL, block_kind_of, neighbor_table,
                                  ring_perms)
from repro.core.layout import block_order
from repro.core.orderings import path_to_rmo, rmo_to_path
from repro.kernels import ref
from repro.kernels.ops import uniform_weights
from repro.kernels.stencil3d import stencil_step_fused
from repro.stencil import Gol3d, Gol3dConfig, ResidentPipeline

rng = np.random.default_rng(7)

KINDS = ("row_major", "column_major", "morton", "hilbert")
HYBRID = OrderingSpec("hybrid", tile=4, outer="hilbert", inner="row_major")


# ------------------------------------------------------------ block round-trip
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("M,T", [(16, 8), (16, 4), (32, 8), (8, 8)])
def test_blockize_roundtrip(kind, M, T):
    cube = jnp.asarray(rng.normal(size=(M, M, M)).astype(np.float32))
    blocks = blockize(cube, T, kind=kind)
    assert blocks.shape == ((M // T) ** 3, T, T, T)
    back = unblockize(blocks, M, kind=kind)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(cube))


def test_permutations_are_int32():
    """DESIGN.md §2: permutation tables ride int32 (gather/prefetch width)."""
    for spec in (ROW_MAJOR, MORTON, HILBERT, HYBRID):
        assert rmo_to_path(spec, 16).dtype == np.int32
        assert path_to_rmo(spec, 16).dtype == np.int32


# ------------------------------------------------------------ neighbour tables
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("nt", [2, 4, 8])
@pytest.mark.parametrize("periodic", [True, False])
def test_neighbor_table_brute_force(kind, nt, periodic):
    """Every table entry matches direct coordinate arithmetic."""
    tab = neighbor_table(kind, nt, periodic=periodic)
    assert tab.shape == (nt ** 3, 27)
    assert tab.dtype == np.int32
    bo = block_order(kind, nt)  # path pos -> (k,i,j)
    lin_to_path = {(int(k), int(i), int(j)): t
                   for t, (k, i, j) in enumerate(bo)}
    for t in range(nt ** 3):
        k, i, j = (int(c) for c in bo[t])
        for o, (dk, di, dj) in enumerate(OFFSETS_FULL):
            if periodic:
                key = ((k + dk) % nt, (i + di) % nt, (j + dj) % nt)
            else:
                key = (min(max(k + dk, 0), nt - 1),
                       min(max(i + di, 0), nt - 1),
                       min(max(j + dj, 0), nt - 1))
            assert tab[t, o] == lin_to_path[key], (t, o, key)


def test_neighbor_table_face_variant():
    tab6 = neighbor_table("hilbert", 4, connectivity="face")
    tab27 = neighbor_table("hilbert", 4)
    assert tab6.shape == (64, 6)
    np.testing.assert_array_equal(tab6, tab27[:, list(FACE_COLS)])
    # column order is [k-, k+, i-, i+, j-, j+]
    assert tuple(OFFSETS_FULL[c] for c in FACE_COLS) == OFFSETS_FACE
    # self column is the identity
    np.testing.assert_array_equal(tab27[:, SELF_COL], np.arange(64))


def test_neighbor_table_spec_generic():
    """OrderingSpec and its block-kind string resolve to the same table."""
    assert block_kind_of(HILBERT) == "hilbert"
    assert block_kind_of(HYBRID) == "hilbert"
    assert block_kind_of("morton") == "morton"
    np.testing.assert_array_equal(neighbor_table(HILBERT, 4),
                                  neighbor_table("hilbert", 4))
    np.testing.assert_array_equal(neighbor_table(HYBRID, 4),
                                  neighbor_table("hilbert", 4))


def test_neighbor_table_cached_and_readonly():
    a = neighbor_table("morton", 4)
    assert neighbor_table("morton", 4) is a
    assert not a.flags.writeable


def test_ring_perms():
    fwd, bwd = ring_perms(4)
    assert fwd == [(0, 1), (1, 2), (2, 3), (3, 0)]
    assert bwd == [(0, 3), (1, 0), (2, 1), (3, 2)]


# ------------------------------------------ in-kernel halo vs the padded cube
def _halo_windows(cube, T, g, kind):
    """Every block's (T+2g)³ window cut from the wrap-padded cube, in
    curve order: what the assembled halo must hold."""
    xp = np.asarray(pad_cube(cube, g, PERIODIC))
    W = T + 2 * g
    return np.stack([xp[k:k + W, i:i + W, j:j + W]
                     for k, i, j in block_order(kind, cube.shape[0] // T) * T])


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("g", [1, 2])
def test_assemble_halo_bit_identical(kind, g):
    M, T = 16, 8
    cube = jnp.asarray(rng.normal(size=(M, M, M)).astype(np.float32))
    store = blockize(cube, T, kind=kind)
    nbr = neighbor_table(kind, M // T)
    asm = ref.assemble_halo_ref(store, nbr, g)
    np.testing.assert_array_equal(np.asarray(asm),
                                  _halo_windows(cube, T, g, kind))


def _identity_launch(cube, w, T, g, kind):
    """(fused identity launch, its oracle, the assembled windows)."""
    store = blockize(cube, T, kind=kind)
    nbr = neighbor_table(kind, cube.shape[0] // T)
    got = stencil_step_fused(store, w, nbr, g=g, S=1, rule="identity")
    want = ref.stencil_fused_ref(store, w, nbr, S=1, rule="identity")
    return got, want, ref.assemble_halo_ref(store, nbr, g)


@pytest.mark.parametrize("kind", ("morton", "hilbert"))
@pytest.mark.parametrize("g,T", [(1, 8), (2, 8), (1, 4), (4, 4)])
def test_resident_kernel_bit_identical(kind, g, T):
    """The fused kernel's raw tap sum == its jnp oracle, bit for bit.

    The weights are random signed powers of two, so every w·x product
    is exact: the two programs then agree bit for bit whether or not
    the compiler contracts a multiply-add into one FMA.
    """
    M = 16
    cube = jnp.asarray(rng.normal(size=(M, M, M)).astype(np.float32))
    shape = (2 * g + 1,) * 3
    w = jnp.asarray((rng.choice([-1.0, 1.0], size=shape)
                     * np.exp2(rng.integers(-3, 4, size=shape)))
                    .astype(np.float32))
    got, want, _ = _identity_launch(cube, w, T, g, kind)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kind", ("morton", "hilbert"))
@pytest.mark.parametrize("g,T", [(1, 8), (2, 8), (1, 4), (4, 4)])
def test_resident_kernel_normal_weights_within_rounding(kind, g, T):
    """With normal weights the kernel and the oracle may round
    differently: the CPU compiler fuses a different subset of the
    multiply-adds into FMAs in each program. Two roundings of an n-term
    sum differ by at most 2·n·u·Σ|w·x| (u = 2⁻²⁴); a halo or
    neighbour-table fault moves a site by O(|w·x|)."""
    M = 16
    s = 2 * g + 1
    cube = jnp.asarray(rng.normal(size=(M, M, M)).astype(np.float32))
    w = rng.normal(size=(s,) * 3).astype(np.float32)
    got, want, halo = _identity_launch(cube, jnp.asarray(w), T, g, kind)
    x = np.abs(np.asarray(halo, np.float64))
    mag = sum(abs(float(w[a, b, c])) * x[:, a:a + T, b:b + T, c:c + T]
              for a in range(s) for b in range(s) for c in range(s))
    diff = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    assert np.all(diff <= 2 * s ** 3 * 2.0 ** -24 * mag)


def test_resident_kernel_rejects_non_dividing_g():
    store = jnp.zeros((8, 8, 8, 8), jnp.float32)
    nbr = neighbor_table("morton", 2)
    with pytest.raises(ValueError):
        stencil_step_fused(store, jnp.zeros((7, 7, 7)), nbr, g=3, S=1,
                           rule="identity")


# -------------------------------------------------------------- fused pipeline
@pytest.mark.parametrize("ordering", [ROW_MAJOR, MORTON, HILBERT, HYBRID],
                         ids=lambda s: s.name)
@pytest.mark.parametrize("M", [16, 32])
@pytest.mark.parametrize("g", [1, 2])
def test_resident_pipeline_matches_repack(ordering, M, g):
    """The resident run == the ordering-independent oracles: gol through
    Gol3d against gol3d_step_ref, exactly, and jacobi through
    ResidentPipeline on the same block curve against fields_step_ref, to
    the last-ulp drift of its mean (the two programs round it apart)."""
    steps = 3
    app = Gol3d(Gol3dConfig(M=M, g=g, ordering=ordering, block_T=8))
    want = app.reference_run(steps)
    app.run(steps)
    np.testing.assert_array_equal(np.asarray(app.cube), np.asarray(want))
    cube = jnp.asarray(rng.normal(size=(M, M, M)).astype(np.float32))
    pipe = ResidentPipeline(M=M, T=8, g=g, kind=block_kind_of(ordering),
                            rule="jacobi")
    want = cube
    for _ in range(steps):
        want = ref.fields_step_ref(want, uniform_weights(g), g, rule="jacobi")
    np.testing.assert_allclose(np.asarray(pipe.run(cube, steps)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("g", [1, 2])
def test_resident_pipeline_matches_oracle(g):
    """K=4 fused steps == the ordering-independent canonical oracle."""
    app = Gol3d(Gol3dConfig(M=16, g=g, ordering=HILBERT, block_T=8))
    want = app.reference_run(4)
    app.run(4)
    np.testing.assert_array_equal(np.asarray(app.cube), np.asarray(want))


def test_resident_pipeline_kernel_mode():
    app = Gol3d(Gol3dConfig(M=16, g=1, ordering=MORTON, block_T=8,
                            use_kernel=True))
    want = app.reference_run(2)
    app.run(2)
    np.testing.assert_array_equal(np.asarray(app.cube), np.asarray(want))


def test_resident_step_preserves_weights_semantics():
    """One resident step == one step of the canonical gol3d oracle."""
    M, T, g = 16, 8, 1
    pipe = ResidentPipeline(M=M, T=T, g=g, kind="morton")
    cube = jnp.asarray((rng.random((M, M, M)) < 0.3).astype(np.float32))
    got = pipe.run(cube, 1)
    want = ref.gol3d_step_ref(cube, g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

"""Temporal-blocked fused stencil (DESIGN.md §4) + PR-2 satellites.

Equivalence discipline (mirrors PR-1): bit-identity is asserted within
an implementation family — the fused S-substep kernel against S
sequential launches of the same kernel, and the fused jnp oracle
against S sequential oracle steps. Across families (Pallas interpret vs
jnp) XLA's FMA contraction can differ in the last ulp for arbitrary f32
data, so cross-family checks are exact for gol (integer-valued sums)
and allclose for jacobi.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MORTON, NEUMANN0, PERIODIC, blockize,
                        blockize_fields, dirichlet, mixed)
from repro.core.neighbors import neighbor_table
from repro.kernels import ref
from repro.kernels.ops import uniform_weights
from repro.kernels.rules import RULES, box_centre, get_rule, tap_form
from repro.kernels.stencil3d import (VMEM_LIMIT_BYTES, fused_kernel_vmem_bytes,
                                     stencil_step_fused)
from repro.stencil import Gol3d, Gol3dConfig
from repro.stencil.pipeline import (VMEM_BUDGET_BYTES, ResidentPipeline,
                                    distributed_bytes_per_step,
                                    exchange_bytes_per_step,
                                    exchange_items_per_exchange,
                                    fused_items_per_launch, fused_vmem_bytes,
                                    resident_bytes_per_step)

rng = np.random.default_rng(11)

KINDS = ("row_major", "column_major", "morton", "hilbert")
M, T, G = 16, 8, 1


def _store(kind, rule):
    C = get_rule(rule).channels
    if rule == "gol":
        cube = (rng.random((M, M, M)) < 0.3).astype(np.float32)
    elif C == 1:
        cube = rng.normal(size=(M, M, M)).astype(np.float32)
    else:  # stacked multi-field state (DESIGN.md §9)
        fields = rng.normal(size=(C, M, M, M)).astype(np.float32)
        return blockize_fields(jnp.asarray(fields), T, kind=kind)
    return blockize(jnp.asarray(cube), T, kind=kind)


def _seq_kernel(store, w, nbr, steps, rule):
    for _ in range(steps):
        store = stencil_step_fused(store, w, nbr, g=G, S=1, rule=rule)
    return store


# ------------------------------------------------------- fused bit-identity
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("S", [1, 2, 4])
@pytest.mark.parametrize("rule", ["gol", "jacobi", "wave"])
def test_fused_kernel_matches_sequential_seed_steps(kind, S, rule):
    """One fused S-substep launch == S sequential seed-step launches —
    the kernel-family matrix, now spanning the multi-field C=2 wave
    store (DESIGN.md §9) next to the scalar rules."""
    w = uniform_weights(G)
    nbr = neighbor_table(kind, M // T)
    store = _store(kind, rule)
    r = get_rule(rule)
    fused = stencil_step_fused(store, w, nbr, g=G, S=S, rule=rule)
    seq = _seq_kernel(store, w, nbr, S, rule)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(seq))
    # the jnp oracle of the fused form matches its own sequential form...
    oracle = ref.stencil_fused_ref(store, w, nbr, S=S, rule=rule)
    oseq = store
    for _ in range(S):
        if r.channels == 1:
            neigh = ref.stencil_sum_ref(ref.assemble_halo_ref(oseq, nbr, G), w)
        else:  # per-channel tap sums of the stacked store
            neigh = jnp.stack([
                ref.stencil_sum_ref(ref.assemble_halo_ref(oseq[c], nbr, G), w)
                for c in range(r.channels)])
        oseq = r.apply(oseq.astype(jnp.float32), neigh, G).astype(store.dtype)
    np.testing.assert_array_equal(np.asarray(oracle), np.asarray(oseq))
    # ...and the kernel cross-family: exact for gol (integer sums) and
    # wave (FMA-immune by construction), allclose for jacobi (divide)
    if rule == "jacobi":
        np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                                   rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(oracle))


def test_fused_identity_rule_is_raw_stencil_sum():
    """rule="identity", S=1 returns the raw tap sum of the oracle."""
    w = uniform_weights(G)
    nbr = neighbor_table("morton", M // T)
    store = _store("morton", "jacobi")
    a = stencil_step_fused(store, w, nbr, g=G, S=1, rule="identity")
    b = ref.stencil_fused_ref(store, w, nbr, S=1, rule="identity")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_kernel_rejects_bad_S():
    store = jnp.zeros((8, 8, 8, 8), jnp.float32)
    nbr = neighbor_table("morton", 2)
    w = uniform_weights(1)
    with pytest.raises(ValueError):
        stencil_step_fused(store, w, nbr, g=1, S=3, rule="gol")  # 3 ∤ 8
    with pytest.raises(ValueError):
        stencil_step_fused(store, w, nbr, g=1, S=16, rule="gol")  # 16 > T
    with pytest.raises(ValueError):
        stencil_step_fused(store, w, nbr, g=1, S=2, rule="nope")


def test_rules_registry():
    assert set(RULES) >= {"gol", "jacobi", "identity"}
    assert get_rule("gol") is RULES["gol"]
    assert get_rule(RULES["jacobi"]) is RULES["jacobi"]
    with pytest.raises(ValueError):
        get_rule("unknown-rule")


# ------------------------------------------------------------- the pipeline
@pytest.mark.parametrize("n_steps", [3, 7, 10])
def test_pipeline_S_matches_single_step_pipeline(n_steps):
    """Fused S=4 kernel pipeline == S=1 oracle pipeline, incl. K % S
    remainders (7 = 1 full launch + 3 single-step tail since 3·g ∤ T)."""
    cube = jnp.asarray((rng.random((M, M, M)) < 0.3).astype(np.float32))
    base = ResidentPipeline(M=M, T=T, g=G, kind="hilbert", S=1)
    fused = ResidentPipeline(M=M, T=T, g=G, kind="hilbert", S=4,
                             use_kernel=True)
    np.testing.assert_array_equal(np.asarray(base.run(cube, n_steps)),
                                  np.asarray(fused.run(cube, n_steps)))


def test_pipeline_S_matches_oracle_reference():
    """Fused S through Gol3d (substeps knob) == canonical cube oracle."""
    app = Gol3d(Gol3dConfig(M=M, g=G, ordering=MORTON, block_T=T, substeps=2))
    want = app.reference_run(4)
    app.run(4)
    np.testing.assert_array_equal(np.asarray(app.cube), np.asarray(want))


def test_pipeline_rejects_bad_S():
    with pytest.raises(ValueError):
        ResidentPipeline(M=16, T=8, g=1, S=3)
    with pytest.raises(ValueError):
        ResidentPipeline(M=16, T=8, g=2, S=8)


def test_pipeline_jacobi_rule():
    """The same fused driver serves the jacobi workload (new-rule path)."""
    cube = jnp.asarray(rng.normal(size=(M, M, M)).astype(np.float32))
    a = ResidentPipeline(M=M, T=T, g=G, rule="jacobi", S=2,
                         use_kernel=True).run(cube, 4)
    b = ResidentPipeline(M=M, T=T, g=G, rule="jacobi", S=1,
                         use_kernel=True).run(cube, 4)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- autotuner + VMEM model
def test_plan_respects_vmem_budget():
    """Acceptance: the autotuned (T, S) fits the modelled VMEM budget."""
    for M_, g in [(32, 1), (64, 1), (64, 2), (128, 1)]:
        pipe = ResidentPipeline.plan(M_, g=g)
        assert fused_vmem_bytes(pipe.T, g, pipe.S) <= VMEM_BUDGET_BYTES
        assert pipe._valid_S(pipe.S) and M_ % pipe.T == 0
        # the plan never models more traffic than the default (T=8, S=1)
        assert (pipe.bytes_per_step(10)
                <= resident_bytes_per_step(M_, 8, g, 10, S=1))
    # a tight budget forces a smaller window, and still fits
    tight = ResidentPipeline.plan(64, g=1, vmem_limit=64 * 1024)
    assert fused_vmem_bytes(tight.T, 1, tight.S) <= 64 * 1024
    with pytest.raises(ValueError):
        ResidentPipeline.plan(64, g=1, vmem_limit=64)


def test_plan_pipeline_runs_correctly():
    pipe = ResidentPipeline.plan(M, g=G, kind="morton",
                                 vmem_limit=256 * 1024)
    cube = jnp.asarray((rng.random((M, M, M)) < 0.3).astype(np.float32))
    got = pipe.run(cube, 5)
    want = cube
    for _ in range(5):
        want = ref.gol3d_step_ref(want, G)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------- bytes model
def test_bytes_model_has_interior_optimum_in_S():
    """At fixed T the per-substep window cost (T+2·S·g)³/S first falls
    (launch overheads amortise) then rises (window inflation wins):
    the autotuner exists because S is a real knob, not 'always more'."""
    b = {S: resident_bytes_per_step(64, 8, 1, 100, S=S) for S in (1, 2, 4, 8)}
    assert b[2] < b[1]          # fusing helps...
    assert b[8] > b[2]          # ...but too-deep blocking pays more halo
    # plan() at a budget that admits T=8 picks the interior optimum, not S=1
    pipe = ResidentPipeline.plan(64, g=1, n_steps=100, vmem_limit=64 * 1024)
    assert pipe.bytes_per_step(100) <= min(b.values())


BCS = {"periodic": PERIODIC, "dirichlet": dirichlet(0.0),
       "neumann0": NEUMANN0, "mixed": mixed(k="neumann0")}


@pytest.mark.parametrize("C", [1, 2])
@pytest.mark.parametrize("bc", sorted(BCS))
def test_benchmark_rows_share_accounting(bc, C):
    """One accounting: the mesh model is the resident HBM model plus the
    exchange model, for the mesh mean and for every shard, and the
    resident model is the fused launch stream amortised over S plus the
    layout boundary amortised over the run."""
    M_, T_, g, S, K, procs = 64, 8, 1, 4, 10, (2, 2, 2)
    bcs = BCS[bc]
    res = resident_bytes_per_step(M_, T_, g, K, S=S, fields=C)
    assert res == 4 * (fused_items_per_launch(M_, T_, g, S, fields=C) / S
                       + C * 4 * M_ ** 3 / K)
    for coords in [None] + [(a, b, c) for a in range(2) for b in range(2)
                            for c in range(2)]:
        ici = exchange_bytes_per_step(M_, g, S, bc=bcs, procs=procs,
                                      coords=coords, fields=C)
        assert ici == 4 * exchange_items_per_exchange(
            M_, g, S, bc=bcs, procs=procs, coords=coords, fields=C) / S
        assert distributed_bytes_per_step(
            M_, T_, g, K, S=S, bc=bcs, procs=procs, coords=coords,
            fields=C) == pytest.approx(res + ici)
    # S amortises the stream: one launch of S steps, not S launches
    assert fused_items_per_launch(M_, T_, g, S, fields=C) \
        < S * fused_items_per_launch(M_, T_, g, 1, fields=C)


# ----------------------------------------------------------- cache satellites
def test_surface_row_plan_cached():
    """Satellite: pack_surface memoises the unique/searchsorted row plan
    on (spec, M, g, face, line); repeated packs reuse the same arrays."""
    from repro.kernels import ops

    M_, g, line = 16, 1, 8
    key = ((MORTON, M_, g, "k0"), line)
    ops._ROW_PLANS.pop(key, None)
    cube = jnp.asarray(rng.normal(size=(M_, M_, M_)).astype(np.float32))
    from repro.core import apply_ordering
    data = apply_ordering(cube, MORTON)
    a = ops.pack_surface(data, MORTON, M_, g, "k0", use_kernel=True, line=line)
    assert key in ops._ROW_PLANS
    plan1 = ops._ROW_PLANS[key]
    b = ops.pack_surface(data, MORTON, M_, g, "k0", use_kernel=True, line=line)
    assert ops._ROW_PLANS[key] is plan1  # reused, not recomputed
    assert not plan1[0].flags.writeable
    ref_buf = ops.pack_surface(data, MORTON, M_, g, "k0", use_kernel=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(ref_buf))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(ref_buf))


# ------------------------------------------- chunked grid (TPU tiling form)
@pytest.mark.parametrize("rule", ["gol", "jacobi", "wave", "identity"])
@pytest.mark.parametrize("bc", ["periodic", "neumann0", "dirichlet"])
def test_fused_kernel_k_chunks_match_oracle(rule, bc):
    """The (nb, T/kc) grid with 8-row i-halo pieces — the form that keeps
    every block shape on the TPU's (8, 128) tiling — equals the jnp
    oracle on every k-chunk (first and last chunks take one k halo from
    the k-neighbour block, interior chunks take both from their own).
    identity runs normal weights: the weighted form, to rounding."""
    from repro.core.boundary import as_boundary
    from repro.core.neighbors import boundary_face_table
    from repro.kernels.stencil3d import fused_geometry

    M_, T_, S = 64, 32, 2
    bcs = as_boundary(bc)
    nbr = neighbor_table("hilbert", M_ // T_, periodic=not bcs.clamped)
    bnd = boundary_face_table("hilbert", M_ // T_) if bcs.clamped else None
    C = get_rule(rule).channels
    fields = rng.normal(size=(C, M_, M_, M_)).astype(np.float32)
    if rule == "gol":
        fields = (fields > 0.5).astype(np.float32)
    store = blockize_fields(jnp.asarray(fields), T_, kind="hilbert")
    if C == 1:
        store = store[0]
    w = _ring_weights(rule, G)
    assert fused_geometry(T_, S * G) == (8, 8)        # 4 chunks, 8-row halos
    chunked = stencil_step_fused(store, w, nbr, bnd, g=G, S=S, rule=rule,
                                 bc=bc)
    oracle = ref.stencil_fused_ref(store, w, nbr, S=S, rule=rule, bc=bc,
                                   bnd=bnd)
    if rule == "identity":   # two steps of 27 normal-weighted terms reach
        # ~200, and the two programs contract different multiply-adds
        # into FMAs: they round apart by up to ~2e-5 of that scale
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(oracle),
                                   rtol=1e-4, atol=1e-4)
    elif rule == "jacobi":
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(oracle),
                                   rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(np.asarray(chunked), np.asarray(oracle))


# ------------------------------------------------------- tap-copy ring
RING_CASES = [
    ("gol", 2, 1, "periodic"), ("gol", 4, 1, "periodic"),
    ("jacobi", 2, 1, "periodic"), ("jacobi", 4, 1, "periodic"),
    ("wave", 2, 1, "periodic"), ("wave", 4, 1, "periodic"),
    ("jacobi", 2, 2, "periodic"),
    ("wave", 2, 1, "mixed-i"),
    ("identity", 2, 1, "periodic"), ("identity", 2, 2, "periodic"),
]


def _ring_weights(rule, g):
    """identity runs normal weights, so the 27-tap weighted form keeps
    its coverage; every other rule the uniform box (the box form)."""
    if rule == "identity":
        return jnp.asarray(rng.normal(size=(2 * g + 1,) * 3)
                           .astype(np.float32))
    return uniform_weights(g)


def _ring_store(rule, M_, T_):
    C = get_rule(rule).channels
    fields = rng.normal(size=(C, M_, M_, M_)).astype(np.float32)
    if rule == "gol":
        fields = (fields > 0.5).astype(np.float32)
    store = blockize_fields(jnp.asarray(fields), T_, kind="hilbert")
    return store[0] if C == 1 else store


@pytest.mark.parametrize("rule,S,g,bc", RING_CASES)
def test_fused_ring_matches_sequential_launches(rule, S, g, bc):
    """The tap-copy ring at a geometry the small matrix does not reach:
    M=64, T=32 runs four k-chunks per block (kc < T), and the shrinking
    window (34-40 rows and lanes) is not a whole number of sublane
    tiles. One S-substep launch equals S single-substep launches bit
    for bit, and the jnp oracle exactly for the rules whose sums are
    exact (gol) or FMA-immune (wave). identity runs normal weights
    (``_ring_weights``): the 27-tap weighted form."""
    from repro.core.boundary import as_boundary, axes_periodic, mixed
    from repro.core.neighbors import boundary_face_table

    M_, T_ = 64, 32
    bcs = mixed(i="neumann0") if bc == "mixed-i" else as_boundary(bc)
    nt = M_ // T_
    nbr = neighbor_table("hilbert", nt, periodic=axes_periodic(bcs))
    bnd = boundary_face_table("hilbert", nt) if bcs.clamped else None
    store = _ring_store(rule, M_, T_)
    w = _ring_weights(rule, g)
    fused = stencil_step_fused(store, w, nbr, bnd, g=g, S=S, rule=rule,
                               bc=bcs)
    seq = store
    for _ in range(S):
        seq = stencil_step_fused(seq, w, nbr, bnd, g=g, S=1, rule=rule,
                                 bc=bcs)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(seq))
    if rule in ("gol", "wave"):
        oracle = ref.stencil_fused_ref(store, w, nbr, S=S, rule=rule,
                                       bc=bcs, bnd=bnd)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(oracle))


@pytest.mark.parametrize("g", [1, 2])
def test_fused_ring_identity_is_resident_sum(g):
    """rule="identity" through the ring at M=64, T=32 reproduces the
    oracle's tap sum, which slices its window directly."""
    nbr = neighbor_table("hilbert", 2)
    store = _ring_store("jacobi", 64, 32)
    w = uniform_weights(g)
    a = stencil_step_fused(store, w, nbr, g=g, S=1, rule="identity")
    b = ref.stencil_fused_ref(store, w, nbr, S=1, rule="identity")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_kernel_vmem_counts_ring():
    """The kernel's VMEM, hand-worked at T=128, h=4 (kc = hb = 8): every
    scratch plane pads to whole (8, 128) tiles, so a 136-row plane of
    134 or 132 lanes takes 136 x 256 x 4 = 139264 B."""
    plane = 136 * 256 * 4
    window = 16 * plane                         # (kc + 2h) planes
    streamed = 2 * 4 * (3 * 16 * 144 * 128 + 8 * 128 * 128)
    assert fused_kernel_vmem_bytes(128, 4, g=1) == \
        window + 3 * 8 * plane + streamed       # 3 planes x 8 shifts
    assert fused_kernel_vmem_bytes(128, 4, g=2) == \
        window + 5 * 24 * plane + streamed      # 5 planes x 24 shifts
    assert fused_kernel_vmem_bytes(128, 4, 2, g=2) == \
        2 * fused_kernel_vmem_bytes(128, 4, g=2)
    assert fused_kernel_vmem_bytes(128, 4, 2, g=2) <= VMEM_LIMIT_BYTES
    # the box form: 2g+1 ij-partial planes and the j-partial's stage
    assert fused_kernel_vmem_bytes(128, 4, g=1, form="box") == \
        window + 4 * plane + streamed
    assert fused_kernel_vmem_bytes(128, 4, g=2, form="box") == \
        window + 6 * plane + streamed


def test_fused_geometry_keeps_tpu_tiling():
    from repro.kernels.stencil3d import fused_geometry

    assert fused_geometry(128, 1) == (8, 8)
    assert fused_geometry(128, 2) == (8, 8)
    assert fused_geometry(128, 16) == (16, 16)
    assert fused_geometry(8, 4) == (8, 8)   # small blocks: whole edges
    assert fused_geometry(12, 3) == (12, 12)  # no tile divides T: whole


def test_platform_picks_kernel_mode(monkeypatch):
    """Interpret mode is CPU-only: on a TPU backend the kernels compile,
    an explicit interpret=True raises, and the pipelines default to the
    compiled kernel instead of the jnp oracle."""
    from repro.kernels import backend

    assert backend.resolve_interpret(None) is True      # this CPU run
    assert ResidentPipeline(M=16, T=8).use_kernel is False
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    assert backend.resolve_interpret(None) is False
    with pytest.raises(ValueError, match="TPU"):
        backend.resolve_interpret(True)
    assert ResidentPipeline(M=16, T=8).use_kernel is True
    assert Gol3dConfig(M=16).use_kernel is True
    assert ResidentPipeline(M=16, T=8, use_kernel=False).use_kernel is False
    # the compiled kernel takes the lane-dense block edge by default and
    # in plan(); the oracle keeps the CPU's default T=8
    assert ResidentPipeline(M=1024).T == 128
    assert Gol3dConfig(M=1024).block_T == 128
    assert Gol3dConfig(M=64).block_T == 64
    assert Gol3dConfig(M=1024, use_kernel=False).block_T == 8
    tuned = ResidentPipeline.plan(1024, g=1)
    assert tuned.T == 128
    assert fused_kernel_vmem_bytes(128, tuned.S) <= VMEM_LIMIT_BYTES


# ------------------------------------------------------------- the box form
def _box_with(value, at=(1, 1, 1)):
    """The g=1 box of ones with one weight set to ``value``."""
    w = np.ones((3, 3, 3), np.float32)
    w[at] = value
    return w


FORM_CASES = {
    "uniform-g1": (lambda: uniform_weights(1), 0.0),
    "uniform-g2": (lambda: uniform_weights(2), 0.0),
    "uniform-g1-device": (lambda: jnp.asarray(uniform_weights(1)), 0.0),
    "all-ones-g1": (lambda: _box_with(1.0), 1.0),
    "all-ones-g2": (lambda: np.ones((5, 5, 5), np.float32), 1.0),
    "normal-g1": (lambda: rng.normal(size=(3, 3, 3)).astype(np.float32), None),
    "one-corner-off": (lambda: _box_with(2.0, at=(0, 0, 0)), None),
    "half-centre": (lambda: _box_with(0.5), None),
}


@pytest.mark.parametrize("case", sorted(FORM_CASES))
def test_tap_form_detects_the_box(case):
    """The box form is taken for concrete weights whose off-centre taps
    are all exactly 1.0 and whose centre is 0.0 or 1.0; any other
    weights take the weighted form."""
    make, centre = FORM_CASES[case]
    w = make()
    assert box_centre(w) == centre
    assert tap_form(w) == ("weighted" if centre is None else "box")


@pytest.mark.parametrize("g", [1, 2])
def test_tap_form_of_traced_weights_is_weighted(g):
    """Traced weights have no values when the kernel is traced."""
    seen = []
    jax.jit(lambda w: seen.append(tap_form(w)) or w)(uniform_weights(g))
    assert tap_form(uniform_weights(g)) == "box"
    assert seen == ["weighted"]


BOX_CASES = [("gol", 2, 1), ("gol", 1, 2), ("jacobi", 2, 1),
             ("jacobi", 1, 2), ("wave", 2, 1), ("identity", 1, 1),
             ("identity", 1, 2)]


@pytest.mark.parametrize("rule,S,g", BOX_CASES)
def test_box_form_matches_weighted_form(rule, S, g):
    """The box form (concrete uniform weights) against the weighted form
    (the same weights traced) on normal data. They sum in different
    orders: gol's integer sums agree exactly; the raw sum (identity)
    within the f32 reassociation bound 2·n·u·Σ|x| per site (n = s³
    terms, u = 2⁻²⁴); jacobi and wave within rtol = atol = 1e-5, the
    tolerance the repo uses across programs for jacobi."""
    M_, T_ = 16, 8
    s = 2 * g + 1
    nbr = neighbor_table("hilbert", M_ // T_)
    store = _ring_store(rule, M_, T_)
    w = uniform_weights(g)
    box = stencil_step_fused(store, w, nbr, g=g, S=S, rule=rule)
    weighted = jax.jit(lambda st, w: stencil_step_fused(
        st, w, nbr, g=g, S=S, rule=rule))(store, w)
    box, weighted = np.asarray(box), np.asarray(weighted)
    if rule == "gol":
        np.testing.assert_array_equal(box, weighted)
    elif rule == "identity":
        halo = np.abs(np.asarray(ref.assemble_halo_ref(store, nbr, g),
                                 np.float64))
        mag = sum(halo[:, a:a + T_, b:b + T_, c:c + T_]
                  for a in range(s) for b in range(s) for c in range(s))
        diff = np.abs(box.astype(np.float64) - weighted)
        assert np.all(diff <= 2 * s ** 3 * 2.0 ** -24 * mag)
        assert not np.array_equal(box, weighted)   # two orders, not one
    else:
        np.testing.assert_allclose(box, weighted, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ["jacobi3d-g1-hilbert-1024",
                                  "jacobi3d-g1-hilbert-mesh2x2-512"])
def test_benchmark_pipelines_take_the_box_form(name):
    """Both benchmark configurations launch the box form: the pipelines
    pass the uniform weights, whose form is decided at trace time."""
    import json
    from pathlib import Path

    from repro.core.layout import store_spec
    from repro.stencil import DistributedPipeline, make_stencil_mesh

    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "configs" / f"{name}.json")
    c = json.loads(path.read_text())
    if "mesh" in c:   # the form does not depend on the mesh's shape
        pipe = DistributedPipeline(
            mesh=make_stencil_mesh((1, 1, 1)),
            spec=store_spec(c["kind"], c["T"]), M=c["M"], T=c["T"],
            g=c["g"], S=c["S"], rule=c["rule"], bc=c["bc"])
    else:
        pipe = ResidentPipeline(M=c["M"], T=c["T"], g=c["g"], kind=c["kind"],
                                S=c["S"], rule=c["rule"], bc=c["bc"])
    assert pipe.tap_form == "box"

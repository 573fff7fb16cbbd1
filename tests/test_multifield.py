"""Multi-field (C-channel) block store end-to-end (DESIGN.md §9).

Coverage layers, mirroring the single-field suites:

- store + registry units: blockize_fields/unblockize_fields round-trips
  against per-channel blockize, the wave rule's declared channels, and
  the rank/channel mismatch guards on kernel and oracle;
- resident matrix: the C=2 wave workload through ResidentPipeline —
  fused S-deep vs sequential bit-identity in both families, and (the
  wave rule is FMA-immune by construction) exact equality against the
  global sequential oracle ref.fields_step_ref across all four
  orderings and periodic + clamped + mixed boundaries;
- plan(): the VMEM budget carries the ×C working set, so wave plans
  never exceed the budget and shrink under tight limits;
- bytes model: every accounting helper's ``fields`` factor is exactly
  ×C, under every boundary contract;
- exchange: the C-channel shell exchange on a 1×1×1 mesh equals the
  per-channel pad, packed through one set of messages;
- the ≥8-device wave acceptance matrix: DistributedPipeline S-deep vs S
  sequential make_distributed_step rounds, bit-identical, for all four
  orderings × {periodic, neumann0}, plus the global-oracle column —
  in-process on the multi-device CI job, subprocess under tier-1.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (COLUMN_MAJOR, HILBERT, MORTON, NEUMANN0, PERIODIC,
                        ROW_MAJOR, blockize, blockize_fields, dirichlet, mixed,
                        unblockize_fields)
from repro.core.neighbors import neighbor_table
from repro.kernels import ref as kref
from repro.kernels.ops import uniform_weights
from repro.kernels.rules import RULES, get_rule
from repro.kernels.stencil3d import stencil_step_fused
from repro.stencil import (DistributedPipeline, ResidentPipeline,
                           distributed_bytes_per_step, exchange_bytes_per_step,
                           exchange_items_per_exchange, fused_items_per_launch,
                           fused_vmem_bytes, make_stencil_mesh,
                           resident_bytes_per_step)

rng = np.random.default_rng(31)

ORDERINGS = (ROW_MAJOR, COLUMN_MAJOR, MORTON, HILBERT)
M, T, G = 16, 8, 1


def _fields(C=2, M_=M):
    return jnp.asarray(rng.normal(size=(C, M_, M_, M_)).astype(np.float32))


def _oracle_run(fields, g, steps, bc="periodic"):
    w = uniform_weights(g)
    want = fields
    for _ in range(steps):
        want = kref.fields_step_ref(want, w, g, rule="wave", bc=bc)
    return np.asarray(want)


# ------------------------------------------------------- store + rule units
def test_wave_rule_registered():
    assert RULES["wave"].channels == 2
    assert get_rule("wave") is RULES["wave"]
    for name in ("gol", "jacobi", "identity"):
        assert get_rule(name).channels == 1


def test_blockize_fields_roundtrip_shares_block_permutation():
    fields = _fields()
    for kind in ("morton", "hilbert", "row_major"):
        store = blockize_fields(fields, T, kind=kind)
        assert store.shape == (2, (M // T) ** 3, T, T, T)
        # channel c's blocks are exactly blockize of channel c — one
        # shared permutation, no per-channel layout drift
        for c in range(2):
            np.testing.assert_array_equal(
                np.asarray(store[c]),
                np.asarray(blockize(fields[c], T, kind=kind)))
        back = unblockize_fields(store, M, kind=kind)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(fields))
    # 3-D input promotes to C=1
    one = blockize_fields(fields[0], T, kind="morton")
    assert one.shape == (1, (M // T) ** 3, T, T, T)


def test_channel_mismatch_rejected():
    w = uniform_weights(G)
    nbr = neighbor_table("morton", M // T)
    scalar = blockize(_fields()[0], T, kind="morton")
    stacked = blockize_fields(_fields(), T, kind="morton")
    with pytest.raises(ValueError):  # wave needs the stacked store
        stencil_step_fused(scalar, w, nbr, g=G, S=1, rule="wave")
    with pytest.raises(ValueError):  # gol is C=1
        stencil_step_fused(stacked, w, nbr, g=G, S=1, rule="gol")
    with pytest.raises(ValueError):
        kref.stencil_fused_ref(scalar, w, nbr, S=1, rule="wave")
    with pytest.raises(ValueError):
        kref.fields_step_ref(_fields(3), w, G, rule="wave")
    with pytest.raises(ValueError):  # pipelines refuse mismatched state
        ResidentPipeline(M=M, T=T, g=G, rule="wave").run(_fields()[0], 1)


def test_wave_leapfrog_is_stable():
    """κ·λ_max < 4: the leapfrog oscillates, state stays bounded — the
    property that makes long fused runs meaningful (DESIGN.md §9)."""
    fields = _fields()
    out = np.asarray(_oracle_run(fields, G, 32))
    assert np.isfinite(out).all()
    assert np.abs(out).max() < 64 * np.abs(np.asarray(fields)).max()


# ----------------------------------------------------------- resident matrix
@pytest.mark.parametrize("spec_kind", ["row_major", "column_major",
                                       "morton", "hilbert"])
@pytest.mark.parametrize("S", [2, 4])
def test_resident_wave_fused_matches_sequential_and_oracle(spec_kind, S):
    """Acceptance: the C=2 wave rule through ResidentPipeline — fused
    S-deep (kernel and jnp families) == S=1 sequential == the global
    sequential jnp oracle, bit-identical (f32), for every ordering."""
    fields = _fields()
    deep = ResidentPipeline(M=M, T=T, g=G, kind=spec_kind, S=S, rule="wave",
                            use_kernel=True)
    seq = ResidentPipeline(M=M, T=T, g=G, kind=spec_kind, S=1, rule="wave")
    a = np.asarray(deep.run(fields, S))
    np.testing.assert_array_equal(a, np.asarray(seq.run(fields, S)))
    ora = ResidentPipeline(M=M, T=T, g=G, kind=spec_kind, S=S, rule="wave")
    np.testing.assert_array_equal(a, np.asarray(ora.run(fields, S)))
    np.testing.assert_array_equal(a, _oracle_run(fields, G, S))


@pytest.mark.parametrize("bc", [NEUMANN0, dirichlet(0.5), mixed(k=NEUMANN0)],
                         ids=lambda b: b.kind)
def test_resident_wave_clamped_and_mixed(bc):
    """Clamped + per-face mixed contracts on the multi-field store: the
    per-substep ghost refresh applies to every channel alike and stays
    bit-identical to the padded-fields oracle (DESIGN.md §8–§9)."""
    fields = _fields()
    S = 4
    deep = ResidentPipeline(M=M, T=T, g=G, kind="hilbert", S=S, rule="wave",
                            bc=bc, use_kernel=True)
    ora = ResidentPipeline(M=M, T=T, g=G, kind="hilbert", S=S, rule="wave",
                          bc=bc)
    a = np.asarray(deep.run(fields, S))
    np.testing.assert_array_equal(a, np.asarray(ora.run(fields, S)))
    np.testing.assert_array_equal(a, _oracle_run(fields, G, S, bc=bc))


# ------------------------------------------------------- plan() + VMEM model
def test_plan_budgets_vmem_for_C_windows():
    """The autotuner's working set carries the ×C factor: wave plans fit
    the budget with C=2 windows live, and a tight budget forces a
    smaller window than the C=1 plan gets away with."""
    for M_, lim in [(32, 256 * 1024), (64, 8 * 2 ** 20)]:
        pipe = ResidentPipeline.plan(M_, g=1, rule="wave", vmem_limit=lim)
        assert pipe.channels == 2
        assert fused_vmem_bytes(pipe.T, 1, pipe.S, fields=2) <= lim
        assert pipe.vmem_bytes() == fused_vmem_bytes(pipe.T, 1, pipe.S,
                                                     fields=2)
    # same tight budget: the wave plan either matches the C=1 pick or
    # was forced off it because two windows no longer fit
    lim = 96 * 1024
    one = ResidentPipeline.plan(64, g=1, rule="gol", vmem_limit=lim)
    two = ResidentPipeline.plan(64, g=1, rule="wave", vmem_limit=lim)
    assert fused_vmem_bytes(two.T, 1, two.S, fields=2) <= lim
    assert (two.T, two.S) == (one.T, one.S) or \
        fused_vmem_bytes(one.T, 1, one.S, fields=2) > lim
    # an impossible budget still raises
    with pytest.raises(ValueError):
        ResidentPipeline.plan(64, g=1, rule="wave", vmem_limit=256)


def test_plan_wave_runs_correctly():
    pipe = ResidentPipeline.plan(M, g=G, kind="morton", rule="wave",
                                 vmem_limit=256 * 1024)
    fields = _fields()
    got = np.asarray(pipe.run(fields, 3))
    np.testing.assert_array_equal(got, _oracle_run(fields, G, 3))


# --------------------------------------------------- bytes model + benchmarks
def test_bytes_model_fields_factor_is_exactly_C():
    """Acceptance: modelled HBM and ICI both scale by exactly ×C — the
    multi-field store adds payload, never overhead."""
    for C in (2, 3, 4):
        assert fused_items_per_launch(64, 8, 1, 4, fields=C) == \
            C * fused_items_per_launch(64, 8, 1, 4)
        assert resident_bytes_per_step(64, 8, 1, 10, S=4, fields=C) == \
            pytest.approx(C * resident_bytes_per_step(64, 8, 1, 10, S=4))
        assert exchange_items_per_exchange(16, 1, 4, fields=C) == \
            C * exchange_items_per_exchange(16, 1, 4)
        assert exchange_bytes_per_step(16, 1, 4, fields=C) == \
            pytest.approx(C * exchange_bytes_per_step(16, 1, 4))
        assert distributed_bytes_per_step(16, 8, 1, 10, S=4, fields=C) == \
            pytest.approx(C * distributed_bytes_per_step(16, 8, 1, 10, S=4))
    # clamped exchange composes with fields
    assert exchange_items_per_exchange(
        16, 1, 4, bc=NEUMANN0, procs=(2, 2, 2), coords=(0, 0, 0),
        fields=2) == 2 * exchange_items_per_exchange(
        16, 1, 4, bc=NEUMANN0, procs=(2, 2, 2), coords=(0, 0, 0))


BCS = {"periodic": PERIODIC, "dirichlet": dirichlet(0.0),
       "neumann0": NEUMANN0, "mixed": mixed(j="dirichlet")}


@pytest.mark.parametrize("C", [1, 2])
@pytest.mark.parametrize("bc", sorted(BCS))
def test_multifield_benchmark_rows_share_accounting(bc, C):
    """The ×C factor under every contract: each model with ``fields=C``
    is C times its single-field value, for the mesh mean and for every
    shard, and the per-field stream of the C-channel store is the
    single-field stream."""
    M_, T_, g, S, K, procs = 32, 8, 1, 4, 10, (2, 2, 2)
    bcs = BCS[bc]
    res = resident_bytes_per_step(M_, T_, g, K, S=S, fields=C)
    assert res / C == pytest.approx(resident_bytes_per_step(M_, T_, g, K,
                                                            S=S))
    assert fused_vmem_bytes(T_, g, S, fields=C) - fused_vmem_bytes(
        T_, g, S) == (C - 1) * 4 * (2 * (T_ + 2 * S * g) ** 3 + 2 * T_ ** 3)
    for coords in [None, (0, 0, 0), (1, 0, 1)]:
        kw = dict(bc=bcs, procs=procs, coords=coords)
        assert exchange_items_per_exchange(M_, g, S, fields=C, **kw) == \
            C * exchange_items_per_exchange(M_, g, S, **kw)
        assert exchange_bytes_per_step(M_, g, S, fields=C, **kw) == \
            pytest.approx(C * exchange_bytes_per_step(M_, g, S, **kw))
        assert distributed_bytes_per_step(M_, T_, g, K, S=S, fields=C,
                                          **kw) == pytest.approx(
            C * distributed_bytes_per_step(M_, T_, g, K, S=S, **kw))


def test_pipeline_wave_bytes_accessors_carry_C():
    pipe = ResidentPipeline(M=32, T=8, g=1, S=4, rule="wave")
    assert pipe.bytes_per_step(10) == resident_bytes_per_step(
        32, 8, 1, 10, S=4, fields=2)
    mesh = make_stencil_mesh((1, 1, 1))
    dp = DistributedPipeline(mesh=mesh, spec=HILBERT, M=16, T=8, g=1, S=2,
                             rule="wave")
    assert dp.channels == 2
    assert dp.exchange_bytes_per_step() == exchange_bytes_per_step(
        16, 1, 2, fields=2)
    assert dp.bytes_per_step(10) == distributed_bytes_per_step(
        16, 8, 1, 10, S=2, fields=2)


# ----------------------------------------- exchange + 1×1×1 mesh (in-process)
def test_exchange_shell_multifield_matches_per_channel_pad():
    """The C-channel shell exchange packs every channel through one set
    of messages and equals the per-channel wrap pad on a self-mesh."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.stencil.halo import exchange_shell

    M_, T_, h = 16, 8, 2
    mesh = make_stencil_mesh((1, 1, 1))
    fields = np.asarray(_fields(2, M_))
    store = blockize_fields(jnp.asarray(fields), T_, kind="hilbert")
    fn = jax.jit(shard_map(
        lambda st: exchange_shell(st.reshape(2, -1), "hilbert", M_, T_, h),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    slabs = [np.asarray(s) for s in fn(store)]
    e = M_ + 2 * h
    for c in range(2):
        xp = np.pad(fields[c], h, mode="wrap")
        np.testing.assert_array_equal(slabs[0][c], xp[:h, h:h + M_, h:h + M_])
        np.testing.assert_array_equal(slabs[1][c],
                                      xp[e - h:, h:h + M_, h:h + M_])
        np.testing.assert_array_equal(slabs[4][c], xp[:, :, :h])
        np.testing.assert_array_equal(slabs[5][c], xp[:, :, e - h:])


@pytest.mark.parametrize("use_kernel", [False, True])
def test_shard_substeps_wave_self_wrap_matches_oracle(use_kernel):
    """One deep C=2 round on a 1×1×1 mesh == S global wave steps."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.stencil.halo import shard_substeps

    S = 4
    mesh = make_stencil_mesh((1, 1, 1))
    fields = _fields()
    store = blockize_fields(fields, T, kind="morton")
    fn = jax.jit(shard_map(
        lambda st: shard_substeps(st, kind="morton", M=M, g=G, S=S,
                                  rule="wave", use_kernel=use_kernel),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    got = np.asarray(unblockize_fields(fn(store), M, kind="morton"))
    np.testing.assert_array_equal(got, _oracle_run(fields, G, S))


# ------------------------------------------------- acceptance matrix (≥ 8 dev)
def _run_wave_matrix():
    """Acceptance (DESIGN.md §9): the C=2 wave DistributedPipeline S-deep
    run == S sequential make_distributed_step rounds, bit-identical, for
    all four orderings × {periodic, neumann0} × S ∈ {1, 2, 4}; the
    periodic hilbert column also equals the global sequential oracle
    through run_cube (shard → K deep rounds → gather).
    """
    from repro.stencil import make_distributed_step, shard_state

    mesh = make_stencil_mesh((2, 2, 2))
    local_M, g, GM = 8, 1, 16
    r = np.random.default_rng(9)
    gf = jnp.asarray(r.normal(size=(2, GM, GM, GM)).astype(np.float32))
    for spec in ORDERINGS:
        for bc in ("periodic", NEUMANN0):
            st0 = shard_state(gf, spec, (2, 2, 2))
            assert st0.shape == (2, 2, 2, 2, local_M ** 3)
            step = make_distributed_step(mesh, spec, local_M, g, rule="wave",
                                         bc=bc)
            for S in (1, 2, 4):
                pipe = DistributedPipeline(mesh=mesh, spec=spec, M=local_M,
                                           T=8, g=g, S=S, rule="wave", bc=bc)
                got = np.asarray(jax.block_until_ready(pipe.run(st0, S)))
                want = st0
                for _ in range(S):
                    want = step(want)
                want = np.asarray(jax.block_until_ready(want))
                assert np.array_equal(got, want), (spec.name, str(bc), S)
    # the global-oracle column (round trip through shard/unshard)
    w = uniform_weights(g)
    want = gf
    for _ in range(4):
        want = kref.fields_step_ref(want, w, g, rule="wave")
    pipe = DistributedPipeline(mesh=mesh, spec=HILBERT, M=local_M, g=g, S=4,
                               rule="wave")
    got = np.asarray(pipe.run_cube(gf, 4))
    assert got.shape == (2, GM, GM, GM)
    assert np.array_equal(got, np.asarray(want))
    return True


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs >=8 devices (multi-device CI job)")
def test_wave_matrix_inprocess():
    assert _run_wave_matrix()


_SUBPROC = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, %r)
from test_multifield import _run_wave_matrix
assert _run_wave_matrix()
print("WAVE_MATRIX_OK")
"""


def test_wave_matrix_subprocess():
    """Tier-1 form of the 8-device distributed wave acceptance test."""
    if jax.device_count() >= 8:
        pytest.skip("in-process variant already covers this")
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    r = subprocess.run([sys.executable, "-c", _SUBPROC % here],
                       capture_output=True, text=True, env=env, timeout=1200)
    assert "WAVE_MATRIX_OK" in r.stdout, r.stdout + r.stderr

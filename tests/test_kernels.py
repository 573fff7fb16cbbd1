"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HILBERT, MORTON, ROW_MAJOR, apply_ordering, blockize
from repro.core.neighbors import neighbor_table
from repro.kernels import ref
from repro.kernels.flash_attn import build_schedule, flash_attention_fwd
from repro.kernels.ops import (flash_attention, pack_surface,
                               sfc_gather_take, unpack_surface, _fold_gqa)
from repro.kernels.sfc_gather import gather_rows
from repro.kernels.stencil3d import stencil_step_fused
from repro.stencil import Gol3d, Gol3dConfig

rng = np.random.default_rng(42)


# ----------------------------------------------------------------- stencil
@pytest.mark.parametrize("g,T", [(1, 4), (1, 8), (2, 4), (2, 8)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_stencil_kernel_allclose(g, T, dtype):
    """The raw tap sum (rule="identity", one substep) of the fused
    kernel against its oracle, normal weights, f32 and bf16 stores."""
    M = 2 * T
    cube = jnp.asarray(rng.normal(size=(M, M, M)).astype(np.float32))
    store = blockize(cube, T, kind="hilbert").astype(dtype)
    nbr = neighbor_table("hilbert", M // T)
    w = jnp.asarray(rng.normal(size=(2 * g + 1,) * 3).astype(np.float32))
    out_k = stencil_step_fused(store, w, nbr, g=g, S=1, rule="identity")
    out_r = ref.stencil_fused_ref(store, w, nbr, S=1, rule="identity")
    assert out_k.dtype == out_r.dtype == dtype
    tol = 1e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)


def test_gol3d_kernel_matches_canonical():
    cube = jnp.asarray((rng.random((16, 16, 16)) < 0.3).astype(np.float32))
    for g in (1, 2):
        for spec in (MORTON, HILBERT):
            app = Gol3d(Gol3dConfig(M=16, g=g, ordering=spec, block_T=4,
                                    use_kernel=True),
                        state_path=apply_ordering(cube, spec))
            app.run(1)
            b = ref.gol3d_step_ref(cube, g=g)
            np.testing.assert_array_equal(np.asarray(app.cube), np.asarray(b))


# ------------------------------------------------------------------ gather
@pytest.mark.parametrize("n,L,r", [(32, 16, 10), (8, 128, 8), (64, 8, 64)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_gather_rows_allclose(n, L, r, dtype):
    src = jnp.asarray(rng.normal(size=(n, L)).astype(np.float32)).astype(dtype)
    idx = jnp.asarray(rng.integers(0, n, size=(r,)).astype(np.int32))
    np.testing.assert_array_equal(np.asarray(gather_rows(src, idx)),
                                  np.asarray(ref.gather_rows_ref(src, idx)))


def test_sfc_gather_take_exact():
    data = jnp.asarray(rng.normal(size=(4096,)).astype(np.float32))
    idx = rng.choice(4096, size=777, replace=False)
    idx.sort()
    a = sfc_gather_take(data, idx, line=64, use_kernel=True)
    b = jnp.take(data, jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("spec", [ROW_MAJOR, MORTON, HILBERT],
                         ids=lambda s: s.name)
def test_pack_unpack_roundtrip(spec):
    M, g = 16, 1
    cube = jnp.asarray(rng.normal(size=(M, M, M)).astype(np.float32))
    data = apply_ordering(cube, spec)
    for face in ("k0", "j1", "i0"):
        buf_k = pack_surface(data, spec, M, g, face, use_kernel=True, line=8)
        buf_r = pack_surface(data, spec, M, g, face, use_kernel=False)
        np.testing.assert_array_equal(np.asarray(buf_k), np.asarray(buf_r))
        back = unpack_surface(data, buf_r, spec, M, g, face)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(data))


# -------------------------------------------------------------- flash attn
@pytest.mark.parametrize("schedule", ["row_major", "morton", "hilbert"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_fwd_allclose(schedule, causal):
    for (BH, Sq, Sk, D) in [(2, 64, 64, 16), (1, 128, 128, 32), (2, 32, 128, 16)]:
        q = jnp.asarray(rng.normal(size=(BH, Sq, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(BH, Sk, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(BH, Sk, D)).astype(np.float32))
        o_k = flash_attention_fwd(q, k, v, causal=causal, block_q=16,
                                  block_k=16, schedule=schedule)
        o_r = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=2e-4, atol=2e-4)


def test_flash_fwd_bf16():
    q = jnp.asarray(rng.normal(size=(2, 64, 32))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 64, 32))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 64, 32))).astype(jnp.bfloat16)
    o_k = flash_attention_fwd(q, k, v, causal=True, block_q=32, block_k=32)
    o_r = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), rtol=0.1, atol=0.1)


def test_flash_gqa_grad_matches_ref():
    q = jnp.asarray(rng.normal(size=(2, 4, 32, 8)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 2, 32, 8)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 2, 32, 8)).astype(np.float32))

    def loss_k(q, k, v):
        return flash_attention(q, k, v, True, "morton", 16, 16).sum()

    def loss_r(q, k, v):
        qf, kf, vf = _fold_gqa(q, k, v)
        return ref.attention_ref(qf, kf, vf, causal=True).sum()

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_schedule_covers_causal_cells():
    for kind in ("row_major", "morton", "hilbert"):
        iq, ik = build_schedule(4, 4, causal=True, block_q=16, block_k=16,
                                kind=kind)
        cells = set(zip(iq.tolist(), ik.tolist()))
        want = {(a, b) for a in range(4) for b in range(4) if b <= a}
        assert cells == want
        assert len(iq) == len(want)  # no duplicates


def test_schedule_sfc_vmem_reuse():
    """SFC schedules reuse VMEM-resident q/kv blocks far better than
    row-major — the paper's LRU model applied to the kernel's block
    fetch stream (hilbert additionally has unit-step traversal)."""
    from repro.core.cache_model import simulate_lru

    def misses(kind, n=16, cap=12):
        iq, ik = build_schedule(n, n, causal=False, block_q=1, block_k=1,
                                kind=kind)
        stream, ids = [], {}
        for a, b in zip(iq.tolist(), ik.tolist()):
            for key in (("q", a), ("k", b), ("v", b)):
                stream.append(ids.setdefault(key, len(ids)))
        return simulate_lru(np.asarray(stream), cap)

    m_rm = misses("row_major")
    m_mo = misses("morton")
    m_hi = misses("hilbert")
    assert m_mo < m_rm / 2
    assert m_hi < m_rm / 2
    # hilbert: unit steps in the block grid
    iq_h, ik_h = build_schedule(8, 8, causal=False, block_q=1, block_k=1,
                                kind="hilbert")
    steps = np.abs(np.diff(iq_h)) + np.abs(np.diff(ik_h))
    assert steps.max() == 1

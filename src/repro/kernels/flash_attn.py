"""Pallas TPU kernel: flash attention with space-filling-curve block schedule.

Beyond-paper application of the paper's idea (DESIGN.md §5, level 2): the
(q-block × kv-block) score grid of flash attention is a 2D index space.
Traversing it row-major re-streams every KV block for every q block; a
Morton/Hilbert traversal visits a 2×2 (then 4×4, …) neighbourhood of
blocks before moving on, so q-block and kv-block fetches are reused while
resident — the exact cache-line argument of the paper, with VMEM as the
cache and HBM→VMEM DMAs as the misses. tests/test_kernels.py scores
the schedules with the paper's own LRU model (core/cache_model).

Mechanics: one flat grid axis walks the (pre-filtered causal) cell list in
schedule order; the schedule is a trace-time numpy computation handed to
the kernel as scalar-prefetch operands, so the index maps (and hence the
DMA engine) know the next block ahead of time. Online-softmax statistics
are kept per q-row-block in VMEM scratch ``(nq, bq)``; the output tile is
rewritten on every visit (last visit wins), which keeps the kernel correct
under *any* traversal order. VMEM cost: ``nq·bq·(D+2)·4B`` — e.g. 4k
tokens, bq=128, D=128 → 2.1 MiB; for longer sequences the schedule is
applied hierarchically within VMEM-sized super-tiles (see ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.orderings import path_index_2d

from .backend import resolve_interpret

__all__ = ["build_schedule", "flash_attention_fwd"]

_NEG_INF = float("-inf")


def build_schedule(nq: int, nk: int, *, causal: bool, block_q: int,
                   block_k: int, kind: str = "morton",
                   offs: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Cell visit order over the (nq × nk) block grid.

    Returns (iq_of_t, ik_of_t) int32 arrays of equal length = #visited
    cells. Causal filtering keeps cells whose block intersects
    ``col <= row + offs`` (offs = Sk - Sq aligns the diagonal at the end).
    """
    if kind == "row_major":
        cells = [(iq, ik) for iq in range(nq) for ik in range(nk)]
    else:
        n = 1 << max(0, (max(nq, nk) - 1)).bit_length()
        n = max(n, 2)
        seq = path_index_2d(kind, n)
        cells = [divmod(int(t), n) for t in seq]
        cells = [(iq, ik) for iq, ik in cells if iq < nq and ik < nk]
    if causal:
        cells = [(iq, ik) for iq, ik in cells
                 if ik * block_k <= (iq + 1) * block_q - 1 + offs]
    iq = np.array([c[0] for c in cells], dtype=np.int32)
    ik = np.array([c[1] for c in cells], dtype=np.int32)
    return iq, ik


def _flash_kernel(iq_ref, ik_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, bq: int, bk: int, scale: float,
                  causal: bool, offs: int, out_dtype):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    iq = iq_ref[t]
    ik = ik_ref[t]
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T) * scale  # (bq, bk) — MXU matmul
    if causal:
        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(cols <= rows + offs, s, _NEG_INF)

    m_prev = m_ref[iq]  # (bq,)
    l_prev = l_ref[iq]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    still_empty = m_cur == _NEG_INF  # rows with no unmasked key yet
    p = jnp.where(still_empty[:, None], 0.0, jnp.exp(s - m_cur[:, None]))
    alpha = jnp.where(still_empty, 1.0, jnp.exp(m_prev - m_cur))
    l_new = l_prev * alpha + p.sum(axis=1)
    acc_new = acc_ref[iq] * alpha[:, None] + jnp.dot(p, v)
    m_ref[iq] = m_cur
    l_ref[iq] = l_new
    acc_ref[iq] = acc_new
    # rewrite the output tile each visit: correct under any schedule
    denom = jnp.where(l_new == 0.0, 1.0, l_new)
    o_ref[0] = (acc_new / denom[:, None]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "schedule", "interpret"))
def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, block_q: int = 64,
                        block_k: int = 64, schedule: str = "morton",
                        interpret: bool | None = None) -> jnp.ndarray:
    """Flash attention forward. q: (BH, Sq, D); k, v: (BH, Sk, D).

    Heads are pre-folded into the batch axis (ops.py handles GQA).
    Sq/Sk must be divisible by block_q/block_k (ops.py picks blocks).
    """
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    offs = Sk - Sq
    iq_arr, ik_arr = build_schedule(nq, nk, causal=causal, block_q=block_q,
                                    block_k=block_k, kind=schedule, offs=offs)
    ncells = len(iq_arr)
    kern = functools.partial(
        _flash_kernel, bq=block_q, bk=block_k, scale=1.0 / np.sqrt(D),
        causal=causal, offs=offs, out_dtype=q.dtype)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, ncells),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, t, iq, ik: (b, iq[t], 0)),
                pl.BlockSpec((1, block_k, D), lambda b, t, iq, ik: (b, ik[t], 0)),
                pl.BlockSpec((1, block_k, D), lambda b, t, iq, ik: (b, ik[t], 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, D),
                                   lambda b, t, iq, ik: (b, iq[t], 0)),
            scratch_shapes=[
                pltpu.VMEM((nq, block_q, D), jnp.float32),
                pltpu.VMEM((nq, block_q), jnp.float32),
                pltpu.VMEM((nq, block_q), jnp.float32),
            ],
        ),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(iq_arr), jnp.asarray(ik_arr), q, k, v)

"""Pallas kernel benchmarks: schedule-locality scoring + interpret timing.

The real object here is structural (this container has no TPU): the
paper's LRU cache model (core/cache_model.simulate_lru) re-parameterised
for VMEM scores the *block fetch stream* of each flash-attention
schedule — row-major vs Morton vs Hilbert traversal of the (q,kv) block
grid. A "line" is one block; capacity c is how many blocks fit VMEM.
Fewer misses = fewer HBM→VMEM DMAs = lower memory term on TPU.

Also times the interpret-mode kernels (CPU correctness path) so
regressions are visible.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache_model import simulate_lru
from repro.core.layout import blockize, blockize_with_halo
from repro.core.neighbors import FACE_COLS, SELF_COL, neighbor_table
from repro.kernels.flash_attn import build_schedule, flash_attention_fwd
from repro.kernels.ops import uniform_weights
from repro.kernels.stencil3d import (stencil_step_fused, stencil_sum_blocks,
                                     stencil_sum_resident)
from repro.stencil.pipeline import (fused_items_per_launch,
                                    repack_items_per_step,
                                    resident_unfused_items_per_step)


def _attention_block_stream(nq, nk, kind, causal=True):
    """Sequence of distinct (kind, block) VMEM fetches for a schedule."""
    iq, ik = build_schedule(nq, nk, causal=causal, block_q=1, block_k=1,
                            kind=kind)
    stream = []
    for a, b in zip(iq.tolist(), ik.tolist()):
        stream.append(("q", a))
        stream.append(("k", b))
        stream.append(("v", b))
    ids = {}
    return np.array([ids.setdefault(s, len(ids)) for s in stream])


def attention_schedule_rows(nq: int = 32, nk: int = 32, vmem_blocks: int = 24):
    out = []
    for kind in ("row_major", "morton", "hilbert"):
        t0 = time.perf_counter()
        stream = _attention_block_stream(nq, nk, kind)
        misses = simulate_lru(stream, vmem_blocks)
        dt = (time.perf_counter() - t0) * 1e6
        hbm_refetch = misses / (nq + 2 * nk)  # 1.0 = each block fetched once
        out.append((f"kernel/flash_sched_{kind}_nq{nq}", dt,
                    f"vmem_misses={misses};refetch_factor={hbm_refetch:.2f}"))
    return out


def stencil_block_rows(nt: int = 8, vmem_blocks: int = 8):
    """Stencil block walk: consecutive blocks share halos; the LRU model
    counts how often a neighbour block is still VMEM-resident. The fetch
    stream is exactly what the resident kernel's index maps emit: the
    block itself plus its -x/-y/-z face neighbours from the SFC
    neighbour table (core/neighbors.py)."""
    out = []
    lo_cols = FACE_COLS[0], FACE_COLS[2], FACE_COLS[4]  # k-, i-, j-
    for kind in ("row_major", "morton", "hilbert"):
        t0 = time.perf_counter()
        tab = neighbor_table(kind, nt)  # (nb, 27) path->path, periodic
        stream = []
        for t in range(nt ** 3):
            stream.append(int(tab[t, SELF_COL]))
            for col in lo_cols:
                stream.append(int(tab[t, col]))
        misses = simulate_lru(np.asarray(stream), vmem_blocks)
        dt = (time.perf_counter() - t0) * 1e6
        out.append((f"kernel/stencil_walk_{kind}_nt{nt}", dt,
                    f"vmem_misses={misses};min_possible={nt**3}"))
    return out


def interpret_timing_rows():
    rng = np.random.default_rng(0)
    out = []
    # stencil kernel
    blocks = jnp.asarray(rng.normal(size=(8, 10, 10, 10)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 3)).astype(np.float32))
    stencil_sum_blocks(blocks, w, g=1)  # compile
    t0 = time.perf_counter()
    for _ in range(5):
        r = stencil_sum_blocks(blocks, w, g=1)
    jax.block_until_ready(r)
    out.append(("kernel/stencil3d_interpret", (time.perf_counter() - t0) / 5 * 1e6,
                "T=8;g=1;nb=8"))
    # flash attention kernel
    q = jnp.asarray(rng.normal(size=(2, 128, 32)).astype(np.float32))
    flash_attention_fwd(q, q, q, causal=True, block_q=32, block_k=32)
    t0 = time.perf_counter()
    for _ in range(5):
        r = flash_attention_fwd(q, q, q, causal=True, block_q=32, block_k=32)
    jax.block_until_ready(r)
    out.append(("kernel/flash_attn_interpret", (time.perf_counter() - t0) / 5 * 1e6,
                "S=128;D=32;morton"))
    return out


def resident_kernel_rows(M: int = 16, T: int = 8, g: int = 1,
                         kind: str = "hilbert", S: int = 4):
    """Repack vs resident vs fused-temporal kernel on the same cube
    (interpret mode, CPU): times all three forms. The modelled per-
    substep HBM stream comes from stencil/pipeline.py's shared
    accounting helpers — the same numbers benchmarks/stencil_update.py
    reports, asserted consistent in tests/test_fused_stencil.py."""
    rng = np.random.default_rng(0)
    cube = jnp.asarray(rng.normal(size=(M, M, M)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(2 * g + 1,) * 3).astype(np.float32))
    nb = (M // T) ** 3
    out = []

    halo = blockize_with_halo(cube, T, g, kind=kind)
    stencil_sum_blocks(halo, w, g=g)  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        # the repack form rebuilds the halo store every step
        r = stencil_sum_blocks(blockize_with_halo(cube, T, g, kind=kind), w, g=g)
    jax.block_until_ready(r)
    out.append((f"kernel/stencil_repack_interpret_{kind}",
                (time.perf_counter() - t0) / 3 * 1e6,
                f"T={T};g={g};nb={nb}"
                f";hbm_items_per_substep={repack_items_per_step(M, T, g)}"))

    store = blockize(cube, T, kind=kind)
    nbr = neighbor_table(kind, M // T)
    stencil_sum_resident(store, w, nbr, g=g)  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        r = stencil_sum_resident(store, w, nbr, g=g)
    jax.block_until_ready(r)
    out.append((f"kernel/stencil_resident_interpret_{kind}",
                (time.perf_counter() - t0) / 3 * 1e6,
                f"T={T};g={g};nb={nb}"
                f";hbm_items_per_substep={resident_unfused_items_per_step(M, T, g)}"))

    # fused temporal blocking: S whole gol substeps per launch
    gw = uniform_weights(g)
    stencil_step_fused(store, gw, nbr, g=g, S=S, rule="gol")  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        r = stencil_step_fused(store, gw, nbr, g=g, S=S, rule="gol")
    jax.block_until_ready(r)
    per_sub = fused_items_per_launch(M, T, g, S) / S
    out.append((f"kernel/stencil_fused_S{S}_interpret_{kind}",
                (time.perf_counter() - t0) / 3 / S * 1e6,
                f"T={T};g={g};nb={nb};S={S};fields=1"
                f";hbm_items_per_substep={per_sub:.0f}"))

    # multi-field wave (C=2, DESIGN.md §9): same fused launch over the
    # stacked store — one grid step streams two windows, writes two tiles
    wstore = jnp.stack([store, jnp.zeros_like(store)])
    stencil_step_fused(wstore, gw, nbr, g=g, S=S, rule="wave")  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        r = stencil_step_fused(wstore, gw, nbr, g=g, S=S, rule="wave")
    jax.block_until_ready(r)
    per_sub2 = fused_items_per_launch(M, T, g, S, fields=2) / S
    out.append((f"kernel/stencil_fused_wave_S{S}_interpret_{kind}",
                (time.perf_counter() - t0) / 3 / S * 1e6,
                f"T={T};g={g};nb={nb};S={S};fields=2"
                f";hbm_items_per_substep={per_sub2:.0f}"))
    return out


def rows():
    return (attention_schedule_rows() + stencil_block_rows()
            + interpret_timing_rows() + resident_kernel_rows())

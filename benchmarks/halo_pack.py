"""Paper Figs 11 / 15: time to pack the six surfaces into buffers.

Packs from the ordering's path-ordered storage via the precomputed index
lists (the paper's mechanism), for halo widths {1, 2} and M ∈ {32, 64}.
Also reports the structural metric behind the timings: DMA-run count
(contiguous runs per face) — the TPU-side cost model, where each run is
one descriptor for kernels/sfc_gather.py.

The ``exchange/`` rows sweep the *deep* exchange depth h = S·g of the
communication-avoiding distributed pipeline (DESIGN.md §7): six width-h
faces cut from the resident block store by block slices, as the
exchange packs them, with the modelled ICI bytes per exchange and per
*timestep* from the shared accounting helpers — so the perf trajectory
carries network traffic alongside the HBM numbers.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (HILBERT, MORTON, NEUMANN0, ROW_MAJOR, apply_ordering,
                        blockize)
from repro.core.surfaces import PAPER_SURFACE_NAMES, run_stats
from repro.kernels.ops import pack_surface
from repro.stencil import exchange_bytes_per_step, exchange_items_per_exchange
from repro.stencil.halo import _face_slab

FACE_GROUPS = (("k0", "k1"), ("i0", "i1"), ("j0", "j1"))
N_REPS = 20


def rows(sizes=(32, 64), widths=(1, 2)):
    out = []
    rng = np.random.default_rng(0)
    for M in sizes:
        cube = jnp.asarray(rng.random((M, M, M)).astype(np.float32))
        for g in widths:
            for spec in (ROW_MAJOR, MORTON, HILBERT):
                data = apply_ordering(cube, spec)

                @jax.jit
                def pack_all(d, spec=spec, M=M, g=g):
                    return [pack_surface(d, spec, M, g, f)
                            for pair in FACE_GROUPS for f in pair]

                jax.block_until_ready(pack_all(data))  # compile
                t0 = time.perf_counter()
                for _ in range(N_REPS):
                    bufs = pack_all(data)
                jax.block_until_ready(bufs)
                dt = (time.perf_counter() - t0) / N_REPS
                runs = {PAPER_SURFACE_NAMES[f]: run_stats(spec, M, g, f).n_runs
                        for pair in FACE_GROUPS for f in pair}
                out.append((f"fig11_15/pack_M{M}_g{g}_{spec.name}", dt * 1e6,
                            "dma_runs=" + ",".join(f"{k}:{v}"
                                                   for k, v in runs.items())))
    out += deep_rows(sizes=sizes)
    out += clamped_exchange_rows(sizes=sizes)
    return out


def deep_rows(sizes=(32, 64), depths=(1, 2, 4), g=1, T=8):
    """Deep-exchange pack sweep: six width-S·g faces from the block store.

    Times the in-store pack the distributed pipeline runs once per S
    substeps; ``derived`` carries the modelled ICI traffic
    (exchange_items/bytes helpers — the same single accounting the
    stencil_update rows and DistributedPipeline.plan() use). Bytes per
    exchange grow with S (the corner terms), bytes per *step* stay
    nearly flat — the win is exchange frequency and HBM amortisation.
    """
    out = []
    rng = np.random.default_rng(1)
    for M in sizes:
        cube = jnp.asarray(rng.random((M, M, M)).astype(np.float32))
        for kind in ("morton", "hilbert"):
            store = blockize(cube, T, kind=kind)[None]
            for S in depths:
                h = S * g
                if h > T or T % h:
                    continue

                @jax.jit
                def pack_all(d, kind=kind, h=h):
                    return [_face_slab(d, kind, T, h, f)
                            for pair in FACE_GROUPS for f in pair]

                jax.block_until_ready(pack_all(store))  # compile
                t0 = time.perf_counter()
                for _ in range(N_REPS):
                    bufs = pack_all(store)
                jax.block_until_ready(bufs)
                dt = (time.perf_counter() - t0) / N_REPS
                out.append((
                    f"exchange/deep_pack_M{M}_g{g}_S{S}_{kind}", dt * 1e6,
                    f"h={h}"
                    f";ici_bytes_per_exchange="
                    f"{4 * exchange_items_per_exchange(M, g, S):.0f}"
                    f";ici_bytes_per_step={exchange_bytes_per_step(M, g, S):.0f}",
                ))
    return out


def clamped_exchange_rows(sizes=(32, 64), depths=(1, 4), g=1, T=8,
                          procs=(2, 2, 2)):
    """Clamped exchange surface (DESIGN.md §8): mesh-edge shards skip the
    wrap links, so they pack the same six faces (the packs also feed the
    boundary fill) but *send* fewer. Timing is the six-face in-store
    pack (identical work to the periodic row — the saving is wire-only);
    ``derived`` carries the per-shard clamped ICI model: torus vs mesh
    mean vs corner shard, from the one accounting helper set.
    """
    out = []
    rng = np.random.default_rng(2)
    for M in sizes:
        cube = jnp.asarray(rng.random((M, M, M)).astype(np.float32))
        for kind in ("morton", "hilbert"):
            store = blockize(cube, T, kind=kind)[None]
            for S in depths:
                h = S * g
                if h > T or T % h:
                    continue

                @jax.jit
                def pack_all(d, kind=kind, h=h):
                    return [_face_slab(d, kind, T, h, f)
                            for pair in FACE_GROUPS for f in pair]

                jax.block_until_ready(pack_all(store))  # compile
                t0 = time.perf_counter()
                for _ in range(N_REPS):
                    bufs = pack_all(store)
                jax.block_until_ready(bufs)
                dt = (time.perf_counter() - t0) / N_REPS
                per = 4 * exchange_items_per_exchange(M, g, S)
                mean = 4 * exchange_items_per_exchange(
                    M, g, S, bc=NEUMANN0, procs=procs)
                corner = 4 * exchange_items_per_exchange(
                    M, g, S, bc=NEUMANN0, procs=procs, coords=(0, 0, 0))
                out.append((
                    f"exchange/clamped_M{M}_g{g}_S{S}_{kind}", dt * 1e6,
                    f"h={h};bc=neumann0"
                    f";ici_bytes_per_exchange_periodic={per:.0f}"
                    f";ici_bytes_per_exchange_clamped={mean:.0f}"
                    f";ici_bytes_per_exchange_edge_shard={corner:.0f}"
                    f";ici_clamped_vs_periodic={mean / per:.3f}",
                ))
    return out

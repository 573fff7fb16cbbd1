"""Public jit'd wrappers around the Pallas kernels.

Every op has an exact pure-jnp fallback (ref.py) selected by
``use_kernel=False``. Kernels compile on a TPU and run in interpret mode
on the CPU (kernels/backend.py); the caller never picks the mode.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.orderings import OrderingSpec
from repro.core.surfaces import surface_path_indices

from . import ref
from .flash_attn import flash_attention_fwd
from .sfc_gather import gather_rows

__all__ = ["pack_surface", "unpack_surface",
           "flash_attention", "sfc_gather_take", "uniform_weights"]


@functools.lru_cache(maxsize=16)
def uniform_weights(g: int) -> np.ndarray:
    """All-ones stencil with a zero centre (neighbour count), read-only
    numpy: jit embeds it as a constant."""
    s = 2 * g + 1
    w = np.ones((s, s, s), dtype=np.float32)
    w[g, g, g] = 0.0
    w.setflags(write=False)
    return w


_ROW_PLANS: dict = {}
_ROW_PLANS_CAP = 256
# The serving thread pool and the main trace thread share this LRU —
# mutate it under the lock.
_ROW_PLANS_LOCK = threading.RLock()


def _row_plan(idx: np.ndarray, line: int, plan_key=None):
    """(unique rows covering idx, per-element position) — cached by key.

    The np.unique/searchsorted plan depends only on (idx, line); callers
    with a stable idx provenance (pack_surface: one face of one ordering)
    pass ``plan_key`` so repeated packs of the same face skip the O(|idx|
    log |idx|) host work. LRU-capped (and lock-guarded) like
    layout.device_constant; concurrent misses may both compute the plan
    (pure — benign), the dict is only touched under the lock.
    """
    key = None if plan_key is None else (plan_key, line)
    if key is not None:
        with _ROW_PLANS_LOCK:
            hit = _ROW_PLANS.get(key)
            if hit is not None:
                _ROW_PLANS[key] = _ROW_PLANS.pop(key)  # move-to-end
                return hit
    idx = np.asarray(idx)
    rows = np.unique(idx // line).astype(np.int32)
    pos = (np.searchsorted(rows, idx // line) * line + idx % line).astype(np.int32)
    rows.setflags(write=False)
    pos.setflags(write=False)
    if key is not None:  # numpy only — trace-safe to cache
        with _ROW_PLANS_LOCK:
            while len(_ROW_PLANS) >= _ROW_PLANS_CAP:
                _ROW_PLANS.pop(next(iter(_ROW_PLANS)))
            _ROW_PLANS[key] = (rows, pos)
    return rows, pos


def sfc_gather_take(data: jnp.ndarray, idx: np.ndarray, *, line: int = 64,
                    use_kernel: bool = False, interpret: bool | None = None,
                    plan_key=None) -> jnp.ndarray:
    """data[idx] for a flat array, via line-granularity kernel gather.

    Kernel path: fetch the unique ``line``-sized rows covering ``idx``
    (one scalar-prefetched DMA each), then select elements. The row count
    is the modelled HBM traffic — SFC layouts need fewer rows (paper
    Figs 11/15 re-expressed). Exact for any idx. ``plan_key`` (hashable,
    identifying idx's provenance) memoises the row plan across calls.

    The fallback path gathers along the *last* axis, so a stacked
    multi-field ``(C, M³)`` state (DESIGN.md §9) packs all channels in
    one call; the kernel path stays 1-D (per-channel).
    """
    idx = np.asarray(idx)
    if not use_kernel:
        return jnp.take(data, jnp.asarray(idx), axis=-1)
    assert data.ndim == 1, "kernel gather path is 1-D (pack per channel)"
    n = data.shape[0]
    assert n % line == 0, (n, line)
    rows, pos = _row_plan(idx, line, plan_key)
    got = gather_rows(data.reshape(n // line, line), jnp.asarray(rows),
                      interpret=interpret)
    return got.reshape(-1)[jnp.asarray(pos)]


def pack_surface(data_path: jnp.ndarray, spec: OrderingSpec, M: int, g: int,
                 face: str, *, line: int = 64, use_kernel: bool = False,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Pack one face of a path-ordered cube into a contiguous buffer.

    ``data_path`` is the (M³,) cube in ``spec`` order (apply_ordering) —
    or the stacked multi-field ``(C, M³)`` state (DESIGN.md §9), packed
    along the last axis so one call moves every channel's face. Buffer
    order is curve-visit order p_t (paper §3.2). The row plan is
    cached on (spec, M, g, face, line) across calls.

    ``g`` is the face *width* — the communication-avoiding distributed
    pipeline packs deep faces of width S·g (one exchange funds S fused
    substeps, stencil/halo.py), and packs them straight from the resident
    block store by passing ``layout.store_spec(kind, T)`` as the spec
    (the store is path-ordered state under that hybrid ordering).
    """
    idx = surface_path_indices(spec, M, g, face)
    return sfc_gather_take(data_path, idx, line=line, use_kernel=use_kernel,
                           interpret=interpret, plan_key=(spec, M, g, face))


def unpack_surface(data_path: jnp.ndarray, buf: jnp.ndarray,
                   spec: OrderingSpec, M: int, g: int, face: str) -> jnp.ndarray:
    """Inverse of pack_surface: scatter a buffer back into the cube."""
    return data_path.at[surface_path_indices(spec, M, g, face)].set(buf)


# ----------------------------------------------------------------------
# Flash attention public API (GQA folding + trainable custom_vjp)
# ----------------------------------------------------------------------

def _fold_gqa(q, k, v):
    """(B,Hq,S,D)/(B,Hkv,S,D) -> (B*Hq, S, D) with kv repeated per group."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    return (q.reshape(B * Hq, Sq, D), k.reshape(B * Hq, -1, D),
            v.reshape(B * Hq, -1, D))


def _pick_block(s: int, pref: int) -> int:
    b = min(pref, s)
    while s % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, schedule: str = "morton",
                    block_q: int = 64, block_k: int = 64):
    """Trainable flash attention. q: (B,Hq,S,D); k,v: (B,Hkv,Sk,D).

    Forward runs the SFC-scheduled Pallas kernel; backward recomputes
    through the jnp oracle (standard recompute-bwd, keeps the kernel
    forward-only).
    """
    B, Hq, Sq, D = q.shape
    qf, kf, vf = _fold_gqa(q, k, v)
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(kf.shape[1], block_k)
    o = flash_attention_fwd(qf, kf, vf, causal=causal, block_q=bq,
                            block_k=bk, schedule=schedule)
    return o.reshape(B, Hq, Sq, D)


def _fa_fwd(q, k, v, causal, schedule, block_q, block_k):
    return flash_attention(q, k, v, causal, schedule, block_q, block_k), (q, k, v)


def _fa_bwd(causal, schedule, block_q, block_k, res, g_out):
    q, k, v = res

    def ref_fn(q, k, v):
        B, Hq, Sq, D = q.shape
        qf, kf, vf = _fold_gqa(q, k, v)
        return ref.attention_ref(qf, kf, vf, causal=causal).reshape(B, Hq, Sq, D)

    _, vjp = jax.vjp(ref_fn, q, k, v)
    return vjp(g_out)


flash_attention.defvjp(_fa_fwd, _fa_bwd)

"""JAX relayout operations: apply an ordering to real arrays.

These are the executable counterparts of core/orderings.py — pure-JAX
gathers with *static* (numpy, trace-time) permutations, so XLA sees plain
gathers/reshapes and can fuse them.

The TPU-native form stores the cube as ``(n_blocks, T, T, T)`` with blocks
ordered along the curve (DESIGN.md §2): the curve ordering is then a
property of the memory layout, exactly as in the paper, and a Pallas
kernel that walks blocks sequentially walks HBM contiguously.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from .orderings import OrderingSpec, path_to_rmo, rmo_to_path, _check_pow2, _flat_index

_SIMPLE_KINDS = ("row_major", "column_major", "morton", "hilbert")

__all__ = [
    "apply_ordering", "undo_ordering", "block_order", "blockize", "unblockize",
    "blockize_fields", "unblockize_fields", "store_spec", "needs_element_perm",
]


def _blocked_spec(spec: OrderingSpec) -> bool:
    """True for a block-store ordering (``store_spec``): a simple curve
    between tiles, row-major inside — realised by reshapes and an
    nb-sized block gather instead of an M³ element permutation."""
    return (spec.kind == "hybrid" and spec.inner == "row_major"
            and spec.outer in _SIMPLE_KINDS)


def needs_element_perm(spec: OrderingSpec) -> bool:
    """True when relayout under ``spec`` gathers through an M³ element
    permutation (jit embeds it: 4 GiB of int32 at M=1024). Row- and
    column-major are a reshape and a transpose; block-store orderings
    gather nb whole blocks."""
    return spec.kind not in ("row_major", "column_major") \
        and not _blocked_spec(spec)


def apply_ordering(x: jnp.ndarray, spec: OrderingSpec) -> jnp.ndarray:
    """(..., M, M, M) canonical cubes -> (..., M³) path-ordered vectors.

    Leading axes (channels, shards) ride along. Row-/column-major are a
    reshape (and a transpose), block-store orderings (:func:`store_spec`)
    gather nb whole blocks; any other ordering gathers through its M³
    element permutation (:func:`needs_element_perm`).
    """
    M = x.shape[-1]
    if x.shape[-3:] != (M, M, M):
        raise ValueError(f"apply_ordering needs cubes, got {x.shape}")
    lead = x.shape[:-3]
    if spec.kind == "row_major":
        return x.reshape(lead + (-1,))
    if spec.kind == "column_major":
        return jnp.swapaxes(x, -1, -3).reshape(lead + (-1,))
    if _blocked_spec(spec):
        return _to_blocks(x, spec.tile, spec.outer).reshape(lead + (-1,))
    return jnp.take(x.reshape(lead + (-1,)), path_to_rmo(spec, M), axis=-1)


def undo_ordering(v: jnp.ndarray, spec: OrderingSpec, M: int) -> jnp.ndarray:
    """Inverse of :func:`apply_ordering`: (..., M³) -> (..., M, M, M)."""
    lead = v.shape[:-1]
    if spec.kind == "row_major":
        return v.reshape(lead + (M,) * 3)
    if spec.kind == "column_major":
        return jnp.swapaxes(v.reshape(lead + (M,) * 3), -1, -3)
    if _blocked_spec(spec):
        T = spec.tile
        return _from_blocks(v.reshape(lead + (-1, T, T, T)), M, spec.outer)
    return jnp.take(v, rmo_to_path(spec, M), axis=-1).reshape(lead + (M,) * 3)


@functools.lru_cache(maxsize=64)
def block_order(kind: str, nt: int) -> np.ndarray:
    """Order of T³-tile *block coordinates* along a curve.

    Returns (nt³, 3) int array: row t holds the (bk,bi,bj) visited at path
    position t by ordering ``kind`` over the nt×nt×nt block grid.
    """
    _check_pow2(nt)
    if nt == 1:  # single-block grid: every curve is trivial
        if kind not in _SIMPLE_KINDS:
            raise ValueError(f"unknown simple ordering {kind!r}")
        out = np.zeros((1, 3), dtype=np.int64)
        out.setflags(write=False)
        return out
    kk, ii, jj = np.meshgrid(*(np.arange(nt, dtype=np.uint64),) * 3, indexing="ij")
    kk, ii, jj = kk.ravel(), ii.ravel(), jj.ravel()
    pidx = _flat_index(kind, kk, ii, jj, nt).astype(np.int64)
    out = np.empty((nt ** 3, 3), dtype=np.int64)
    out[pidx, 0] = kk
    out[pidx, 1] = ii
    out[pidx, 2] = jj
    out.setflags(write=False)
    return out


@functools.lru_cache(maxsize=64)
def _block_perm(kind: str, nt: int, inverse: bool) -> np.ndarray:
    """Block permutation path↔linear (int32, read-only). Jit embeds it:
    nt³ entries, not M³."""
    bo = block_order(kind, nt)
    lin = (bo[:, 0] * nt * nt + bo[:, 1] * nt + bo[:, 2]).astype(np.int32)
    if inverse:
        inv = np.empty(nt ** 3, dtype=np.int32)
        inv[lin] = np.arange(nt ** 3, dtype=np.int32)
        lin = inv
    lin.setflags(write=False)
    return lin


def store_spec(kind: str, T: int) -> OrderingSpec:
    """The element ordering realised by the ``(nb, T, T, T)`` block store.

    ``blockize(x, T, kind).ravel()`` equals
    ``apply_ordering(x, store_spec(kind, T))`` exactly: blocks follow the
    ``kind`` curve, elements inside a block are row-major — i.e. the
    TPU-native store *is* a hybrid ordering (paper §2.3). This identity
    is what lets the surface machinery (core/surfaces.py, ops.pack_surface)
    pack halo faces straight out of the resident store: the store is just
    path-ordered state under this spec.
    """
    return OrderingSpec("hybrid", tile=T, outer=kind, inner="row_major")


def _check_blockable(M: int, T: int) -> int:
    """nt of an (M,M,M) cube split into T³ blocks — a clear error, not a
    bare assert: the layout boundary is where an elastic restore first
    meets a mismatched (M, T) target (DESIGN.md §10)."""
    nt, rem = divmod(M, T)
    if rem or nt < 1:
        raise ValueError(f"block edge T={T} does not tile cube edge M={M}")
    return nt


def _to_blocks(x: jnp.ndarray, T: int, kind: str) -> jnp.ndarray:
    """(..., M, M, M) -> (..., nb, T, T, T), blocks in ``kind`` order."""
    M = x.shape[-1]
    lead = x.shape[:-3]
    nt = _check_blockable(M, T)
    n = len(lead)
    x6 = x.reshape(lead + (nt, T, nt, T, nt, T)).transpose(
        tuple(range(n)) + tuple(n + a for a in (0, 2, 4, 1, 3, 5)))
    flat = x6.reshape(lead + (nt ** 3, T, T, T))
    return jnp.take(flat, _block_perm(kind, nt, False), axis=n)


def _from_blocks(store: jnp.ndarray, M: int, kind: str) -> jnp.ndarray:
    """Inverse of :func:`_to_blocks`: (..., nb, T, T, T) -> (..., M, M, M)."""
    lead = store.shape[:-4]
    nb, T = store.shape[-4], store.shape[-3]
    nt = _check_blockable(M, T)
    if nb != nt ** 3:
        raise ValueError(f"store has {nb} blocks, M={M}, T={T} "
                         f"implies {nt ** 3}")
    n = len(lead)
    x6 = jnp.take(store, _block_perm(kind, nt, True), axis=n)
    x6 = x6.reshape(lead + (nt,) * 3 + (T,) * 3).transpose(
        tuple(range(n)) + tuple(n + a for a in (0, 3, 1, 4, 2, 5)))
    return x6.reshape(lead + (M, M, M))


def blockize(x: jnp.ndarray, T: int, kind: str = "morton") -> jnp.ndarray:
    """(M,M,M) -> (nb, T, T, T) with blocks in ``kind`` curve order."""
    M = x.shape[0]
    if x.shape != (M, M, M):
        raise ValueError(f"blockize needs a cubic (M,M,M) state, "
                         f"got {x.shape}")
    return _to_blocks(x, T, kind)


def unblockize(blocks: jnp.ndarray, M: int, kind: str = "morton") -> jnp.ndarray:
    """Inverse of :func:`blockize`."""
    return _from_blocks(blocks, M, kind)


def blockize_fields(fields: jnp.ndarray, T: int,
                    kind: str = "morton") -> jnp.ndarray:
    """(C,M,M,M) stacked fields -> (C, nb, T, T, T) multi-field block store.

    The C-channel store of DESIGN.md §9: every channel shares **one**
    block permutation (the ``kind`` curve over the nt³ block grid), so
    the whole multi-field state is curve-ordered by a single gather and
    the per-block neighbour/boundary tables apply to all channels alike.
    A 3-D input is promoted to C=1 and returned as ``(1, nb, T, T, T)``.
    """
    if fields.ndim == 3:
        fields = fields[None]
    C, M = fields.shape[0], fields.shape[1]
    if fields.shape != (C, M, M, M):
        raise ValueError(f"blockize_fields needs (C,M,M,M) stacked "
                         f"fields, got {fields.shape}")
    return _to_blocks(fields, T, kind)


def unblockize_fields(store: jnp.ndarray, M: int,
                      kind: str = "morton") -> jnp.ndarray:
    """Inverse of :func:`blockize_fields`: (C, nb, T³) -> (C, M, M, M)."""
    return _from_blocks(store, M, kind)

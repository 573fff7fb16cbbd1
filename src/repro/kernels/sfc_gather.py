"""Pallas TPU kernel: scalar-prefetched row gather (the pack primitive).

TPU-native form of the paper's precomputed-path-list buffer packing
(paper §4): the index list is a *scalar-prefetch* operand, so the TPU can
issue the HBM→VMEM DMA for row ``idx[i]`` ahead of grid step ``i`` — the
hardware analogue of "an initial traversal ... lists of path indices".

The gather granularity is a whole row of length L (one DMA). An SFC
layout makes face packing decompose into few long runs (core/surfaces.py
run stats), so rows are large and few; a row-major layout's slab-row
faces degrade to L=1 rows — the stride-M² pathology of Figs 11/15
re-expressed as DMA count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

__all__ = ["gather_rows"]


def _copy_kernel(idx_ref, x_ref, o_ref):
    del idx_ref  # consumed by the index_map
    o_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(src: jnp.ndarray, idx: jnp.ndarray, *,
                interpret: bool | None = None) -> jnp.ndarray:
    """out[r] = src[idx[r]].  src: (N, L); idx: (R,) int32; out: (R, L)."""
    n, L = src.shape
    r = idx.shape[0]
    idx = idx.astype(jnp.int32)
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((r, L), src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r,),
            in_specs=[pl.BlockSpec((1, L), lambda i, idx_ref: (idx_ref[i], 0))],
            out_specs=pl.BlockSpec((1, L), lambda i, idx_ref: (i, 0)),
        ),
        interpret=resolve_interpret(interpret),
    )(idx, src)

"""Pallas TPU kernels (interpret-validated) + jnp oracles.

- stencil3d.py  — the fused SFC-blocked 3D stencil over the resident
                  block store (paper's compute loop, temporal-blocked)
- rules.py      — update-rule registry shared by kernels and oracles
- sfc_gather.py — scalar-prefetched row gather (paper's pack primitive)
- flash_attn.py — flash attention with Morton/Hilbert block schedule
- ops.py        — public jit'd wrappers (kernel or jnp-ref selectable)
- ref.py        — pure-jnp oracles
"""

from .ops import (  # noqa: F401
    pack_surface, unpack_surface, flash_attention, sfc_gather_take,
    uniform_weights,
)
from .rules import RULES, UpdateRule, get_rule  # noqa: F401
from .stencil3d import stencil_step_fused  # noqa: F401
from .sfc_gather import gather_rows  # noqa: F401
from .flash_attn import flash_attention_fwd, build_schedule  # noqa: F401

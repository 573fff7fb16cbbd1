"""Which way a Pallas kernel runs: compiled on a TPU, interpreted on the CPU.

The platform decides, not the caller (ROADMAP design item 2): the CPU
backend can only run a Pallas kernel through the interpreter, and a TPU
must never run the interpreter in its place, silently or on request.
"""

from __future__ import annotations

import jax

__all__ = ["on_tpu", "resolve_interpret"]


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """The ``interpret`` flag for a ``pallas_call`` on this backend.

    ``None`` (every kernel's default) means: compiled on a TPU,
    interpreted anywhere else. ``interpret=True`` on a TPU raises — the
    interpreter there would stand in for the kernel without a word.
    """
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        raise ValueError("interpret=True on a TPU backend: Pallas kernels run "
                         "compiled on the TPU (interpret mode is CPU-only)")
    return bool(interpret)

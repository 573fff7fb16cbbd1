"""JAX's persistent compilation cache, kept at one fixed place.

A cold run on the chip compiles every program; the cache lets a second
process (or a second run on the same machine) load them instead. The
cache lives in ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads
the variable itself), and in ``<checkout>/.jax_cache`` otherwise.

A compiled Pallas TPU kernel embeds the source locations of its body,
file paths included, and the cache key hashes them: the same code in
another checkout would miss. So the checkout's own path is cut from
those locations (JAX's ``jax_hlo_source_file_canonicalization_regex``)
and the key depends only on the code.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

__all__ = ["CACHE_DIR", "CHECKOUT", "enable_compile_cache"]

CHECKOUT = Path(__file__).resolve().parents[2]
CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; return its
    directory. Sets no directory when ``JAX_COMPILATION_CACHE_DIR`` is
    set; keeps a source-path regex the caller has already set."""
    import jax

    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(str(CHECKOUT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)

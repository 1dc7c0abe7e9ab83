"""Where the persistent XLA compilation cache lives.

The directory is part of the cache key's lookup, so it must be the same
on every start: `JAX_COMPILATION_CACHE_DIR` when the operator sets it
(jax reads that variable itself; nothing is set here), else
`<checkout>/.jax_cache` — never a temp name, a pid or a time. Called by
the chip-owning entry points (`python -m production_stack_tpu.engine`,
`benchmarks/chip/engine_child.py`) before their first compile.
"""

from __future__ import annotations

import os

from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)

# <checkout>/.jax_cache: the directory that holds the package (git-ignored)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place the compile cache; returns the directory in effect."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program that took a second or more to compile,
    # whatever its size
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    logger.info("compile cache: %s", cache_dir)
    return cache_dir

"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles on the chip (chip_smoke.py,
bench.py, benchmarks/run.py, tools/pipeline_tick_ab.py):
the machine decides, not the program. If ``JAX_COMPILATION_CACHE_DIR``
is set, jax reads it itself and nothing is set in code; otherwise the
cache is ``<checkout>/.jax_compile_cache``. The path is part of the
cache key, so it is never derived from a temp name, a pid or a time.

The operations' metadata is part of the key as well (jax leaves it out
by default): the named scopes of a program (``profiler.tracing.scope``,
``Layer.__call__``) live there and change nothing else, so without it a
program compiled before a scope was added would be loaded in place of
the one that carries it, and a profile of it would name nothing.
"""

from __future__ import annotations

import os

__all__ = ["configure_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache():
    """Apply the rule above; returns the directory in effect."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_compile_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

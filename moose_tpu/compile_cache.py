"""Where JAX's persistent compilation cache lives: one rule, one place.

The directory is part of the cache key, so a cache that moves never
hits.  Every entry point that wants the cache (``bench.py``, the
benchmarks, ``tests/conftest.py``, ``bin/blitzen.py``,
``chip_smoke.py``) calls :func:`enable` and nothing else touches
``jax_compilation_cache_dir``:

- where ``JAX_COMPILATION_CACHE_DIR`` is set in the environment, JAX
  has already read it and this module sets no directory at all — the
  operator (or the chip tool) placed the cache from outside;
- where it is not, the directory is ``<checkout>/.jax_cache`` (listed
  in ``.gitignore``).
"""

import os
from pathlib import Path

_CHECKOUT = Path(__file__).resolve().parent.parent


def enable(min_compile_secs: float = 1.0) -> str:
    """Turn the persistent compilation cache on and return the directory
    in use.  ``min_compile_secs`` is JAX's threshold below which a
    program is not worth an entry (serving passes 0: its bucket programs
    are exactly the small ones a restart must not recompile)."""
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs
    )
    return directory

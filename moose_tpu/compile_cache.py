"""Where JAX's persistent compilation cache lives: one rule, one place.

The directory is part of the cache key, so a cache that moves never
hits.  Every entry point that wants the cache (``chipbench``,
``tests/conftest.py``, ``bin/blitzen.py``, ``chip_smoke.py``) calls
:func:`enable` and nothing else touches ``jax_compilation_cache_dir``:

- where ``JAX_COMPILATION_CACHE_DIR`` is set in the environment, JAX
  has already read it and this module sets no directory at all — the
  operator (or the chip tool) placed the cache from outside;
- where it is not, the directory is ``<checkout>/.jax_cache`` (listed
  in ``.gitignore``).

The same directory holds what else a deployment keeps from one process
to the next about its compiled programs: the validated-jit ladder's
verdicts (``<dir>/plan_verdicts/<slot>.json``, written and matched by
``execution/interpreter._SelfCheckRunner``).  A verdict is trusted
exactly as far as the programs beside it: whoever can write this
directory can replace a compiled program, which is worse than replacing
a verdict.  Where no cache directory is configured there is no record
and no file is touched.
"""

import json
import os
from pathlib import Path
from typing import Optional

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


def plan_verdict_dir() -> Optional[Path]:
    """Where plan verdicts live: beside the compiled programs, wherever
    JAX has a persistent cache directory; ``None`` where it has none."""
    import jax

    directory = jax.config.jax_compilation_cache_dir
    return Path(directory) / "plan_verdicts" if directory else None


def read_plan_verdict(slot: str) -> Optional[dict]:
    """The record in ``slot`` as a dict, or ``None`` where there is no
    store, no file, or a file that is not one JSON object (truncated by
    a kill, or somebody else's): the caller then validates for itself
    and its first write replaces the file."""
    directory = plan_verdict_dir()
    if directory is None:
        return None
    try:
        with open(directory / f"{slot}.json", encoding="utf-8") as f:
            record = json.load(f)
    except (OSError, ValueError):
        return None
    return record if isinstance(record, dict) else None


def write_plan_verdict(slot: str, record: dict) -> bool:
    """Put ``record`` in ``slot`` atomically (written beside, renamed
    over): a reader sees the old record or the new one, and a process
    killed mid-write leaves the old one.  ``False`` where there is no
    store or the directory cannot be written (a read-only cache is a
    cache without verdicts, not an error)."""
    directory = plan_verdict_dir()
    if directory is None:
        return False
    path = directory / f"{slot}.json"
    beside = directory / f".{slot}.{os.getpid()}.tmp"
    try:
        directory.mkdir(parents=True, exist_ok=True)
        with open(beside, "w", encoding="utf-8") as f:
            json.dump(record, f, sort_keys=True)
        os.replace(beside, path)
    except OSError:
        return False
    return True

"""Where JAX's persistent compilation cache lives.

A cold run of the serving engine or the train step at real widths spends
minutes compiling, so every entry point that runs on the chip
(`chip_smoke.py`'s children, `bench.py`, the benchmark's drivers) calls
`enable_compile_cache()` first thing in its `main`, and the raylet gives
a worker leased to TPU work the same directory. Not at import: tests
import those modules and run on the CPU without a cache.

The cache is placed from outside where it can be: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
sets another directory. Otherwise it is ONE fixed directory inside the
checkout — never a temporary name, a pid or a time, because the path is
part of the cache key and a directory that moves never hits.

A process held to the CPU backend (``JAX_PLATFORMS=cpu``: every test,
every worker not leased to TPU work) gets no cache here: it has no chip
programs to keep, and a test that runs an entry point in-process must
not switch a global cache on for the rest of the suite.

The directory does not travel between installations: entries JAX wrote
without ``JAX_COMPILATION_CACHE_MAX_SIZE`` carry no ``-atime`` file, and a
JAX that has the variable set (the chip machine does) then fails every
write into a directory that holds them (`Error writing persistent
compilation cache entry … -atime`). `.chiprunignore` lists `.jax_cache` to
keep the sandbox's copy off the chip machine.
"""

from __future__ import annotations

import os
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_compile_cache_dir() -> Optional[str]:
    """The in-checkout cache directory, or None when
    ``JAX_COMPILATION_CACHE_DIR`` already places the cache or this
    process is held to the CPU backend."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    return os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Point this process's JAX at the persistent cache; returns the
    directory set here (None: nothing was set, see above)."""
    path = default_compile_cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path

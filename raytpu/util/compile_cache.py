"""Where JAX's persistent compilation cache lives.

The engine alone builds one program per prefill bucket, chunk x
table-width bucket and batch x table-width bucket; without a cache every
process compiles all of them again. Call :func:`enable` before the first
compile of every process that compiles.

``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: JAX reads
the variable itself, so where it is set this module sets no directory.
Otherwise the cache is ``<checkout>/.jax_cache``, resolved from the
package path — one fixed place for every process of every run of this
tree, never a temp name, pid or timestamp.

A process held to the CPU (``JAX_PLATFORMS=cpu``: the tests, a worker
that leased no chip) gets no cache from here. It compiles nothing worth
keeping, and XLA:CPU's loader logs an error about target machine
features for every entry it reads back.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"


def _held_to_cpu() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def default_dir() -> str:
    """``<checkout>/.jax_cache``."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def enable() -> Optional[str]:
    """Turn the persistent compilation cache on for this process and
    return its directory (``None`` in a process held to the CPU)."""
    path = os.environ.get(ENV)
    if path:
        return path
    if _held_to_cpu():
        return None
    import jax

    path = default_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def spawn_env() -> Dict[str, str]:
    """The variable for the environment of a worker process, so that it
    compiles into the same cache as its parent."""
    if _held_to_cpu() and not os.environ.get(ENV):
        return {}
    return {ENV: os.environ.get(ENV) or default_dir()}

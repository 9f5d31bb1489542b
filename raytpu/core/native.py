"""Locates the native libraries, building them from ``src/`` on first
use.

The libraries are build outputs and are not committed: a fresh checkout
has none, and the first process to need one runs the one recipe in
``src/Makefile`` (which publishes each library with a rename, so
processes starting together cannot read a half-written file). Nothing
rebuilds by timestamp — after a copy or a checkout mtimes say nothing;
run ``make -C src`` after editing the sources.
"""

from __future__ import annotations

import os
import subprocess

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC_DIR = os.path.join(_ROOT, "src")
_NATIVE_DIR = os.path.join(_ROOT, "raytpu", "_native")


def lib_path(name: str) -> str:
    """Path of ``raytpu/_native/<name>``, built if absent. Raises
    ``RuntimeError`` when it cannot be built (no ``make``, no compiler,
    no sources)."""
    path = os.path.join(_NATIVE_DIR, name)
    if os.path.exists(path):
        return path
    try:
        subprocess.run(["make", "-C", _SRC_DIR], check=True,
                       capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", None) or e
        raise RuntimeError(
            f"{name} is not built and `make -C {_SRC_DIR}` failed: "
            f"{detail}") from e
    return path

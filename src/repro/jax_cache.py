"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/``, the ``benchmarks/``
mains) call :func:`enable` before their first compile; importing the
library never touches the setting.  JAX reads ``JAX_COMPILATION_CACHE_DIR``
itself, so when it is set this sets no other directory.  Otherwise the
cache lives at one fixed, git-ignored path inside the checkout: the path
is part of what a later run must find again, so it never depends on a
temp dir, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: The in-checkout cache directory used when the environment names none.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on (every compile is kept, however
    short: Mosaic kernels compile in well under JAX's default 1 s
    threshold) and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path

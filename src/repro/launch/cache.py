"""Where JAX keeps its persistent compilation cache for the launchers."""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

#: used on a TPU when ``JAX_COMPILATION_CACHE_DIR`` is unset: one fixed path
#: inside the checkout (git-ignored), since the path is part of each key
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
    sets nothing. Otherwise a TPU process caches in ``DEFAULT_DIR``, and any
    other backend not at all (the CPU rig compiles in seconds, and its
    entries warn about machine features when loaded). Call before the first
    compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

"""Where the program's entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when it is set, wins: JAX reads it itself and
no other cache is set here.  Otherwise the cache lives at one fixed path
inside the checkout, ``<repo>/.jax_cache`` (gitignored): a directory whose
path changes between runs (a temp name, a pid, the time) would never be hit
again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[3]


def use_compile_cache(root: Path = REPO) -> str:
    """Turn the persistent compilation cache on before the first compile;
    returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path

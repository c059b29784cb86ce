"""Launchers: mesh, dry-run, train/serve drivers."""
import os
from pathlib import Path

# the checkout root (src/repro/launch/__init__.py -> three levels up)
_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else at ``.jax_cache/``
    in the checkout.  The path is part of every cache key, so it is fixed:
    a directory that moved between runs would never hit.  Entry points
    call this under their ``__main__`` guard; importing the library sets
    no cache.  Returns the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

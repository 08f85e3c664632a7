"""Where XLA's persistent compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``chipbench``'s program,
``examples/benchmark``): where ``JAX_COMPILATION_CACHE_DIR`` is set, jax
reads it itself and no code sets another; where it is not, the cache is
``<checkout>/.jax_cache`` — a fixed, git-ignored path (the path is part of
the cache key, so a directory that moves never hits).
"""
import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable():
    """Point this process — and, through the environment, the processes it
    spawns — at the cache directory; returns the directory.  Call before
    the first compile."""
    path = os.environ.get(_ENV)
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        os.environ[_ENV] = path
        jax.config.update("jax_compilation_cache_dir", path)
    return path

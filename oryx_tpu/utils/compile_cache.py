"""Where JAX's persistent compilation cache lives.

One rule, applied once by every entry point that compiles: if
`JAX_COMPILATION_CACHE_DIR` is set, whoever launched the process placed
the cache and nothing is set in code (jax reads the variable itself);
otherwise the cache is `<checkout>/.jax_cache` — a fixed path, because
the path is part of the cache's key and a directory that moves never
hits, and inside the checkout, because this repo writes nothing around
it.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """`<checkout>/.jax_cache`, whatever the working directory."""
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".jax_cache")


def configure_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path

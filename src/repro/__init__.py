"""repro: Topical Result Caching (STD cache) as a multi-pod JAX framework."""
import os

__version__ = "0.1.0"

#: the persistent compilation cache's home when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: a fixed directory inside the checkout (listed in .gitignore).
#: The path is part of the cache key, so it must never move between runs.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    other directory is set here; otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`.  Programs that compile in a second or more
    are written (the served step takes seconds to minutes at deployment
    table sizes).  Returns the directory in use.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return jax.config.jax_compilation_cache_dir

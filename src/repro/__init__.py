"""repro: multi-pod JAX framework reproducing and extending
"GPU-Based Fuzzy C-Means Clustering Algorithm for Image Segmentation"
(Almazrooie, Vadiveloo, Abdullah, 2016). See DESIGN.md."""

__version__ = "1.0.0"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it itself and nothing else is set here; otherwise the
    cache lives in the checkout's ``.jax_cache``, a fixed path, so a
    later run of the same checkout finds what an earlier one compiled.
    Entry points call this; importing the package never does."""
    import os
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path

"""utils package.  `tpch` and `trace` are lazy (PEP 562): they import
`storage.engine`, and engine-side modules import `utils.san` at module
level for the sanitizer lock factories — an eager tpch import here
would re-enter a partially-initialized engine module."""

from matrixone_tpu.utils import fault, metrics, san, sync  # noqa: F401

__all__ = ["fault", "metrics", "san", "sync", "tpch", "trace",
           "enable_compilation_cache"]

_LAZY = ("tpch", "tpch_full", "trace", "bvt", "lru", "roofline")


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f"matrixone_tpu.utils.{name}")
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def enable_compilation_cache(min_compile_seconds: float = 0.05) -> bool:
    """Turn on jax's persistent XLA compilation cache for this process.
    Where JAX_COMPILATION_CACHE_DIR is set jax already uses it and no
    other directory is set here; otherwise the cache lives at the fixed
    path `<checkout>/.jax_cache` (the path is part of the cache key, so
    it must not move between runs). MO_JAX_CACHE=0 disables. Returns
    whether the cache was enabled. Call before the first compile."""
    import os

    import jax
    if os.environ.get("MO_JAX_CACHE", "1") == "0":
        return False
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_seconds)
    return True

"""Process-level JAX set-up shared by every entry point that compiles.

Three small things that must be the same everywhere or they silently stop
working: where uncommitted work lands (:func:`default_device`), where
compiled programs persist (:func:`enable_compile_cache`), and how many
programs were built (:func:`count_compiles`). Importing this module does not
import jax — a client process that only ships tensors never pays for it.
"""

from __future__ import annotations

import os

#: fixed, git-ignored, inside the checkout: the directory is part of the
#: cache key's lookup, so a temp name, pid or timestamp would never hit
_CACHE_DIRNAME = ".jax_cache"

_counting = False


def default_device():
    """The device JAX places uncommitted work on from the calling thread:
    ``jax.default_device(...)`` when one is in force, else ``jax.devices()[0]``.
    (The context manager is thread-local — a server's worker threads see the
    process-wide default, not the one of the thread that started it.)"""
    import jax

    dev = jax.config.jax_default_device
    if dev is None:
        return jax.devices()[0]
    if isinstance(dev, str):  # a platform name is accepted too
        return jax.devices(dev)[0]
    return dev


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before first use of
    JAX. Returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` decides the place when it is set (JAX reads
    it itself; nothing is set in code). Otherwise the cache lives in
    ``<checkout>/.jax_cache``. The minimum-compile-time bar drops to 0 either
    way: the ring update/slice/kernel programs compile in well under JAX's
    1 s default and would never be kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(root, _CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def count_compiles() -> None:
    """Feed JAX's compile events into the metrics registry (idempotent):

    * ``xla_compiles`` / ``xla_compile_ms`` — executables built or loaded
      (every new program shape, persistent-cache hit or not: either way the
      caller stalled), and the time that took;
    * ``xla_cache_hits`` / ``xla_cache_misses`` — what the persistent cache
      said about them.

    A steady-state window is compile-free when ``xla_compiles`` did not move
    across it."""
    global _counting
    if _counting:
        return
    _counting = True
    from jax import monitoring

    from tpurpc.obs import metrics

    compiles = metrics.counter("xla_compiles")
    compile_ms = metrics.counter("xla_compile_ms")
    hits = metrics.counter("xla_cache_hits")
    misses = metrics.counter("xla_cache_misses")

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.inc()
            compile_ms.inc(int(secs * 1000))

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            hits.inc()
        elif event == "/jax/compilation_cache/cache_misses":
            misses.inc()

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)

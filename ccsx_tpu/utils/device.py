"""Backend selection.

The backend is whatever JAX initialises: ``JAX_PLATFORMS`` decides, and
an init error propagates.  There is no fallback — a run that asked for
the accelerator and did not get it fails, rather than carrying on
silently on the CPU.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache: fixed, so the cache key (which includes the
# path) stays stable across runs of the same checkout
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """The persistent compilation cache directory an accelerator run uses.

    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else
    ``<checkout>/.jax_cache``.
    """
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


def stable_kernel_locations() -> None:
    """Cut MLIR locations to their innermost frame.

    A Pallas kernel reaches the program as serialized MLIR with its
    debug locations, and the cache keys on it.  A location's traceback
    names the callers that first traced the kernel and the jitted
    ``jnp`` helpers it shares (a warmup thread or the dispatch thread,
    in any order), so one program got a different key in each process; the
    innermost frame alone, a line of the kernel, is the same in every
    one.  (Op names keep their scopes: they come from the name stack.)
    """
    import jax

    jax.config.update("jax_traceback_in_locations_limit", 1)


def enable_compile_cache() -> str:
    """Turn on the persistent XLA compilation cache; returns its directory.

    Batched-round shapes recur across runs, and a TPU compile costs
    seconds to minutes.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the
    directory is left to JAX; otherwise it is placed in the checkout.
    Kernel locations are cut first (stable_kernel_locations), so that a
    program's key is the same in every process.
    """
    import jax

    stable_kernel_locations()
    cache = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
    return cache


def resolve_device(requested: str = "auto") -> str:
    """Initialize JAX's backend per the request; returns the backend name.

    requested: 'auto' (whatever backend JAX initialises), 'tpu' (raise
    unless that backend is a TPU), 'cpu' (force CPU).
    """
    import jax

    # the persistent cache is enabled only on accelerator paths: XLA:CPU
    # AOT entries embed machine features and can be unsafe to reload
    # (observed "+prefer-no-scatter not supported on host" E-logs)
    if requested == "cpu":
        jax.config.update("jax_platforms", "cpu")
        return jax.default_backend()
    backend = jax.default_backend()
    if requested == "tpu" and backend != "tpu":
        raise RuntimeError(
            f"--device tpu requested but JAX initialised {backend!r}")
    if backend != "cpu":
        enable_compile_cache()
    return backend

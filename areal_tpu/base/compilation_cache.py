"""Persistent XLA compilation cache.

The generator compiles one program per (batch, prompt-bucket, window)
shape and the train engines one per packed-row shape; first compiles at
1.5B scale run 20-60 s each.  Enabling jax's persistent compilation cache
makes them one-time costs per cache directory instead of per process (the
reference leans on CUDA-graph capture being cheap; XLA's equivalent is
this cache).

The directory is part of the cache key, so it must not move between
runs: `JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX reads
the variable itself; nothing here overrides it), otherwise one fixed
directory inside the checkout.
"""

import os

from areal_tpu.base import logging

logger = logging.getLogger("compilation_cache")

DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Call before the first compilation of the process.  A directory that
    cannot be created raises.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    # Cache every compile that takes measurable time (the default
    # threshold of 1s would skip the many mid-sized decode-step programs).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    logger.info(f"persistent compilation cache at {path}")
    return path

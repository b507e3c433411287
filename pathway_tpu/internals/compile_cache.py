"""Where compiled XLA programs are kept between processes.

A fresh process compiles every program it runs (the fused ingest, one
fused search per query bucket, the packed-slab encoder, the index
scatter); JAX's persistent compilation cache lets the next process load
them instead.  The directory is part of the cache key, so it must not
move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — nothing is
    done here, and no other directory is ever set in code;
  * unset: a fixed path inside the checkout, ``<repo>/.jax_cache``
    (listed in ``.gitignore``).

Either way every program is kept, not only the slow ones: the main path
compiles some hundred small programs (scatters, slices, probes) beside
the few large ones, and with jax's default one-second threshold they made
up half of a warm start's compile time (chip run, PR 21).

Entry points (``chip_smoke.py``, ``chipbench``) call
``configure()`` before their first compile.  This module imports jax only
inside ``configure()``, so ``import pathway_tpu`` stays jax-free.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """The fixed in-checkout cache path: ``.jax_cache`` beside the
    ``pathway_tpu`` package directory."""
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_dir), ".jax_cache")


def cache_dir() -> str:
    """The directory the cache lives in for this process."""
    return os.environ.get(ENV_VAR) or default_dir()


def configure() -> str:
    """Point JAX's persistent compilation cache at ``cache_dir()`` and
    return it.  Call before the first compile of the process: JAX decides
    whether the cache is in use the first time it compiles."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path

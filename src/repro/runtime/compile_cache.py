"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/bench_serving.py``) call `enable_compile_cache` once, before
their first compile; nothing calls it at import.  The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
    this code places no other directory.
  * unset: ``<checkout>/.jax_cache`` — a fixed path (never a temp name, a
    pid or a time), because the directory is part of what a later run must
    find again.  ``.gitignore`` lists it.

Every compile is kept, however short (JAX's default skips compiles under a
second, which is most of a CPU run), unless
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise.
``LIBTPU_INIT_ARGS`` is not touched here.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

"""Persistent XLA compilation cache.

The config-#4 cycle programs take minutes to compile for the chip;
upstream kube-scheduler restarts in seconds, so a TPU scheduler that
recompiles its programs on every process start would be an operational
regression (leader failover, rolling restarts). JAX's persistent
compilation cache turns a warm restart's compile into a disk read.

Where the cache lives is decided OUTSIDE the program:

- `JAX_COMPILATION_CACHE_DIR` set: the cache is exactly that directory.
  This module sets no other.
- unset: one fixed directory inside the checkout, `<repo>/.jax_cache`
  (git-ignored). The path is part of every entry's key, so it is never
  under `~`, a temporary name, a pid or a time — a cache that moves
  never hits.

Nothing here initialises a backend: the process that calls this has not
necessarily decided to hold the chip yet.

Called from the CLI entrypoint and tests' conftest.
"""

from __future__ import annotations

import os

# <repo>/.jax_cache: utils/ -> k8s_scheduler_tpu/ -> <repo>
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def compilation_cache_dir() -> str:
    """Where the persistent cache goes (see module docstring); "" when
    `K8S_TPU_DISABLE_COMPILE_CACHE=1`. Touches neither jax nor disk."""
    if os.environ.get("K8S_TPU_DISABLE_COMPILE_CACHE") == "1":
        return ""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_compilation_cache() -> str:
    """Idempotently point JAX at the persistent on-disk compilation
    cache; returns the directory ("" when disabled)."""
    import jax

    d = compilation_cache_dir()
    if not d:
        return ""
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    # cache everything that takes real time; tiny programs stay in-memory
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    return d

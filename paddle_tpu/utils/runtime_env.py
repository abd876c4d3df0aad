"""Where a process keeps its compiled programs, and what a child
process is told about devices before its interpreter starts.

Both are decided by the environment a process is started in, so that
whoever runs the program (a test harness, the chip tool, a launcher)
can place them from outside:

* `use_compile_cache()` — JAX's persistent compilation cache lives
  where `JAX_COMPILATION_CACHE_DIR` says; only when that is unset does
  the program choose, and then a fixed directory inside the checkout
  (the path is part of the cache's key: a directory that moves never
  hits).
* `cpu_only_child_env()` — a chip belongs to one process. A child that
  must stay off it is told so through the environment it is spawned
  with, which holds from its first instruction, not from whenever its
  target function gets to run.
"""
from __future__ import annotations

import contextlib
import os
import threading

__all__ = ["CACHE_ENV", "CHECKOUT", "default_compile_cache_dir",
           "use_compile_cache", "cpu_only_child_env"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the directory that holds the package: what the program builds or
#: caches by itself goes into gitignored directories under it
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_compile_cache_dir() -> str:
    """`<checkout>/.jax_cache` (gitignored): derived from where the
    package lives, never from a temp name, a pid or the time."""
    return os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory. With `JAX_COMPILATION_CACHE_DIR` set, JAX has already
    read it and nothing is set in code."""
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    import jax
    path = default_compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_ENV_LOCK = threading.Lock()


@contextlib.contextmanager
def cpu_only_child_env():
    """Processes started inside this block inherit JAX_PLATFORMS=cpu.

    The variable is put into this process's environment only for the
    duration of the block; this process's own JAX read it at import and
    is not affected."""
    with _ENV_LOCK:
        old = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            yield
        finally:
            if old is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = old

"""JAX's persistent compilation cache at one fixed place.

A chip run compiles every program from cold unless an earlier process
left its executables in the persistent cache, and the cache only hits
when the directory is the same from run to run.  So the rule is:

  * `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and this
    module sets nothing;
  * otherwise: `<checkout>/.jax_cache` (listed in `.gitignore`) — never
    a temporary, per-process or time-stamped path.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

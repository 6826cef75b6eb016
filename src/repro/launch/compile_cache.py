"""Where JAX keeps compiled programs between runs.

A TPU compile of the sweep takes tens of seconds; JAX's persistent
compilation cache turns the next process's compile into a file read.
A cache directory that moves between runs is never found again, so the
default is a fixed path inside the checkout (``.jax_cache/``, ignored
by git), never one built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os

DEFAULT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is set here; otherwise the cache goes to :data:`DEFAULT_DIR`.
    Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

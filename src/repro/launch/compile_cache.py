"""Where JAX's persistent compilation cache lives.

Every entry point (the train and serve drivers, ``chip_smoke.py``) calls
:func:`setup_compile_cache` before its first compile, so a second run in
the same checkout reads its programs back instead of compiling them.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "setup_compile_cache"]

#: fixed in-checkout location (a moving path never hits: the directory is
#: where entries are looked up)
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return
    it.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    is left as it is; otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)

"""Where JAX keeps its persistent compilation cache for this repository's
entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``)."""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed directory inside the checkout: the cache is only found again when
# the path does not move between runs (git-ignored)
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
    alone; otherwise the cache lives in ``.jax_cache/`` at the checkout root.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)

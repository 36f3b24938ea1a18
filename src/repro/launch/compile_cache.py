"""JAX's persistent compilation cache, placed from outside or at one fixed
path in the checkout.

A cached executable is keyed on, among other things, the cache directory
itself, so a directory that moves between runs never hits.  The entry
points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``,
``kernels/autotune.py``) call :func:`enable_compile_cache` before their
first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the in-checkout cache directory used when ``JAX_COMPILATION_CACHE_DIR``
#: is not set (listed in ``.gitignore``)
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> pathlib.Path:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else at
    :data:`CHECKOUT_CACHE`, and return the directory.  Sets nothing but
    the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return pathlib.Path(path)

"""JAX's persistent compilation cache for the command-line entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and
this module changes nothing.  Otherwise the entry points keep it at the
fixed path ``<checkout>/.jax_cache``: the directory is part of each
entry's key, so a path built from a temporary directory, a pid or the
time would never hit.  Call ``enable_compile_cache`` from a CLI's main,
never at import.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: Root of the checkout (``src/repro/launch/`` is three levels below).
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already names one.  Returns the
    directory in use."""
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    path = str(CHECKOUT / '.jax_cache')
    jax.config.update('jax_compilation_cache_dir', path)
    return path

"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``bench/run.py``,
``repro.launch.serve_crypto`` and the other launchers) call
:func:`enable` once, before they compile anything; importing ``repro``
never touches the cache.

* If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  nothing here sets another path.
* Otherwise the cache goes to ``.jax_cache`` at the root of the
  checkout.  The path is fixed, never a temporary name, because it is
  part of what lets a later run find its entries again.

A cold compile of one n=4096 preset on the ``jnp`` datapath for a TPU
takes about two minutes, so a warm cache is what spares a second run
that cost.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_DIR", "enable"]

# src/repro/compile_cache.py -> the checkout root, three levels up
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

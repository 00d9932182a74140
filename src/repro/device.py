"""Where the program runs.

Two platform decisions live here and nowhere else:

* :func:`interpret_kernels` — whether the Pallas kernels are compiled by
  Mosaic (on a TPU) or run in the Pallas interpreter (everywhere else);
* :func:`enable_compile_cache` — where the entry points (``chip_smoke.py``,
  ``examples/*``, ``benchmarks/run.py``) keep JAX's persistent compilation
  cache. Library code and tests never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

# a fixed path inside the checkout: the cache is keyed by its directory, so
# a path that moved between runs would never hit
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def interpret_kernels(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel wrapper's ``interpret`` argument.

    ``None`` decides from the platform: kernels are compiled on a TPU and
    interpreted on any other backend. An explicit bool wins, which is how
    compile-only tests target a described (not attached) chip.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for an entry point and
    return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads the
    variable itself) and nothing else is configured. Otherwise the cache
    lives in ``.jax_cache`` at the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE_DIR))
    return str(_CHECKOUT_CACHE_DIR)

"""Where JAX keeps its persistent compilation cache for this checkout.

Called by the entry points that compile for the chip (``chip_smoke.py``,
``repro.launch.serve_slab`` and the benchmark scripts) before their first
compile — never at import of ``repro`` and never from the tests.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and
  this sets no other directory.
* otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``), where
  the checkout is the directory that holds ``pyproject.toml`` and the
  ``src/repro`` this module was imported from. The path is fixed — no temp name,
  pid or time in it — so a later run from the same checkout hits. An
  installed copy of ``repro`` has no checkout: it raises rather than
  writing a cache into site-packages.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def checkout_cache_dir() -> Path:
    """``<checkout>/.jax_cache`` for the checkout this module sits in."""
    src, root = Path(__file__).resolve().parents[2:4]
    if src.name == "src" and (root / "pyproject.toml").is_file():
        return root / ".jax_cache"
    raise RuntimeError(
        f"repro is not imported from a source checkout ({__file__}); set "
        f"{ENV_VAR} to choose where the compile cache goes")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    cache = str(checkout_cache_dir())
    jax.config.update("jax_compilation_cache_dir", cache)
    return cache

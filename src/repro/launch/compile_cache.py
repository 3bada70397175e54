"""Where JAX's persistent compilation cache lives.

A compiled executable is keyed, among other things, on the cache
directory's path, so the directory must not move between runs: a
temporary name, a pid or a timestamp would never hit.  The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set -- JAX reads it itself; this module
    sets no other directory.
  * otherwise -- the fixed path ``<checkout>/.jax_cache`` (git-ignored).

Entry points call ``enable_compile_cache()`` from their ``main()``; it is
never called at import, so importing the library changes no JAX state.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None
                      ) -> pathlib.Path:
    """The directory the compile cache uses under ``environ``."""
    environ = os.environ if environ is None else environ
    return pathlib.Path(environ.get(ENV_VAR) or DEFAULT_DIR)


def enable_compile_cache() -> pathlib.Path:
    """Turn the persistent compile cache on; returns its directory."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path

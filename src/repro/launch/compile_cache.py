"""Where the entry points keep JAX's persistent compilation cache.

`configure()` is called by `chip_smoke.py`, `launch/serve.py` and
`benchmarks/serve_bench.py` before their first compile, never on import.
If `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing is
changed. Otherwise the cache goes to `<checkout>/.jax_cache`: one fixed
directory (listed in `.gitignore`), so a later run from the same checkout
finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Set up the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

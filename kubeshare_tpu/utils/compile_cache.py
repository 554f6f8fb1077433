"""Where JAX's persistent compilation cache lives.

One rule for every entry point (chip_smoke.py, chipbench/run.py, the
benchmark scripts, the serving examples, tests/conftest.py): the cache is placed
from OUTSIDE.  When ``JAX_COMPILATION_CACHE_DIR`` is in the environment
JAX reads it itself and nothing here touches the config; otherwise the
cache goes to one fixed directory under the checkout.  The path is part
of the cache key's surroundings — a directory that moves between runs
(mkdtemp, pid, timestamp) never hits — so it is never derived from
anything that changes.
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache(default_dir: str = DEFAULT_CACHE_DIR
                            ) -> Optional[str]:
    """Point the persistent compilation cache at ``default_dir`` unless
    the environment already placed it.  Returns the directory this call
    set, or None when ``JAX_COMPILATION_CACHE_DIR`` decided."""
    if os.environ.get(CACHE_ENV):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir

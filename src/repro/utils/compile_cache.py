"""JAX persistent compilation cache: one fixed place per checkout.

Entry points call ``configure_compile_cache()`` at start-up (never the
library at import). Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache lives in ``.jax_cache``
at the root of the checkout — a fixed path, so a later process finds what an
earlier one compiled (the path is part of the cache key).
"""

from __future__ import annotations

import os
from pathlib import Path

from repro.utils.logging import get_logger

log = get_logger("utils.compile_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def configure_compile_cache() -> str | None:
    """Return the directory the persistent compile cache uses, or None when
    the package runs outside a checkout and the variable is unset."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    if not (CHECKOUT_ROOT / "pyproject.toml").is_file():
        log.info("no checkout root found and %s unset: no compile cache", ENV_VAR)
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)

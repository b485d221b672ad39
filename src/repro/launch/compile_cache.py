"""Where JAX keeps its persistent compilation cache for this repo's entry points.

Called from the ``main`` of ``launch/mine.py``, ``launch/serve.py`` and
``chip_smoke.py`` — never at import. The cache key includes the directory, so
the default is a fixed path inside the checkout: a second run in the same
checkout finds what the first one compiled.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache goes to ``.jax_cache/``
    at the root of the checkout.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

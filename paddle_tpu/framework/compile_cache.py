"""JAX's persistent compilation cache, placed from outside or at one fixed
path.

Entry points call :func:`enable` once before their first compile
(`chip_smoke.py`, `bench.py`, `inference/serve.py::main`,
`serving/router.py::main`, the launch workers). It is not called at
``import paddle_tpu`` and not by the tests.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# the cache's path is part of every entry's key, so the default must not
# move between runs: the checkout that holds this package, nothing from
# tempfile, the pid or the clock
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it by itself and
    this sets nothing in code; otherwise the cache lives in
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)

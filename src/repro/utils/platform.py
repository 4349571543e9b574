"""What the backend decides: the training compute dtype and the compile cache.

Nothing here runs at import; entry points call these from their ``main()``.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

# <repo>/.jax_cache: fixed, inside the checkout, listed in .gitignore. The
# cache key includes the path, so it must not move between runs.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def default_compute_dtype() -> str:
    """``'bfloat16'`` on the TPU, whose matrix units compute it natively;
    ``'float32'`` on the CPU, where the parity tests pin float32 numerics."""
    return "bfloat16" if jax.default_backend() == "tpu" else "float32"


def compute_dtype_of(name: Optional[str]):
    """The dtype a ``TrainConfig.compute_dtype`` names; ``None`` is the
    platform's (``default_compute_dtype``)."""
    return jnp.dtype(name or default_compute_dtype())


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and this
    sets nothing. Otherwise the cache goes to ``DEFAULT_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR

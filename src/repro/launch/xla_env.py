"""Process-level XLA settings for the launchers.

``force_host_devices`` edits ``XLA_FLAGS`` and must run before anything
initializes a jax backend (which locks the device count).
``configure_compile_cache`` places JAX's persistent compilation cache and
must run before the first compile.
"""
import os
from pathlib import Path

_FORCE_FLAG = "--xla_force_host_platform_device_count"

# the one cache directory used when the environment names none: fixed
# inside the checkout (and git-ignored), because the path is part of the
# cache key — a directory that moves between runs never hits
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def force_host_devices(count: int = 512) -> None:
    """Append ``--xla_force_host_platform_device_count=<count>`` to
    ``XLA_FLAGS``, preserving every flag the operator already set.  If
    the operator set a device count themselves (any value), their
    explicit choice wins and nothing is changed."""
    tokens = os.environ.get("XLA_FLAGS", "").split()
    if any(t.startswith(_FORCE_FLAG) for t in tokens):
        return
    os.environ["XLA_FLAGS"] = " ".join(tokens + [f"{_FORCE_FLAG}={count}"])


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing here changes it.  Otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)

"""Where the persistent XLA compilation cache lives.

One rule for every entry point (the CLI phases, the test suite, the
long-running scripts): when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and the program sets no directory in code; otherwise the
cache is ``<repo>/.jax_cache``.  The directory is part of XLA's cache key,
so it must not move between runs — hence one fixed path inside the
checkout and no per-machine component.
"""

from __future__ import annotations

import os

__all__ = ["ENV_VAR", "DEFAULT_DIR", "cache_dir", "enable"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def cache_dir() -> str:
    """The directory in use: the outer setting when there is one, else
    the fixed path inside the checkout."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable(jax) -> str:
    """Switch the persistent compilation cache on and return its directory.
    Programs that compile in under half a second are not worth an entry.

    Takes the jax module as an argument so importing this helper never
    imports jax (the ``--supervise`` and ``--phase route`` parents stay
    jax-free)."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir()

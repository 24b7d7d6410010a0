"""Persistent XLA compilation cache (on by default in the CLIs).

The bucketed variable-resolution configs compile one program per bucket
shape, and without a persistent cache that bill is repaid on EVERY fresh
process (resume, eval, every restart).  JAX's on-disk compilation cache
amortises it to once per (machine, jaxlib, topology): warm starts
deserialise the executable instead of recompiling.

Where the cache lives is decided OUTSIDE the program:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX itself reads it; this module
  sets no directory in code (only the thresholds below), so whoever
  provisions the machine owns the location.
* unset — one fixed path inside the checkout, ``<repo>/.jax_cache``
  (git-ignored), derived from this package's location.  The path is part
  of the cache key, so it must never come from ``~``, a temp name, a pid
  or the time: a directory that moves never hits.

The CLIs' ``--compile-cache DIR`` still names an explicit directory
(``off`` disables).  Must be called before the first compilation.
"""

from __future__ import annotations

import os
from typing import Optional

_OFF_VALUES = ("off", "none", "0", "disabled")
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """The fixed in-checkout cache path used when ``ENV_VAR`` is unset."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, ".jax_cache")


def enable_compilation_cache(cache_dir: Optional[str] = None) -> Optional[str]:
    """Turn JAX's persistent compilation cache on; returns the directory
    in effect, or None when disabled.

    cache_dir None (the CLIs' ``auto``): the directory ``ENV_VAR`` names,
    left for JAX to read — nothing here writes ``jax_compilation_cache_dir``
    — else :func:`default_cache_dir`, but only on accelerator backends:
    XLA:CPU's AOT deserialisation logs a spurious machine-feature-mismatch
    error per cache hit (and CPU compiles are not the bill this cache
    exists to kill), so the unset case skips the CPU backend.  An explicit
    directory is used as given — and refused (ValueError) while ``ENV_VAR``
    is set: no code path may place the cache anywhere else.  Any of
    "off"/"none"/"0" disables.

    Thresholds are zeroed so every program is cached — the workload's many
    per-bucket-shape programs each take seconds to compile but can fall
    under JAX's default minimum-compile-time gate on fast hosts.
    """
    import jax

    if cache_dir is not None and str(cache_dir).strip().lower() in _OFF_VALUES:
        if jax.config.jax_compilation_cache_dir:
            # the environment named a directory: "off" must still mean a
            # cold compile, or a timing of one silently reads warm
            jax.config.update("jax_enable_compilation_cache", False)
        return None
    if os.environ.get(ENV_VAR):
        if cache_dir is not None:
            raise ValueError(
                f"{ENV_VAR}={os.environ[ENV_VAR]} already places the "
                f"compile cache; drop the explicit directory {cache_dir!r} "
                f"(or unset the variable)")
        in_effect = jax.config.jax_compilation_cache_dir
    else:
        if cache_dir is None:
            if jax.default_backend() == "cpu":
                return None
            cache_dir = default_cache_dir()
        in_effect = os.path.abspath(os.path.expanduser(str(cache_dir)))
        os.makedirs(in_effect, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", in_effect)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return in_effect

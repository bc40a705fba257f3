"""Persistent XLA compilation cache — the productionized seam.

tests/conftest.py proved the disk compile cache (keyed by HLO hash)
carries the suite; this module is the one place the knobs live so the
trainer, the serving replicas, the router daemon and the soak harness
all wire it the same way:

- ``enable(dir)`` / ``enable_from_env()`` turn it on for THIS process
  via ``jax.config`` — deliberately process-local, never by mutating
  the environment: the SIGKILL chaos tests time kills against a
  spawned worker's compile-dominated startup, so a child must stay
  cold unless the parent explicitly forwards ``PADDLE_TPU_COMPILE_CACHE``
  (fleet/autopilot.py SubprocessProvisioner does, for warm fleets);
- ``"0"`` (or ``"off"``) disables — the env-var and the CLI
  ``--compile_cache`` flag share one grammar via ``resolve_dir``;
- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself keeps the
  cache there and this module sets NO directory in code — the
  operator (or the machine image) places the cache from outside;
  otherwise the default is the fixed ``<checkout>/.jax_cache`` (the
  path is part of the cache key, so a directory that moves never
  hits);
- ``disabled()`` is the scoped opt-out (tests/test_oom.py pins
  OOM-vs-freshly-compiled-executable behavior under it).

The compile cache is the warm-start layer UNDER the AOT artifact
store: artifacts skip compilation entirely; the cache bounds the cost
whenever an artifact misses (new shape plan, stale fingerprint,
serialization-incapable backend).
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from typing import Optional

__all__ = ["ENV_VAR", "JAX_ENV_VAR", "default_dir", "resolve_dir", "enable",
           "enable_from_env", "ensure_default", "disabled"]

ENV_VAR = "PADDLE_TPU_COMPILE_CACHE"

#: JAX's own variable: set, it places the cache and nothing here does
JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_disabled_lock = threading.RLock()

#: values of the env var / --compile_cache flag that mean "off"
_OFF = ("0", "off", "none", "")


def default_dir() -> str:
    """``<checkout>/.jax_cache``, from this package's own path."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)),
                        ".jax_cache")


def resolve_dir(value: Optional[str] = None,
                fallback: Optional[str] = None) -> Optional[str]:
    """One grammar for flag and env var: an explicit ``value`` wins,
    else ``PADDLE_TPU_COMPILE_CACHE``, else ``fallback``; "0"/"off"
    anywhere resolves to None (disabled)."""
    for v in (value, os.environ.get(ENV_VAR), fallback):
        if v is None:
            continue
        return None if str(v).lower() in _OFF else str(v)
    return None


def enable(value: Optional[str] = None,
           min_compile_secs: float = 0.05) -> Optional[str]:
    """Point this process's XLA compilation cache at ``resolve_dir``'s
    answer (created if missing); ``None`` answer = leave disabled.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
    answer and JAX reads it itself. Returns the directory in effect."""
    import jax
    d = resolve_dir(value, fallback=default_dir())
    if d is None:
        return None
    if os.environ.get(JAX_ENV_VAR):
        d = os.environ[JAX_ENV_VAR]
    else:
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    return d


def enable_from_env(min_compile_secs: float = 0.05) -> Optional[str]:
    """The conftest seam: env var (or the default checkout cache)
    unless the env var says off."""
    return enable(None, min_compile_secs=min_compile_secs)


def ensure_default() -> Optional[str]:
    """Opt-IN wiring for long-lived entrypoints (trainer startup, the
    C-ABI host): enable the cache only when ``PADDLE_TPU_COMPILE_CACHE``
    is set to a directory — a bare process stays cold, preserving the
    cold-start discipline chaos tests depend on."""
    d = resolve_dir(None)
    return enable(d) if d else None


@contextmanager
def disabled():
    """Scoped compile-cache OFF (reads AND writes): inside, every
    executable is freshly compiled. The OOM chaos suite races the
    allocator against compilation and must never be handed a
    deserialized executable instead, and what goes into the artifact
    store is compiled past the cache (artifacts/runtime.py). JAX asks
    its flag once and remembers the answer, so the scope forgets that
    answer on its way in and on its way out; one scope at a time (a
    thread may nest its own)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    with _disabled_lock:
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()

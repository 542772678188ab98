"""Persistent XLA compilation cache, armed from the ``"compile_cache"``
config block at ``initialize()`` and ``init_inference()``.

Every restart of a process — including the preemption restarts the
resilience subsystem makes survivable (docs/resilience.md) — pays full
XLA recompiles unless ``jax_compilation_cache_dir`` is armed
(``chip_smoke.py`` prints compile seconds per program, cold and warm).
This module is the one shared path, so library users, the benchmark,
the chip smoke and the tests exercise identical code:

    {"compile_cache": {"enabled": true,
                       "cache_dir": "/var/cache/jax",
                       "min_compile_time_secs": 1.0}}

Where the directory lives — the path is part of every cache key's
environment, so a directory that moves never hits:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this module sets NO directory in code (a config ``cache_dir`` is
  ignored); it only logs which directory is in force. Whoever placed
  the cache from outside keeps control of it.
- unset: the config's ``cache_dir``, else the fixed
  ``<checkout>/.jax_cache`` (git-ignored). Never a temp, pid- or
  time-derived path.

Cache hits/misses are observable next to the ``jax/recompiles`` counter:
``jax/compile_cache_hits`` / ``jax/compile_cache_misses`` (telemetry
registry, docs/observability.md) via the ``jax.monitoring`` events the
cache records.
"""

import os

from ..telemetry.registry import count_suppressed
from ..utils.logging import log_dist, warn_once

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# process-global: jax.config is global, so arming is too; re-arming with
# the same (directory, threshold) is a no-op and any DIFFERENT pair
# re-arms cleanly — comparing only the directory would silently keep a
# stale min-compile-time threshold
_armed = None  # (cache_dir, min_compile_time_secs) once armed


def default_cache_dir():
    """``<checkout>/.jax_cache``: beside the package, the same for every
    process that runs from this tree."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def arm_compile_cache(cache_dir=None, min_compile_time_secs=1.0):
    """Turn jax's persistent compilation cache on and return the
    directory in force: ``JAX_COMPILATION_CACHE_DIR`` when set (then no
    directory is set in code), else ``cache_dir``, else
    :func:`default_cache_dir`.

    Returns None when the directory cannot be created (the cache is an
    optimization, never a failure). Safe to call mid-process: a verdict
    jax already cached for "no cache configured" is reset so the
    directory takes effect for subsequent compiles.
    """
    global _armed
    import jax

    min_secs = float(min_compile_time_secs)
    from_env = os.environ.get(CACHE_DIR_ENV)
    if from_env:
        in_force = from_env
    else:
        in_force = os.path.abspath(
            os.path.expanduser(cache_dir or default_cache_dir())
        )
    if _armed == (in_force, min_secs):
        return in_force
    if not from_env:
        try:
            os.makedirs(in_force, exist_ok=True)
        except OSError as e:
            warn_once(
                "compile-cache-unavailable",
                "persistent compile cache unavailable: %s", e,
            )
            return None
        jax.config.update("jax_compilation_cache_dir", in_force)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
    _reset_cache_verdict()
    _armed = (in_force, min_secs)
    log_dist(
        f"persistent compile cache in force: {in_force} "
        f"({'from ' + CACHE_DIR_ENV if from_env else 'set in code'}; "
        f"min_compile_time_secs={min_secs})",
        ranks=[0],
    )
    return in_force


def disarm_compile_cache():
    """Turn the persistent cache back off (tests arm it at tmp paths that
    get deleted; leaving it armed would fail every later compile's cache
    write). A directory placed through ``JAX_COMPILATION_CACHE_DIR`` was
    never set here and is left alone."""
    global _armed
    if _armed is None:
        return
    if not os.environ.get(CACHE_DIR_ENV):
        try:
            import jax

            jax.config.update("jax_compilation_cache_dir", None)
            _reset_cache_verdict()
        except Exception as e:  # pragma: no cover - defensive
            count_suppressed("compile_cache.disarm", e)
    _armed = None


def _reset_cache_verdict():
    """jax caches its cache-enabled? verdict at the first compile; a
    process that compiled before arming needs the verdict reset or the
    new directory is silently ignored. Internal API, so best-effort."""
    try:
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()
    except Exception as e:  # pragma: no cover - jax internals moved
        count_suppressed("compile_cache.reset_verdict", e)


def configure_compile_cache(config):
    """Arm the cache from a validated DeepSpeedConfig (the ``initialize()``
    / ``init_inference()`` entry point). No-op unless the config block
    enables it."""
    if not getattr(config, "compile_cache_enabled", False):
        return None
    return arm_compile_cache(
        config.compile_cache_dir,
        min_compile_time_secs=config.compile_cache_min_compile_time_secs,
    )

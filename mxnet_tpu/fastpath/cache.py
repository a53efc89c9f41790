"""Persistent XLA compilation cache, placed from outside.

Every process restart of the pre-fastpath stack recompiled the entire
program set from scratch — minutes of XLA work to rebuild executables that
were byte-identical to yesterday's. With jax's persistent compilation cache
the first process pays the compiles and writes the executables; every later
process (restarts, elastic replacements, the second benchmark run)
deserializes them instead.

Where the cache lives is decided in exactly one of two ways:

* ``JAX_COMPILATION_CACHE_DIR`` is set — jax reads it itself and this module
  sets NO directory in code (an explicit ``configure(path)`` loses to it:
  the machine's owner placed the cache, and the directory is part of the
  cache key, so moving it would never hit);
* it is unset — ``configure()`` uses one fixed directory inside the
  checkout (``<repo>/.jax_cache``, git-ignored), ``configure(path)`` the
  given one. Never a temp dir, a pid or a timestamp.

Import-time wiring is driven by the environment only: with the variable
unset, ``import mxnet_tpu`` writes no cache anywhere (the test suite must
not grow one inside the checkout); entry points that compile for minutes
(``chip_smoke.py``, ``benchmark/run.py``) call :func:`configure` before
their first compile.

Hit/miss traffic is surfaced through the PR-3 recompile accounting:
jax's monitoring events ``/jax/compilation_cache/cache_hits`` /
``cache_misses`` increment ``mxnet_compile_cache_hits_total`` /
``mxnet_compile_cache_misses_total``, so a scrape (or the benchmark's
``compile_cache_misses``) shows whether a restart actually started warm.
"""
from __future__ import annotations

import os

from .. import telemetry
from ..base import get_env

__all__ = ["configure", "configured", "cache_counts", "DEFAULT_DIR"]

#: the one in-checkout location used when nothing outside placed the cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")



def _env_dir():
    """jax's own variable (not an ``MXNET_*`` knob): read per call so a
    test's monkeypatched environment is seen."""
    return get_env("JAX_COMPILATION_CACHE_DIR", None, str, cache=False)


_CONFIGURED = {"dir": None, "listener": False}

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def _on_event(event, **_kw):
    if event == _HIT_EVENT:
        telemetry.COMPILE_CACHE_HITS.inc()
    elif event == _MISS_EVENT:
        telemetry.COMPILE_CACHE_MISSES.inc()


def configure(path=None):
    """Enable the persistent cache and return its directory.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set (left
    to jax — nothing here overrides it), else ``path``, else
    :data:`DEFAULT_DIR`. Thresholds are zeroed so every executable is
    eligible — the point is warm restarts, not only the multi-second
    monsters."""
    import jax
    from jax._src import monitoring

    if _env_dir():
        active = jax.config.jax_compilation_cache_dir
    else:
        active = str(path or DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", active)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _CONFIGURED["listener"]:
        monitoring.register_event_listener(_on_event)
        _CONFIGURED["listener"] = True
    _CONFIGURED["dir"] = active
    return active


def configured():
    """The active cache directory, or None."""
    return _CONFIGURED["dir"]


def cache_counts():
    """(hits, misses) observed by this process — the benchmark's
    ``compile_cache_misses`` reads them over set-up."""
    return (int(telemetry.COMPILE_CACHE_HITS.value()),
            int(telemetry.COMPILE_CACHE_MISSES.value()))


# wire at import ONLY when the environment placed the cache: a restart on a
# machine that has one starts warm without anyone calling configure(), and
# a process without the variable never writes a cache it was not asked for
if _env_dir():
    configure()

"""Process-level JAX runtime facts shared by the entry points: where the
persistent compilation cache lives, and which devices the process holds.

Every CLI ``main()`` calls :func:`configure_compilation_cache` before its
first device use. The directory is part of the cache key's lookup path, so it
must be the same for every process of a run and for the next run: either the
one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself, this
module then touches nothing), or ``<checkout>/.jax_cache`` derived from where
the package sits on disk.
"""

from __future__ import annotations

import collections
import os

import jax

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# jax.monitoring event names -> occurrences in this process
_events: collections.Counter = collections.Counter()
_listening = False


def _count_event(event: str, *_duration, **_kwargs) -> None:
    _events[event] += 1


def _listen() -> None:
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_count_event)
        # a duration event hands its seconds over as a second argument
        jax.monitoring.register_event_duration_secs_listener(_count_event)
        _listening = True


def configure_compilation_cache() -> str:
    """Place the persistent compilation cache; returns its directory.

    Also starts counting JAX's own cache hit/miss monitoring events, which
    :func:`compilation_cache_stats` reports.
    """
    _listen()
    # The key covers a program's metadata too. JAX leaves it out by default,
    # and an executable compiled from another version of the step (other
    # named scopes, or none) would be served for this one with ITS op_names:
    # obs/compile.py reads the scope map of the step from the executable's
    # text, and a trace reader would set every op to the wrong scope, or to
    # none (seen on the CPU: a cache entry from before the scopes existed).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get(CACHE_DIR_ENV, "").strip()
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def compilation_cache_stats() -> dict:
    """``{"dir", "hits", "misses"}`` for this process so far. ``misses``
    counts entries written; programs under JAX's minimum compile time are
    compiled without touching the cache and appear in neither count."""
    return {
        "dir": jax.config.jax_compilation_cache_dir,
        "hits": _events["/jax/compilation_cache/cache_hits"],
        "misses": _events["/jax/compilation_cache/cache_misses"],
    }


def backend_compilations() -> int:
    """Programs this process has handed to the backend's compiler, or loaded
    from the persistent cache in its place, since the first call of this
    function or of :func:`configure_compilation_cache`: JAX records one
    duration around both. A difference of two readings counts the device
    programs a piece of code built."""
    _listen()
    return _events[BACKEND_COMPILE_EVENT]


def device_identity() -> dict:
    """The devices this process runs on, as JAX reports them. Initializes
    the backend."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }

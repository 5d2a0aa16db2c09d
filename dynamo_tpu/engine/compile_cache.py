"""Persistent XLA compilation cache + compile-event accounting.

Two halves of the compile war (ROADMAP #4):

- ``enable_compile_cache()`` turns on JAX's persistent compilation
  cache, so a restarted worker reloads its serving programs from disk
  instead of paying cold-start TTFT re-deriving them. WHERE the cache
  lives is decided outside the program: with ``JAX_COMPILATION_CACHE_DIR``
  set, JAX's own handling of that variable is the whole story; without
  it, every engine process on an accelerator uses
  ``<checkout>/.jax_cache`` — one fixed path, because the path is part
  of what makes a later process find the entries (on the CPU backend
  the cache stays off, see ``enable_compile_cache``). Thresholds are
  zeroed either way: serving programs are worth caching regardless of
  size or compile time.
- ``compile_snapshot()`` reads a process-wide compile-event counter fed
  by a ``jax.monitoring`` duration listener (``backend_compile``
  events; a program loaded from the persistent cache still counts — it
  is a shape nobody had compiled in THIS process). The engine's profiler
  exposes the delta as the ``dispatch.compile`` phase,
  ``InferenceEngine.precompile`` uses it to report compiles-per-shape at
  startup, and the precompile-coverage test asserts warmed traffic
  triggers ZERO new compiles. ``cache_snapshot()`` says how many of
  those the persistent cache answered.
"""

from __future__ import annotations

import logging
import os
import threading
from pathlib import Path

log = logging.getLogger("dynamo.engine.compile")

# <checkout>/.jax_cache: beside the package, listed in .gitignore
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")

_lock = threading.Lock()
_listener_installed = False
_cache_dir: str | None = None
_decided = False
# [count, total_secs] — mutated by the jax listeners under _events_lock
# (two threads compile during precompile)
_events: list = [0, 0.0]
_events_lock = threading.Lock()
# the same by compiling thread (the listener runs in the thread that
# compiled): precompile warms the samplers beside the model's programs
_thread_events: dict[int, list] = {}
# [persistent-cache lookups, hits]
_cache_events: list = [0, 0]


def _on_event_duration(name: str, secs: float, **_kw) -> None:
    if "backend_compile" in name:
        with _events_lock:
            _events[0] += 1
            _events[1] += secs
            mine = _thread_events.setdefault(threading.get_ident(), [0, 0.0])
            mine[0] += 1
            mine[1] += secs


def _on_event(name: str, **_kw) -> None:
    if name == "/jax/compilation_cache/compile_requests_use_cache":
        _cache_events[0] += 1
    elif name == "/jax/compilation_cache/cache_hits":
        _cache_events[1] += 1


def ensure_compile_listener() -> None:
    """Install the compile-event listeners once per process."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration
        )
        jax.monitoring.register_event_listener(_on_event)
        _listener_installed = True


def compile_snapshot() -> tuple[int, float]:
    """(compile events, total backend-compile seconds) so far. The
    listener installs lazily on first read, so deltas from a snapshot
    taken before any jit activity are complete."""
    ensure_compile_listener()
    return _events[0], _events[1]


def thread_compile_snapshot() -> tuple[int, float]:
    """``compile_snapshot()`` of the calling thread's own compiles."""
    ensure_compile_listener()
    count, secs = _thread_events.get(threading.get_ident(), (0, 0.0))
    return count, secs


def cache_snapshot() -> tuple[int, int]:
    """(persistent-cache lookups, hits) so far: hits == lookups over a
    window means every program in it came warm from disk."""
    ensure_compile_listener()
    return _cache_events[0], _cache_events[1]


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on for this process and
    return its directory (None where it stays off). Idempotent — the
    chokepoint InferenceEngine.__init__ calls, so every engine process
    (worker, follower shell, bench, smoke) shares one cache.

    On the CPU backend the default directory is NOT applied: the cache
    exists to save a chip's start-up, and this jax's CPU loader logs a
    3 KB machine-feature complaint at ERROR level for every cached
    program it loads (tens of KB of stderr for one toy-model start, into
    pipes the tests' worker processes may never drain). A
    ``JAX_COMPILATION_CACHE_DIR`` set from outside still rules there —
    that is jax's doing, not this function's."""
    global _cache_dir, _decided
    with _lock:
        if _decided:
            return _cache_dir
        import jax

        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not cache_dir and jax.default_backend() != "cpu":
            # not placed from outside (where it is, jax read the
            # variable itself at import)
            cache_dir = DEFAULT_CACHE_DIR
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        if cache_dir:
            # serving programs are worth caching regardless of size or
            # compile time — the defaults skip small/fast programs
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0
            )
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1
            )
            log.info("persistent compilation cache: %s", cache_dir)
        _cache_dir, _decided = cache_dir or None, True
        return _cache_dir

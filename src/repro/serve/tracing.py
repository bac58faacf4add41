"""Spans and the compile counter of the serving engine.

A span is a ``jax.profiler.TraceAnnotation`` named ``serve.<name>``: it
lands in the profiler's own trace, on the clock of the device's events,
and costs one check when no trace is being taken.

The counter is the process's: how many executables the engines'
``generate`` calls compiled, and in how many seconds. "Compiled" means
built by XLA or loaded from JAX's persistent compilation cache, as JAX's
``/jax/core/compile/backend_compile_duration`` event times both. One
process-wide listener of that event, registered when the first engine
is built, counts it if the compiling thread is inside a ``generate``
call (``charged``); a compile anywhere else is not counted.
"""
from __future__ import annotations

import threading

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

_local = threading.local()
_lock = threading.Lock()
_registered = False
_compiles = 0
_compile_s = 0.0


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation("serve." + name, **args)


class charged:
    """While entered, compiles on this thread are counted. Reentrant."""

    def __enter__(self) -> None:
        _local.depth = getattr(_local, "depth", 0) + 1

    def __exit__(self, *exc) -> None:
        _local.depth -= 1


def _on_duration(event: str, secs: float, **_kw) -> None:
    global _compiles, _compile_s
    if event != BACKEND_COMPILE or not getattr(_local, "depth", 0):
        return
    with _lock:
        _compiles += 1
        _compile_s += secs


def listen() -> None:
    """Register the compile listener, once per process."""
    global _registered
    with _lock:
        if not _registered:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _registered = True


def compiles() -> dict:
    """``compiles`` and ``compile_s`` of every ``generate`` call of this
    process so far."""
    with _lock:
        return {"compiles": _compiles, "compile_s": _compile_s}

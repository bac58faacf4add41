"""Spans, the compile counter and the routing counter of the serving
engine.

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

The routing counter is the process's too: for an MoE model, how many
experts got at least one row, summed over the MoE layers, in the
prefills and in the decode steps of every ``generate`` call. A call's
programs add it up on the device, and the call fetches it inside its
last ``serve.fetch`` span: the last decode step, whose token is
discarded, is not counted.
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
_routing = dict.fromkeys(("prefill_runs", "prefill_experts", "decode_runs",
                          "decode_experts", "moe_layers"), 0)


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


def count_routing(prefill_experts: int, decode_experts: int,
                  decode_runs: int, moe_layers: int) -> None:
    """Add one call's prefill and ``decode_runs`` decode steps."""
    with _lock:
        _routing["prefill_runs"] += 1
        _routing["prefill_experts"] += prefill_experts
        _routing["decode_runs"] += decode_runs
        _routing["decode_experts"] += decode_experts
        _routing["moe_layers"] = moe_layers


def routing() -> dict:
    """``prefill_runs``, ``prefill_experts``, ``decode_runs``,
    ``decode_experts`` of every ``generate`` call of this process so far,
    and ``moe_layers`` of the model they served: experts routed to, per
    layer and run, are ``decode_experts / (decode_runs * moe_layers)``."""
    with _lock:
        return dict(_routing)

"""One module per architecture: the only place the benchmark knows one.

A configuration file's ``reference`` key names the module
``chipbench.references.<reference>``, which gives:

* ``leaf_shapes(cfg)``: path -> (shape, init kind) of every weight, the
  layout that ``weights.make_weights`` fills from the seed;
* ``Dims.of(cfg)`` and ``logits(dims, weights, tokens, fp8=False)``: the
  plain float32 forward pass that ``check.compare`` replays;
* ``Shapes.of(cfg)``: the operations and bytes that the roofline and
  utilisation readers divide by (``decode_flops``, ``decode_bytes``,
  ``prefill_flops``, ``prefill_bytes``, ``params``,
  ``kv_bytes_per_token``).

A new architecture is a new module here; nothing else of the benchmark
changes for it.
"""
from __future__ import annotations

import importlib
from types import ModuleType
from typing import Any, Mapping

NAMES = ("leaf_shapes", "Dims", "logits", "Shapes")


def module_name(cfg: Mapping[str, Any]) -> str:
    return f"{__name__}.{cfg['reference']}"


def of(cfg: Mapping[str, Any]) -> ModuleType:
    """The architecture module of the configuration, or an error that
    names what it lacks."""
    mod = importlib.import_module(module_name(cfg))
    missing = [n for n in NAMES if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"architecture module {mod.__name__} lacks "
                             f"{', '.join(missing)}")
    return mod

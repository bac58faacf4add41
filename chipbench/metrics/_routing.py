"""The program's routing counter: experts routed to per MoE layer and run.

``repro.serve.tracing.routing`` is the process's count over every
``generate`` call (set-up's warm-up calls and the window's alike, all at
the cell's batch). A program without that counter, or one that counted
no MoE run of the kind asked for, reads None.
"""
from __future__ import annotations

from typing import Optional


def mean_experts(kind: str) -> Optional[float]:
    """Mean experts routed to per layer in one ``kind`` run (``prefill``
    or ``decode``)."""
    try:
        from repro.serve.tracing import routing
    except ImportError:
        return None
    r = routing()
    runs = r[f"{kind}_runs"] * r["moe_layers"]
    return r[f"{kind}_experts"] / runs if runs else None

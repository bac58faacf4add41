"""What no architecture owns in the yardstick: the chip's peaks, the
least time of a piece of work, and the operations a call needs.

An architecture's own operations and bytes are its ``Shapes``, in its
module under ``chipbench/references/``; the functions here take any
such ``Shapes``.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Mapping

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind``.

    A kind missing from ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def min_seconds(flops: float, nbytes: float, peaks: Mapping[str, Any]
                ) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def call_useful_flops(shapes: Any, batch: int, prompt: int,
                      n_new: int) -> float:
    """Operations that generating ``n_new`` tokens after a ``prompt``-long
    prefill needs: the prefill (which yields the first token) and one
    decode step for each further token."""
    total = shapes.prefill_flops(batch, prompt)
    for j in range(n_new - 1):
        total += shapes.decode_flops(batch, prompt + j + 1)
    return total


"""The serve engine's own spans in a traced run, and the decode gap split
by the step span the host was in.

The engine names its host spans ``serve.*`` (``repro.serve.tracing``);
they land in the profiler's file of the traced window, on the clock of
the device's events. The harness's ``Trace`` keeps the benchmark's own
spans only, so the engine's are read here from that file, which the
harness leaves in ``run.py``'s ``CACHE / "trace"``, and only if the file
holds the trace's first benchmark span.
A program without these spans yields none, and every reader of them
then returns None.
"""
from __future__ import annotations

import bisect
import gzip
import json
from dataclasses import asdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench import trace as tracing
from chipbench.metrics._programs import DECODE, PREFILL
from chipbench.run import CACHE

PREFIX = "serve."
TRACE_DIR = CACHE / "trace"
# where the host may be while the device idles between two decode steps
PARTS = ("fetch", "dispatch", "sample", "none")

Span = Tuple[str, float, float, dict]     # name, start_ns, end_ns, args


def program_spans(run) -> List[Span]:
    """The engine's spans of the traced window, in order of start."""
    tr = run.trace
    if tr is None:
        return []
    if getattr(tr, "program_spans", None) is None:
        tr.program_spans = load(TRACE_DIR, tr)
    return tr.program_spans


def load(trace_dir: Path, tr: tracing.Trace) -> List[Span]:
    """``serve.*`` host events of the profiler's file in ``trace_dir``,
    or none if that file is not the one ``tr`` was read from."""
    files = sorted(trace_dir.rglob("*.xplane.pb")) if trace_dir.is_dir() else []
    if not files or not tr.spans:
        return []
    from jax.profiler import ProfileData

    first = tr.spans[0]
    ours, out = False, []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
                elif e.name == first[0] and e.start_ns == first[1]:
                    ours = True
    return sorted(out, key=lambda s: s[1]) if ours else []


def dump(tr: tracing.Trace, spans: List[Span], path: Path) -> None:
    """``Trace.dump``'s file with the engine's spans beside the trace."""
    with gzip.open(path, "wt") as f:
        json.dump(dict(asdict(tr), program_spans=spans), f,
                  separators=(",", ":"))


def read(path: Path) -> tracing.Trace:
    """A ``Trace`` with its ``program_spans`` (none in a file without)."""
    tr = tracing.Trace.read(path)
    with gzip.open(path, "rt") as f:
        spans = json.load(f).get("program_spans", [])
    tr.program_spans = [(n, s, e, a) for n, s, e, a in spans]
    return tr


def gap_split(run) -> Optional[Dict[str, float]]:
    """Device idle between consecutive ``jit_decode_step`` runs of one
    call, in ms per decode step (the walk of ``decode_gap_ms``), by what
    the host was in: ``serve.fetch``, ``serve.dispatch``,
    ``serve.sample``, or ``none`` of them. The parts sum to
    ``decode_gap_ms``."""
    spans = program_spans(run)
    if not spans:
        return None
    tr = run.trace
    inside = {p: tracing.merge([(s, e) for n, s, e, _ in spans
                                if n == PREFIX + p]) for p in PARTS[:3]}
    starts = {p: [s for s, _ in ivs] for p, ivs in inside.items()}
    idle = dict.fromkeys(PARTS, 0.0)
    steps, prev = 0, None
    for name, s, e in tr.programs(0):
        if name == DECODE:
            steps += 1
            if prev is not None:
                rest = (s - prev) - tr.busy_ns(0, prev, s)
                for p, ivs in inside.items():
                    part = _idle_in(tr, ivs, starts[p], prev, s)
                    idle[p] += part
                    rest -= part
                idle["none"] += rest
            prev = e
        elif name == PREFILL:
            prev = None           # a new call: its first step has no gap
    if not steps:
        return None
    return {p: v / steps / 1e6 for p, v in idle.items()}


def _idle_in(tr: tracing.Trace, ivs, starts, lo: float, hi: float) -> float:
    """Device idle time in [lo, hi] that falls inside the intervals."""
    total = 0.0
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(ivs) and ivs[i][0] < hi:
        a, b = max(ivs[i][0], lo), min(ivs[i][1], hi)
        if b > a:
            total += (b - a) - tr.busy_ns(0, a, b)
        i += 1
    return total

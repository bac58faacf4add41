"""Device trace of a traced run, reduced to what the metrics read.

``capture`` records a profiler trace; ``load`` reads the ``.xplane.pb``
with ``jax.profiler.ProfileData`` into a ``Trace``: per device, the
program (``XLA Modules``) events and the union of the op (``XLA Ops``)
intervals; the device time of each op by name (self time, so a loop op
does not count its body twice); and the benchmark's own host spans
(``chipbench.*``). A ``Trace`` round-trips through JSON, so a recorded
one can be kept and read again by the tests.
"""
from __future__ import annotations

import bisect
import contextlib
import gzip
import json
import re
import shutil
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

SPAN_PREFIX = "chipbench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]          # start_ns, end_ns
Event = Tuple[str, float, float]        # name, start_ns, end_ns


@dataclass
class Trace:
    modules: List[List[Event]] = field(default_factory=list)   # per device
    busy: List[List[Interval]] = field(default_factory=list)   # per device
    op_self_ns: Dict[str, float] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    # ---- persistence -------------------------------------------------------
    def dump(self, path: Path) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(asdict(self), f, separators=(",", ":"))

    @classmethod
    def read(cls, path: Path) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(
            modules=[[tuple(e) for e in dev] for dev in d["modules"]],
            busy=[[tuple(i) for i in dev] for dev in d["busy"]],
            op_self_ns=d["op_self_ns"],
            spans=[tuple(e) for e in d["spans"]])

    # ---- queries -------------------------------------------------------------
    def window(self) -> Optional[Interval]:
        """From the start of the first benchmark call to the end of the
        last, on the trace's clock."""
        calls = [s for s in self.spans if s[0] == SPAN_PREFIX + "call"]
        if not calls:
            return None
        return min(s[1] for s in calls), max(s[2] for s in calls)

    def busy_ns(self, device: int, lo: float, hi: float) -> float:
        """Time in [lo, hi] in which some op ran on ``device``."""
        return self._busy_before(device, hi) - self._busy_before(device, lo)

    def _busy_before(self, device: int, t: float) -> float:
        if not hasattr(self, "_index"):
            self._index = []
            for busy in self.busy:
                starts = [s for s, _ in busy]
                done = [0.0]
                for s, e in busy:
                    done.append(done[-1] + (e - s))
                self._index.append((starts, done))
        starts, done = self._index[device]
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0.0
        s, e = self.busy[device][i]
        return done[i] + min(e, t) - s

    def programs(self, device: int = 0) -> List[Event]:
        """Every program run on ``device``, in order (none off the TPU)."""
        return self.modules[device] if device < len(self.modules) else []

    def module_events(self, name: str, device: int = 0) -> List[Event]:
        return [m for m in self.programs(device) if m[0] == name]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Device time of each op by name, less the ops nested inside it."""
    totals: Dict[str, float] = {}
    stack: List[List] = []       # [name, end, child_ns]

    def close(frame):
        name, end, start, child = frame
        totals[name] = totals.get(name, 0.0) + (end - start) - child

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, e, s, 0.0])
    while stack:
        close(stack.pop())
    return totals


def _in_programs(ops: Sequence[Event], mods: Sequence[Event]
                 ) -> List[Event]:
    """Name each op ``<program>/<instruction>``: the HLO text of an op
    event is cut to its instruction name, and prefixed with the program
    whose run holds it, since instruction names repeat across programs."""
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][0] if i >= 0 and mods[i][2] >= e else "?"
        out.append((f"{prog}/{name.split(' = ')[0].lstrip('%')}", s, e))
    return out


@contextlib.contextmanager
def capture(trace_dir: Path) -> Iterator[None]:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(trace_dir: Path) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(trace_dir.rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = ProfileData.from_file(str(files[-1]))
    tr = Trace()
    ops: List[Event] = []
    for plane in prof.planes:
        if _DEVICE_PLANE.match(plane.name):
            mods: List[Event] = []
            dev_ops: List[Event] = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += [(_SUFFIX.sub("", e.name), e.start_ns, e.end_ns)
                             for e in line.events]
                elif line.name == "XLA Ops":
                    dev_ops += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            mods.sort(key=lambda m: m[1])
            tr.modules.append(mods)
            tr.busy.append(merge([(s, e) for _, s, e in dev_ops]))
            ops += _in_programs(dev_ops, mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans += [(e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX)]
    tr.spans.sort(key=lambda s: s[1])
    tr.op_self_ns = _self_times(ops)
    return tr


# ---- what every traced run reports beside its metrics -------------------
def device_times(tr: Trace) -> Tuple[float, float]:
    """(busy_s averaged over devices, window_s) of the traced window."""
    lo, hi = tr.window()
    busy = sum(tr.busy_ns(d, lo, hi) for d in range(len(tr.busy)))
    return busy / max(len(tr.busy), 1) / 1e9, (hi - lo) / 1e9


def breakdown(tr: Trace, top: int = 10) -> Dict[str, list]:
    """The device ops that took most time, and the idle time of the
    window grouped by what the host and the device were doing around
    each gap: ``<host span>: <program before> > <program after>``, or
    ``inside <program>`` for a gap between the ops of one program."""
    ops = sorted(tr.op_self_ns.items(), key=lambda kv: -kv[1])[:top]
    lo, hi = tr.window()
    span_starts = [s[1] for s in tr.spans]
    idle: Dict[str, float] = {}
    for dev, busy in enumerate(tr.busy):
        mods = tr.modules[dev]
        starts = [m[1] for m in mods]
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            i = bisect.bisect_right(starts, a) - 1      # last started by a
            if i >= 0 and mods[i][2] >= b:
                label = f"inside {mods[i][0]}"
            else:
                j = bisect.bisect_right(span_starts, (a + b) / 2) - 1
                host = (tr.spans[j][0][len(SPAN_PREFIX):]
                        if j >= 0 and tr.spans[j][2] >= (a + b) / 2
                        else "none")
                k = bisect.bisect_left(starts, b)         # first after
                label = (f"{host}: {mods[i][0] if i >= 0 else 'start'} > "
                         f"{mods[k][0] if k < len(mods) else 'end'}")
            idle[label] = idle.get(label, 0.0) + (b - a)
    n = max(len(tr.busy), 1)
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / n / 1e9] for k, v in gaps]}

"""One run of one cell: set-up, the measured (or traced) window, the
check against the reference, and the metrics of the result line."""
from __future__ import annotations

import gc
import importlib
import importlib.util
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax

from chipbench import check, references, trace as tracing
from chipbench.spec import Cell
from chipbench.weights import make_weights


@dataclass
class RunRecord:
    """What the metric readers read."""
    calls: List[Any]              # the window's calls, in order
    setup_s: float
    window_s: float               # host clock, first call start to last end
    shapes: Any                   # the architecture's Shapes
    peaks: Optional[Dict[str, Any]]
    trace: Optional[tracing.Trace]


class CompileCounter:
    """Counts the programs compiled or loaded from the cache while on."""

    def __init__(self) -> None:
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event: str, **_kw) -> None:
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def reader(name: str):
    """``read`` of ``chipbench/metrics/<name>.py`` (a name may hold dots)."""
    path = Path(__file__).resolve().parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(specs: List[Dict[str, Any]], rec: RunRecord
                 ) -> Dict[str, Dict[str, Any]]:
    """Each metric from its reader; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in specs:
        value = reader(m["name"])(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t0: float, trace_dir: Path,
             peaks: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    cfg, traffic = cell.config, cell.traffic
    marks = [("start", time.perf_counter())]
    weights = make_weights(cfg, seed, cfg["serve_dtype"])
    jax.block_until_ready(weights)
    marks.append(("weights", time.perf_counter()))
    system = importlib.import_module(
        f"chipbench.adapters.{cfg['adapter']}").System(cfg, traffic, weights)
    marks.append(("system", time.perf_counter()))
    drv_mod = importlib.import_module(f"chipbench.drivers.{traffic['driver']}")
    driver = drv_mod.Driver(traffic, cfg["vocab_size"], seed)
    driver.warm_up(system.generate)
    marks.append(("warm-up", time.perf_counter()))
    print("setup (s): " + ", ".join(
        f"{name} {t - prev:.3f}" for (name, t), prev
        in zip(marks, [t0] + [t for _, t in marks])), file=sys.stderr)

    counter = CompileCounter()
    counter.on = True
    tr = None
    if traced:
        with tracing.capture(trace_dir):
            calls = driver.run(system.generate, 0.0, max_rounds=1)
            drv_mod.settle()
        tr = tracing.load(trace_dir)
        tr.dump(trace_dir / "trace.json.gz")
    else:
        calls = driver.run(system.generate, seconds)
    counter.on = False
    counter.close()
    print(f"compiles in window: {counter.n}", file=sys.stderr)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    rec = RunRecord(calls=calls, setup_s=calls[0].t_start - t0,
                    window_s=calls[-1].t_end - calls[0].t_start,
                    shapes=references.of(cfg).Shapes.of(cfg),
                    peaks=peaks, trace=tr)
    if tr is not None:
        device["busy_s"], device["window_s"] = tracing.device_times(tr)

    del system
    gc.collect()
    failed = check.failed_requests(calls, cfg["vocab_size"])
    picks = check.sample(calls, int(traffic["check_requests"]), seed)
    t_ref = time.perf_counter()
    got = check.compare(cfg, weights, calls, picks, int(traffic["max_seq"]))
    print(f"reference: {len(picks)} requests, {got['tokens_compared']} "
          f"tokens, {time.perf_counter() - t_ref:.1f} s", file=sys.stderr)

    correct, checks = check.verdict(got, cell.limits, failed)
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": sum(c.batch for c in calls),
        "failed": failed,
        "metrics": read_metrics(cell.per_layer if traced else cell.end_to_end,
                                rec),
        "device": device,
    }
    if tr is not None:
        result["breakdown"] = tracing.breakdown(tr)
    result["checks"] = checks
    return result

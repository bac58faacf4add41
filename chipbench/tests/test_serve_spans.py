"""The readers of the serve engine's spans and counters: the decode gap
split by the host's step span, on hand-made traces whose answers are
known, on the CPU profiler's own file, and on recorded chip traces."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from chipbench import trace
from chipbench.metrics import (_serve_spans, decode_gap_ms, gap_dispatch_ms,
                               gap_fetch_ms, gap_sample_ms, setup_compile_s)
from chipbench.tests.test_trace_reduce import RECORDED, hand_trace, run_of

MS = 1e6
DATA = Path(__file__).resolve().parent / "data"
GAPS = {"gap_fetch_ms": gap_fetch_ms, "gap_dispatch_ms": gap_dispatch_ms,
        "gap_sample_ms": gap_sample_ms}


def spanned_trace():
    """``hand_trace``'s decode gaps (13-14 ms with a 0.25 ms program at
    13.5, and 16-17 ms) under the host's step spans of two tokens."""
    tr = hand_trace()
    steps = [(13.0, 13.4, 13.9, 14.0), (16.0, 16.2, 16.8, 16.95)]
    tr.program_spans = []
    for i, (a, b, c, d) in enumerate(steps):
        tr.program_spans += [("serve.sample", a * MS, b * MS, {"step": i}),
                             ("serve.fetch", b * MS, c * MS, {"step": i}),
                             ("serve.dispatch", c * MS, d * MS, {"step": i})]
    return tr


def test_the_gap_splits_by_the_hosts_step_span():
    run = run_of(spanned_trace())
    # fetch: 13.4-13.5 and 13.75-13.9 idle, then 16.2-16.8
    assert gap_fetch_ms.read(run) == pytest.approx((0.1 + 0.15 + 0.6) / 3)
    assert gap_dispatch_ms.read(run) == pytest.approx((0.1 + 0.15) / 3)
    # sample 13.0-13.4 and 16.0-16.2, and 16.95-17.0 in no step span
    assert gap_sample_ms.read(run) == pytest.approx((0.4 + 0.2 + 0.05) / 3)
    assert _serve_spans.gap_split(run)["none"] == pytest.approx(0.05 / 3)
    total = sum(m.read(run) for m in GAPS.values())
    assert total == pytest.approx(decode_gap_ms.read(run), rel=1e-12)


def test_a_trace_without_the_engines_spans_reads_none(tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(_serve_spans, "TRACE_DIR", tmp_path)
    run = run_of(hand_trace())
    assert all(m.read(run) is None for m in GAPS.values())
    assert run_of(None).trace is None
    assert gap_fetch_ms.read(run_of(None)) is None


def test_program_spans_round_trip(tmp_path):
    tr = spanned_trace()
    _serve_spans.dump(tr, tr.program_spans, tmp_path / "t.json.gz")
    back = _serve_spans.read(tmp_path / "t.json.gz")
    assert back.program_spans == tr.program_spans
    assert back.modules == tr.modules and back.spans == tr.spans
    # the file is still a Trace's file
    assert trace.Trace.read(tmp_path / "t.json.gz").busy == tr.busy
    for m in GAPS.values():
        assert m.read(run_of(back)) == m.read(run_of(tr))


def test_the_old_recorded_trace_reads_no_spans_and_its_readings():
    tr = _serve_spans.read(DATA / "qwen2-0.5b.chat.trace.json.gz")
    assert tr.program_spans == []
    run = run_of(tr)
    for name, want in RECORDED.items():
        mod = __import__(f"chipbench.metrics.{name}", fromlist=["read"])
        assert mod.read(run) == want, name
    assert all(m.read(run) is None for m in GAPS.values())


def test_spans_are_read_from_the_profilers_file_of_the_same_trace(
        tmp_path, monkeypatch):
    from repro.configs import SMOKES
    from repro.serve.engine import ServeEngine

    eng = ServeEngine(SMOKES["qwen2-0.5b"], max_seq=32)
    prompts = np.zeros((2, 6), np.int32)
    eng.generate(prompts, 3)
    with trace.capture(tmp_path / "t"):
        with jax.profiler.TraceAnnotation("chipbench.call"):
            eng.generate(prompts, 3)
    tr = trace.load(tmp_path / "t")
    monkeypatch.setattr(_serve_spans, "TRACE_DIR", tmp_path / "t")
    names = [s[0] for s in _serve_spans.program_spans(run_of(tr))]
    assert names == ["serve.sample", "serve.fetch", "serve.dispatch"] * 3
    # another trace's file is not read
    assert _serve_spans.program_spans(run_of(hand_trace())) == []


def test_setup_compile_s_reads_the_engines_compile_seconds(monkeypatch):
    from repro.configs import SMOKES
    from repro.serve import tracing
    from repro.serve.engine import ServeEngine

    ServeEngine(SMOKES["qwen2-0.5b"], max_seq=24).generate(
        np.zeros((5, 4), np.int32), 2)
    got = setup_compile_s.read(run_of(None))
    assert got == tracing.compiles()["compile_s"] > 0
    monkeypatch.setitem(sys.modules, "repro.serve.tracing", None)
    assert setup_compile_s.read(run_of(None)) is None


# Recorded on one TPU v5e ("TPU v5 lite"): the traced round of
# qwen2-0.5b.chat, seed 3400000003, by
# ``dump(Trace.read(d / "trace.json.gz"), load(d, ...), out)`` over the
# run's ``.chipbench_cache/trace``; the run printed these readings.
RECORDED_SPANS = {"decode_gap_ms": 2.0659591309523813,
                  "gap_fetch_ms": 1.2764541726190475,
                  "gap_dispatch_ms": 0.7824828839285715,
                  "gap_sample_ms": 0.007022074404761905}


def test_readers_reproduce_a_recorded_chip_trace_with_spans():
    run = run_of(_serve_spans.read(
        DATA / "qwen2-0.5b.chat.spans.trace.json.gz"))
    for name, want in RECORDED_SPANS.items():
        mod = __import__(f"chipbench.metrics.{name}", fromlist=["read"])
        assert mod.read(run) == want, name
    # the host's clock and the device's agree: the idle between decode
    # steps falls inside the engine's step spans
    split = _serve_spans.gap_split(run)
    assert split["none"] <= 0.01 * sum(split.values())

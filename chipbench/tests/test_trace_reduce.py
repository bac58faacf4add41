"""The trace reducers on a hand-made trace whose answers are known."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from chipbench import counts, trace
from chipbench.metrics import (decode_gap_ms, decode_step_ms, idle_share,
                               prefill_ms)

MS = 1e6


def hand_trace():
    # one call: a 10 ms prefill, then 3 decode steps of 2 ms with host gaps
    # of 1 ms, inside which a 0.25 ms argmax program runs
    mods = [("jit_prefill", 0, 10 * MS),
            ("jit_decode_step", 11 * MS, 13 * MS),
            ("jit_argmax", 13.5 * MS, 13.75 * MS),
            ("jit_decode_step", 14 * MS, 16 * MS),
            ("jit_decode_step", 17 * MS, 19 * MS)]
    busy = trace.merge([(s, e) for _, s, e in mods])
    spans = [("chipbench.call", -1 * MS, 20 * MS)]
    return trace.Trace(modules=[mods], busy=[busy], op_self_ns={},
                       spans=spans)


def run_of(tr):
    return SimpleNamespace(trace=tr, calls=[], peaks=None, shapes=None)


def test_step_gap_and_idle_from_a_known_trace():
    run = run_of(hand_trace())
    assert decode_step_ms.read(run) == pytest.approx(2.0)
    assert prefill_ms.read(run) == pytest.approx(10.0)
    # gaps 1 ms - 0.25 ms busy, and 1 ms; over 3 steps
    assert decode_gap_ms.read(run) == pytest.approx(1.75 / 3)
    # window 21 ms, busy 10 + 6 + 0.25
    assert idle_share.read(run) == pytest.approx(100 * (1 - 16.25 / 21))


def test_nested_ops_count_self_time():
    got = trace._self_times([("while", 0, 10), ("dot", 2, 5), ("add", 6, 7)])
    assert got == {"while": 6, "dot": 3, "add": 1}


def test_busy_lookup_matches_a_plain_sum():
    tr = hand_trace()
    for lo, hi in [(0, 25 * MS), (12 * MS, 14.1 * MS), (13.6 * MS, 13.7 * MS)]:
        plain = sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in tr.busy[0])
        assert tr.busy_ns(0, lo, hi) == pytest.approx(plain)


def test_breakdown_labels_gaps_by_host_span_and_programs():
    bd = trace.breakdown(hand_trace())
    labels = dict(bd["idle_gaps"])
    assert labels["call: jit_decode_step > jit_decode_step"] == pytest.approx(
        1e-3)
    assert labels["call: start > jit_prefill"] == pytest.approx(1e-3)


def test_round_trip(tmp_path):
    tr = hand_trace()
    tr.dump(tmp_path / "t.json.gz")
    back = trace.Trace.read(tmp_path / "t.json.gz")
    assert back.modules == tr.modules and back.busy == tr.busy
    assert decode_gap_ms.read(run_of(back)) == decode_gap_ms.read(run_of(tr))


def test_roofline_never_counts_more_than_the_chip_can_do():
    # a step that took exactly its least time reads 100%
    from chipbench.metrics import decode_roofline
    import json
    from pathlib import Path
    cfg = json.loads((Path(__file__).resolve().parents[1] / "configs"
                      / "qwen2-0.5b.json").read_text())
    sh = counts.Shapes.of(cfg)
    peaks = counts.peaks_for("TPU v5 lite")
    call = SimpleNamespace(batch=32, prompt_len=128, n_new=2)
    t = [counts.min_seconds(sh.decode_flops(32, c), sh.decode_bytes(32, c),
                            peaks) * 1e9 for c in (129, 130)]
    mods = [("jit_decode_step", 0.0, t[0]),
            ("jit_decode_step", t[0] + 5, t[0] + 5 + t[1])]
    tr = trace.Trace(modules=[mods], busy=[trace.merge(
        [(s, e) for _, s, e in mods])], spans=[])
    run = SimpleNamespace(trace=tr, calls=[call], peaks=peaks, shapes=sh)
    assert decode_roofline.read(run) == pytest.approx(100.0)


# Recorded on one TPU v5e ("TPU v5 lite"): one traced round of nine
# calls of qwen2-0.5b at batch 32, prompts 128-512, 32-128 new tokens,
# seed 2500000003; the run printed these readings.
RECORDED = {"decode_step_ms": 3.7045540967261905,
            "decode_gap_ms": 2.0569164836309524,
            "idle_share": 31.42837242935319}


def test_reducers_reproduce_a_recorded_chip_trace():
    from pathlib import Path
    path = (Path(__file__).resolve().parent / "data"
            / "qwen2-0.5b.chat.trace.json.gz")
    run = run_of(trace.Trace.read(path))
    for name, want in RECORDED.items():
        mod = __import__(f"chipbench.metrics.{name}", fromlist=["read"])
        assert mod.read(run) == want, name


def test_ops_are_named_by_program_and_instruction():
    mods = [("jit_decode_step", 0, 10), ("jit__argmax", 12, 14)]
    ops = [("%fusion.1 = bf16[8]{0} fusion(...)", 1, 3),
           ("%fusion.1 = s32[8]{0} reduce(...)", 12, 13),
           ("copy.2", 10.5, 11)]
    got = [n for n, _, _ in trace._in_programs(ops, mods)]
    assert got == ["jit_decode_step/fusion.1", "jit__argmax/fusion.1",
                   "?/copy.2"]

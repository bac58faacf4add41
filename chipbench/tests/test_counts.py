"""The yardstick's counts against totals worked by hand, and the peaks."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import counts, references

ROOT = Path(__file__).resolve().parents[2]


def shapes(name):
    cfg = json.loads(
        (ROOT / "chipbench" / "configs" / f"{name}.json").read_text())
    return references.of(cfg).Shapes.of(cfg)


@pytest.mark.parametrize("name, params, kv_bytes", [
    # 151936*896 + 24*(896*(896+2*128) + 896*896 + 3*896*4864
    #                  + (896+2*128) + 2*896) + 896
    ("qwen2-0.5b", 494_032_768, 12_288),        # 24 * 2 * 2 * 64 * 2
    # 2*151936*2048 + 24*(4*2048^2 + 3*2048*5504 + 3*2048 + 2*2048) + 2048
    ("qwen1.5-1.8b", 1_836_828_672, 196_608),   # 24 * 2 * 16 * 128 * 2
])
def test_params_and_kv_bytes(name, params, kv_bytes):
    s = shapes(name)
    assert s.params == params
    assert s.kv_bytes_per_token == kv_bytes


def test_decode_counts_read_weights_and_live_context():
    s = shapes("qwen2-0.5b")
    weights = 2 * 494_032_768
    assert s.decode_bytes(32, 300) == weights + 32 * 300 * 12_288 + 32 * 896 * 2
    # one more position of context costs one KV entry per row, nothing else
    assert s.decode_bytes(32, 301) - s.decode_bytes(32, 300) == 32 * 12_288
    matmul = 494_032_768 - 24 * (896 + 2 * 128 + 2 * 896) - 896
    attn = 4 * 24 * 14 * 64 * 300
    assert s.decode_flops(1, 300) == 2 * matmul + attn


def test_prefill_counts_are_causal_with_last_position_logits():
    s = shapes("qwen2-0.5b")
    layer_mm = 896 * (896 + 2 * 128) + 896 * 896 + 3 * 896 * 4864
    b, t = 2, 128
    want = (2 * b * t * 24 * layer_mm
            + 4 * 24 * 14 * 64 * b * t * (t + 1) / 2
            + 2 * b * 896 * 151936)
    assert s.prefill_flops(b, t) == pytest.approx(want, rel=1e-12)


def test_useful_flops_leave_out_the_discarded_last_step():
    s = shapes("qwen1.5-1.8b")
    got = counts.call_useful_flops(s, 4, 256, 3)
    assert got == (s.prefill_flops(4, 256) + s.decode_flops(4, 257)
                   + s.decode_flops(4, 258))


def test_min_seconds_takes_the_larger_bound():
    p = counts.peaks_for("TPU v5 lite")
    assert counts.min_seconds(197e12, 1.0, p) == pytest.approx(1.0)
    assert counts.min_seconds(1.0, 819e9, p) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks_for("cpu")

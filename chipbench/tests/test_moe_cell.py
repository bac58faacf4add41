"""The Qwen2-MoE architecture module, its adapter and its readers: the
counts hold every weight, a tiny configuration runs through
``harness.run_cell`` on the CPU and is correct, an altered token is seen
by the check, and each MoE reader reads a value from a run that has the
program's routing counter and the kernel's ops, and None without."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import check, harness, references
from chipbench.adapters import repro_serve_engine_moe as adapter
from chipbench.drivers import static_batch
from chipbench.spec import Cell
from chipbench.trace import Trace
from chipbench.weights import make_weights, weight_shapes

ROOT = Path(__file__).resolve().parents[2]
CONFIG = ROOT / "chipbench" / "configs" / "qwen1.5-moe-a2.7b.json"
SEED = 2**31 + 4321
TRAFFIC = {"driver": "static_batch", "batch": 4, "max_seq": 48,
           "prompt_lens": [8, 16], "output_lens": [4, 8], "greedy": True,
           "check_requests": 3}
READERS = ("moe_decode_roofline", "moe_prefill_roofline",
           "moe_gmm_roofline", "moe_decode_experts")


def tiny() -> dict:
    """The configuration at test widths: every shrunk key is listed under
    ``reduced``, so the adapter still checks the rest against the
    program's preset."""
    cfg = json.loads(CONFIG.read_text())
    small = {"num_hidden_layers": 2, "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "vocab_size": 512, "num_experts": 6, "num_experts_per_tok": 2,
             "moe_intermediate_size": 32,
             "shared_expert_intermediate_size": 128}
    cfg.update(small, serve_dtype="float32", reduced=sorted(small))
    return cfg


def test_the_counts_hold_every_weight_and_match_the_program():
    from repro.configs import ARCHS

    cfg = json.loads(CONFIG.read_text())
    sh = references.of(cfg).Shapes.of(cfg)
    leaves = jax.tree.leaves(weight_shapes(cfg))
    assert sh.params == sum(int(np.prod(x.shape)) for x in leaves)
    assert sh.params == 4_045_694_976
    assert sh.params == ARCHS["qwen2-moe-a2.7b"].replace(
        n_layers=6).param_count()
    # a step that reads every expert reads every weight once
    assert sh.decode_bytes(1, 0, sh.experts) == pytest.approx(
        sh.params * 2 + 2048 * 2 - 311_164_928 * 2)


def test_a_tiny_configuration_runs_and_is_correct(tmp_path):
    cfg = tiny()
    cell = Cell(name="moe-tiny", chips=1, config=cfg, traffic=TRAFFIC,
                limits={"logit_gap": 1e-3}, end_to_end=[
                    {"name": "out_tok_s", "unit": "tokens/s"}],
                per_layer=[])
    r = harness.run_cell(cell, SEED, 0.0, False, time.perf_counter(),
                         tmp_path / "trace", None)
    assert r["correct"] and r["failed"] == 0
    assert r["checks"]["logit_gap"]["value"] < 1e-4
    assert set(r["metrics"]) == {"out_tok_s"}

    # the check replays the module's logits: an altered token is seen
    w = make_weights(cfg, 5, "float32")
    system = adapter.System(cfg, TRAFFIC, w)
    calls = static_batch.StaticBatch(TRAFFIC, 512, 5).run(
        system.generate, 0.0, max_rounds=1)
    every = [(i, r) for i, c in enumerate(calls) for r in range(c.batch)]
    assert check.compare(cfg, w, calls, every, 48)["logit_gap"] < 1e-4
    calls[0].tokens[0, 1] = (calls[0].tokens[0, 1] + 1) % 512
    assert check.compare(cfg, w, calls, every, 48)["logit_gap"] > 1e-3


def test_the_adapter_refuses_what_the_program_cannot_run():
    real = adapter.model_config(json.loads(CONFIG.read_text()))
    assert (real.n_layers, real.n_shared_experts) == (6, 4)
    cfg = tiny()
    adapter.model_config(cfg)
    for key, value in (("norm_topk_prob", True), ("rope_theta", 1e4),
                       ("decoder_sparse_step", 2)):
        with pytest.raises(ValueError):
            adapter.model_config(dict(cfg, **{key: value}))


def _run():
    """One traced call (B=2, prompt 8, 3 new tokens) of the real
    configuration's counts: three decode steps and a prefill, each with
    the kernel's op inside."""
    cfg = json.loads(CONFIG.read_text())
    call = static_batch.Call(8, 3, 2, 0.0, 1.0, None, None)
    ms = 1e6
    tr = Trace(
        modules=[[("jit_prefill", 0, 40 * ms),
                  ("jit_decode_step", 41 * ms, 51 * ms),
                  ("jit_decode_step", 52 * ms, 62 * ms),
                  ("jit_decode_step", 63 * ms, 73 * ms)]],
        busy=[[(0, 40 * ms), (41 * ms, 51 * ms), (52 * ms, 62 * ms),
               (63 * ms, 73 * ms)]],
        op_self_ns={"jit_prefill/moe_gmm.1": 9 * ms,
                    "jit_decode_step/moe_gmm.2": 20 * ms,
                    "jit_decode_step/fusion.3": 5 * ms},
        spans=[("chipbench.call", 0, 74 * ms)])
    return harness.RunRecord(
        calls=[call], setup_s=1.0, window_s=0.074,
        shapes=references.of(cfg).Shapes.of(cfg),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace=tr)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_reads_the_counter_and_the_trace(name, monkeypatch):
    from repro.serve import tracing

    monkeypatch.setattr(tracing, "routing", lambda: {
        "prefill_runs": 1, "prefill_experts": 6 * 16,
        "decode_runs": 2, "decode_experts": 2 * 6 * 7,
        "moe_layers": 6})
    value = harness.reader(name)(_run())
    assert value is not None and 0 < value < 100
    if name == "moe_decode_experts":
        assert value == 7.0
    if name == "moe_decode_roofline":
        sh = _run().shapes
        least = sum(sh.decode_bytes(2, 8 + j, 7.0) for j in (1, 2, 3)) / 819e9
        assert value == pytest.approx(100 * least / 0.030)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_reads_none_without_the_counter(name, monkeypatch):
    from repro.serve import tracing

    monkeypatch.setattr(tracing, "routing", lambda: dict.fromkeys(
        ("prefill_runs", "prefill_experts", "decode_runs",
         "decode_experts", "moe_layers"), 0))
    assert harness.reader(name)(_run()) is None
    monkeypatch.setitem(sys.modules, "repro.serve.tracing", None)
    assert harness.reader(name)(_run()) is None


def test_the_kernel_roofline_reads_none_without_the_kernels_ops(monkeypatch):
    from repro.serve import tracing

    monkeypatch.setattr(tracing, "routing", lambda: {
        "prefill_runs": 1, "prefill_experts": 60, "decode_runs": 2,
        "decode_experts": 80, "moe_layers": 6})
    run = _run()
    run.trace.op_self_ns = {"jit_decode_step/fusion.3": 5e6}
    assert harness.reader("moe_gmm_roofline")(run) is None

"""A whole run of a cell at test widths on the CPU, past the chip gate:
the traffic driver, the system, the check and the readers. The faults a
served cell can have must turn ``correct`` false, and the fp8 control
must read a wider gap than the program."""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, harness
from chipbench.adapters import repro_serve_engine as adapter
from chipbench.drivers import static_batch
from chipbench.weights import make_weights
from repro.models.registry import Model
from repro.serve.engine import ServeEngine

SEED = 2**31 + 12345


def run(cell, tmp_path, traced=False, seed=SEED):
    return harness.run_cell(cell, seed, 0.5, traced, time.perf_counter(),
                            tmp_path / "trace", None)


def test_a_run_is_correct_and_reports_its_metrics(smoke_cell, tmp_path):
    r = run(smoke_cell, tmp_path)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] % (4 * smoke_cell.traffic["batch"]) == 0
    assert set(r["metrics"]) == {"out_tok_s", "req_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["checks"]["logit_gap"]["limit"] == 0.05


def test_a_traced_run_checks_the_traced_calls(smoke_cell, tmp_path):
    r = run(smoke_cell, tmp_path, traced=True)
    assert r["correct"]
    assert r["attempted"] == 4 * smoke_cell.traffic["batch"]   # one round
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_inputs_and_every_round_the_same_work(smoke_cell):
    tr = smoke_cell.traffic
    a = static_batch.StaticBatch(tr, 512, SEED)
    b = static_batch.StaticBatch(tr, 512, SEED)
    ra, rb = next(a.rounds()), next(b.rounds())
    assert ra == rb and sorted(ra) == sorted(a.grid)
    assert np.array_equal(a._draw(a._prompts, 8), b._draw(b._prompts, 8))
    other = static_batch.StaticBatch(tr, 512, SEED + 1)
    assert sorted(next(other.rounds())) == sorted(ra)


def _alter_one_step(monkeypatch):
    orig, n = ServeEngine._sample, [0]

    def sample(self, logits, key, temperature):
        n[0] += 1
        out = orig(self, logits, key, temperature)
        return (out + 1) % self.cfg.vocab_size if n[0] % 3 == 2 else out
    monkeypatch.setattr(ServeEngine, "_sample", sample)


def _state_unchanged(monkeypatch):
    orig = Model.decode_step

    def decode_step(self, params, cache, batch):
        logits, _ = orig(self, params, cache, batch)
        return logits, cache
    monkeypatch.setattr(Model, "decode_step", decode_step)


def _half_batch_left_out(monkeypatch):
    orig = ServeEngine.generate

    def generate(self, prompt_tokens, n_new, **kw):
        res = orig(self, prompt_tokens, n_new, **kw)
        half = res.tokens.shape[0] // 2
        res.tokens[half:] = res.tokens[:half]
        return res
    monkeypatch.setattr(ServeEngine, "generate", generate)


@pytest.mark.parametrize("fault", [_alter_one_step, _state_unchanged,
                                   _half_batch_left_out])
def test_a_fault_in_the_timed_path_fails_the_check(fault, smoke_cell,
                                                   tmp_path, monkeypatch):
    fault(monkeypatch)
    r = run(smoke_cell, tmp_path)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > 0.05


@pytest.mark.parametrize("seed", [11, 2**31 + 7, 4_000_000_000])
def test_the_fp8_control_reads_a_wider_gap(seed, smoke_cell):
    cfg, tr = smoke_cell.config, smoke_cell.traffic
    w = make_weights(cfg, seed, jnp.bfloat16)
    system = adapter.System(cfg, tr, w)
    driver = static_batch.StaticBatch(tr, cfg["vocab_size"], seed)
    calls = driver.run(system.generate, 0.0, max_rounds=2)
    picks = check.sample(calls, 6, seed)
    got = check.compare(cfg, w, calls, picks, tr["max_seq"], control=True)
    assert got["control_logit_gap"] > 0.05 > got["logit_gap"]
    limits = smoke_cell.limits
    assert check.verdict(got, limits, 0)[0]
    assert not check.verdict({"logit_gap": got["control_logit_gap"]},
                             limits, 0)[0]


def test_a_failed_request_or_a_reading_over_its_limit_is_not_correct():
    limits = {"logit_gap": 0.05}
    correct, checks = check.verdict({"logit_gap": 0.05}, limits, 0)
    assert correct and checks == {"logit_gap": {"value": 0.05,
                                                "limit": 0.05}}
    assert not check.verdict({"logit_gap": 0.0}, limits, 1)[0]
    assert not check.verdict({"logit_gap": 0.0501}, limits, 0)[0]


def test_the_sample_holds_a_longest_request(smoke_cell):
    tr = smoke_cell.traffic
    calls = [static_batch.Call(s, n, 4, 0, 1, None, None)
             for s, n in [(8, 4), (16, 8), (8, 8)]]
    for seed in range(5):
        picks = check.sample(calls, 3, seed)
        assert picks[0][0] == 1 and len(set(picks)) == 3


def test_the_adapter_refuses_what_the_program_cannot_run(smoke_cell):
    cfg = dict(smoke_cell.config, scale_emb=12)
    with pytest.raises(ValueError, match="scale"):
        adapter.model_config(cfg)
    cfg = dict(smoke_cell.config, program_arch="qwen2-0.5b")
    with pytest.raises(ValueError, match="differs"):
        adapter.model_config(cfg)

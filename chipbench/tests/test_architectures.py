"""Each architecture is one module under ``chipbench/references/``, named
by a configuration's ``reference``: the weights' layout, the counts and
the check all come from it, and the dense decoder's weights are the same
bit for bit as when their layout lived in ``weights.py``."""
from __future__ import annotations

import hashlib
import json
import sys
import time
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import check, counts, harness, references
from chipbench.drivers import static_batch
from chipbench.spec import Cell
from chipbench.tests import two_expert_mlp
from chipbench.weights import make_weights, weight_shapes

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

# sha256 (first 16 hex digits) of each bf16 leaf of ``make_weights`` for
# ``data/smoke-config.json``, computed with the layout in ``weights.py``
# before it moved to ``references/dense_decoder.py``
DIGESTS = {
    11: {
        "embed": "a2fd714355ab03da", "final_norm": "8b80eebdd77acdda",
        "layers/attn_norm": "68e59821578b5c3b", "layers/bk": "fad1b7e6b605f78c",
        "layers/bq": "d40103f238accb4f", "layers/bv": "03bfe0795217c10c",
        "layers/mlp_norm": "f178a834340c4d1a",
        "layers/w_down": "c295565ec2358fe3",
        "layers/w_gate": "7cec41885ab3e63d", "layers/w_up": "6f5770fd192deb6c",
        "layers/wk": "2f12d5f88ca2f08e", "layers/wo": "665a86eed5c8baba",
        "layers/wq": "4593f167b08d9d2c", "layers/wv": "65597182941ddc73"},
    2**31 + 12345: {
        "embed": "608fbc4c013e6e45", "final_norm": "6285d53138bdf77e",
        "layers/attn_norm": "dbd06ca2ee3181e5", "layers/bk": "61cb2fe2287a5cfe",
        "layers/bq": "049194a1eb26aa3f", "layers/bv": "b632d0d741b1f6e3",
        "layers/mlp_norm": "ccf385792c3d61f5",
        "layers/w_down": "f43ff4296d705f79",
        "layers/w_gate": "e077d2791fd5cdc9", "layers/w_up": "04a77493fc986fbd",
        "layers/wk": "ccae76d00d820cd2", "layers/wo": "72ad9b9460bb3f50",
        "layers/wq": "21b411624d9d13d7", "layers/wv": "cc4c0f12e2e0f5d3"},
}


def _config(name):
    return json.loads((ROOT / "chipbench" / "configs" / name).read_text())


def _digests(tree):
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(p.key for p in path)
        raw = np.asarray(leaf).view(np.uint16).tobytes()
        out[key] = hashlib.sha256(raw).hexdigest()[:16]
    return out


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_dense_weights_are_the_same_bit_for_bit(seed):
    cfg = json.loads((DATA / "smoke-config.json").read_text())
    assert _digests(make_weights(cfg, seed, "bfloat16")) == DIGESTS[seed]


@pytest.mark.parametrize("name", ["qwen2-0.5b.json", "qwen1.5-1.8b.json"])
def test_the_counts_hold_every_weight_of_the_layout(name):
    cfg = _config(name)
    leaves = jax.tree.leaves(weight_shapes(cfg))
    assert references.of(cfg).Shapes.of(cfg).params == sum(
        int(np.prod(x.shape)) for x in leaves)


@pytest.fixture
def second_architecture(monkeypatch):
    """``two_expert_mlp`` as both the reference and the system of a
    configuration of its own."""
    for pkg in ("references", "adapters"):
        monkeypatch.setitem(sys.modules, f"chipbench.{pkg}.two_expert_mlp",
                            two_expert_mlp)
    return {"reference": "two_expert_mlp", "adapter": "two_expert_mlp",
            "hidden_size": 32, "intermediate_size": 48,
            "num_hidden_layers": 2, "vocab_size": 256,
            "rms_norm_eps": 1e-6, "serve_dtype": "float32"}


def test_a_second_architecture_brings_its_own_layout_and_counts(
        second_architecture):
    cfg = second_architecture
    w = make_weights(cfg, 7, "float32")
    assert w["layers"]["w_gate"].shape == (2, 2, 32, 48)
    assert set(w) == {"embed", "final_norm", "layers"}
    sh = references.of(cfg).Shapes.of(cfg)
    assert isinstance(sh, two_expert_mlp.Shapes)
    assert sh.params == sum(x.size for x in jax.tree.leaves(w))
    assert counts.call_useful_flops(sh, 2, 8, 3) == (
        sh.prefill_flops(2, 8) + 2 * sh.decode_flops(2, 0))


def test_a_second_architecture_runs_and_is_checked(second_architecture,
                                                   smoke_cell, tmp_path):
    cfg = second_architecture
    cell = Cell(name="two-expert", chips=1, config=cfg,
                traffic=smoke_cell.traffic, limits={"logit_gap": 0.05},
                end_to_end=smoke_cell.end_to_end, per_layer=[])
    r = harness.run_cell(cell, 2**31 + 99, 0.0, False, time.perf_counter(),
                         tmp_path / "trace", None)
    assert r["correct"] and r["checks"]["logit_gap"]["value"] < 1e-5
    assert set(r["metrics"]) == {"out_tok_s", "req_p95_ms", "setup_s"}

    # the check reads the same module's logits: an altered token is seen
    w = make_weights(cfg, 5, "float32")
    system = two_expert_mlp.System(cfg, smoke_cell.traffic, w)
    driver = static_batch.StaticBatch(smoke_cell.traffic, 256, 5)
    calls = driver.run(system.generate, 0.0, max_rounds=1)
    calls[0].tokens[0, 1] = (calls[0].tokens[0, 1] + 1) % 256
    every = [(i, r) for i, c in enumerate(calls) for r in range(c.batch)]
    got = check.compare(cfg, w, calls, every, 48, control=True)
    assert got["logit_gap"] > 1e-3
    assert "control_logit_gap" in got


def test_a_module_without_a_name_says_which(monkeypatch):
    mod = types.ModuleType("chipbench.references.no_layout")
    mod.Dims, mod.logits, mod.Shapes = (two_expert_mlp.Dims,
                                        two_expert_mlp.logits,
                                        two_expert_mlp.Shapes)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    cfg = {"reference": "no_layout"}
    with pytest.raises(AttributeError, match="lacks leaf_shapes"):
        make_weights(cfg, 1)
    with pytest.raises(AttributeError, match="lacks leaf_shapes"):
        references.of(cfg).Shapes.of(cfg)

"""The serve engine's step spans and compile counter, at test widths on
the CPU. Spans are read back from the profiler's own trace."""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import SMOKES
from repro.serve import tracing
from repro.serve.engine import ServeEngine

CFG = SMOKES["qwen2-0.5b"]
B, S, N_NEW = 3, 11, 5          # shapes no other test compiles


def prompts(seed=0, b=B, s=S, cfg=CFG):
    shape = (b, cfg.n_codebooks, s) if cfg.family == "audio" else (b, s)
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape, dtype=np.int32)


def serve_spans(trace_dir):
    """(name, start_ns, end_ns, args) of every ``serve.*`` host event."""
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    prof = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                        for e in line.events if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: s[1])


def traced_generate(eng, trace_dir, n_new=N_NEW):
    jax.profiler.start_trace(str(trace_dir))
    try:
        res = eng.generate(prompts(cfg=eng.cfg), n_new)
    finally:
        jax.profiler.stop_trace()
    return res, serve_spans(trace_dir)


def plain_greedy(eng, toks, n_new):
    """The greedy loop over the model's own programs, with no spans."""
    cache = eng.model.init_cache(toks.shape[0], eng.max_seq, eng.dtype)
    logits, cache = jax.jit(eng.model.prefill)(
        eng.params, {"tokens": jnp.asarray(toks)}, cache)
    last, out = logits[:, -1:], []
    for i in range(n_new):
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        out.append(np.asarray(nxt))
        last, cache = jax.jit(eng.model.decode_step)(
            eng.params, cache,
            {"tokens": nxt, "cache_index": jnp.asarray(toks.shape[1] + i,
                                                       jnp.int32)})
    return np.concatenate(out, axis=-1)


@pytest.fixture(scope="module")
def engine():
    return ServeEngine(CFG, max_seq=64)


def check_step_spans(spans, n_new):
    """``n_new`` each of sample, dispatch and fetch, numbered by the step
    each works for, in turn and not overlapping, and no other ``serve.*``
    span. Token i is fetched after step i + 1 is dispatched: sample i,
    dispatch i, fetch i - 1, and the last token's fetch after the loop."""
    want = [("sample", 0), ("dispatch", 0)]
    for i in range(1, n_new):
        want += [("sample", i), ("dispatch", i), ("fetch", i - 1)]
    want.append(("fetch", n_new - 1))
    assert [(s[0][len("serve."):], s[3]) for s in spans] == [
        (name, {"step": i}) for name, i in want]
    assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))


def test_a_traced_call_has_one_span_per_phase_and_step(engine, tmp_path):
    res, spans = traced_generate(engine, tmp_path)
    check_step_spans(spans, N_NEW)
    assert res.tokens.shape == (B, N_NEW)


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "internvl2-1b",
                                  "musicgen-large"])
def test_each_family_serves_the_same_tokens_inside_its_step_spans(
        arch, tmp_path):
    eng = ServeEngine(SMOKES[arch], max_seq=32)
    on, spans = traced_generate(eng, tmp_path, n_new=3)
    check_step_spans(spans, 3)
    off = eng.generate(prompts(cfg=eng.cfg), 3)
    np.testing.assert_array_equal(on.tokens, off.tokens)


def test_tokens_are_the_same_with_the_profiler_on_and_off(engine, tmp_path):
    on, _ = traced_generate(engine, tmp_path)
    off = engine.generate(prompts(), N_NEW)
    np.testing.assert_array_equal(on.tokens, off.tokens)
    np.testing.assert_array_equal(off.tokens,
                                  plain_greedy(engine, prompts(), N_NEW))


def plain_sampled(eng, toks, n_new, temperature, seed):
    """The sampled loop over the model's own programs, splitting the key
    before each token."""
    cache = eng.model.init_cache(toks.shape[0], eng.max_seq, eng.dtype)
    logits, cache = jax.jit(eng.model.prefill)(
        eng.params, {"tokens": jnp.asarray(toks)}, cache)
    key, last, out = jax.random.PRNGKey(seed), logits[:, -1:], []
    for i in range(n_new):
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(
            sub, last / temperature, axis=-1).astype(jnp.int32)
        out.append(np.asarray(nxt))
        last, cache = jax.jit(eng.model.decode_step)(
            eng.params, cache,
            {"tokens": nxt, "cache_index": jnp.asarray(toks.shape[1] + i,
                                                       jnp.int32)})
    return np.concatenate(out, axis=-1)


@pytest.mark.parametrize("seed", [0, 3000000019])
def test_a_sampled_call_keeps_the_key_split_order(engine, seed):
    got = engine.generate(prompts(2), N_NEW, temperature=0.7, seed=seed)
    np.testing.assert_array_equal(
        got.tokens, plain_sampled(engine, prompts(2), N_NEW, 0.7, seed))


def test_only_a_sampled_call_splits_keys(engine, monkeypatch):
    split, n = jax.random.split, []

    def counted(key, *a, **kw):
        n.append(1)
        return split(key, *a, **kw)

    monkeypatch.setattr(jax.random, "split", counted)
    engine.generate(prompts(), N_NEW)
    assert not n
    engine.generate(prompts(), N_NEW, temperature=1.0, seed=5)
    assert len(n) == N_NEW


def test_the_timers_cover_the_calls_phases(engine):
    res = engine.generate(prompts(), N_NEW)
    assert res.prefill_s > 0 and res.decode_s > 0
    assert res.tokens_per_s == pytest.approx(B * N_NEW / res.decode_s)


def test_compiles_are_charged_to_the_engine_and_program_once():
    # a batch of its own, so that even the sampler's eager ops compile
    eng, b = ServeEngine(CFG, max_seq=64), 7
    before = tracing.compiles()
    eng.generate(prompts(b=b), 2)
    first = tracing.compiles()
    # at least the prefill, the sampler and the decode step
    assert first["compiles"] - before["compiles"] >= 3
    assert first["compile_s"] > before["compile_s"]
    eng.generate(prompts(1, b=b), 2)
    assert tracing.compiles() == first
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((b, 7))).block_until_ready()
    assert tracing.compiles() == first


def test_a_compile_on_another_thread_during_a_call_is_not_charged():
    eng, b = ServeEngine(CFG, max_seq=64), 6
    eng.generate(prompts(b=b), 2)
    first = tracing.compiles()
    done = threading.Event()

    def other():
        jax.jit(lambda x: x * 5 - 2)(jnp.ones((b, 9))).block_until_ready()
        done.set()

    # this thread is inside a call; the other one's compile is not
    with tracing.charged():
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert done.is_set()
    assert tracing.compiles() == first
    with tracing.charged():
        other()
    assert tracing.compiles()["compiles"] > first["compiles"]

#!/usr/bin/env python3
"""Run the system's device path once on a TPU and check what comes out.

    python chip_smoke.py

One process, no child processes, no options. Phases, in order:

1. device gate: the first JAX device must be a TPU. Anything else exits
   non-zero and names the platform found; there is no CPU path.
2. serve: ``ServeEngine`` builds qwen2-0.5b at its published widths in
   bf16 (random weights from a seed) and answers 4 prompts of 128
   tokens with 32 greedy tokens, twice; the second call must compile
   nothing (``repro.serve.tracing.compiles``). A float32 engine at
   "highest" matmul precision is the reference: its cached decode
   logits must match the no-cache forward pass over prompt + generated
   tokens, and the bf16 engine's last-position prefill logits must
   match its own.
3. kernels: the Pallas flash-attention (qwen2-0.5b prefill shape) and
   ragged grouped-matmul (qwen2-moe-a2.7b expert shape) kernels, compiled for
   Mosaic, against the pure-jnp oracles in bf16.
4. fleet sweep: ``sim_jax.sweep_collocations`` on the device against
   the discrete-event simulator and against the same call on the CPU.
5. control plane: a short ``ServingSession`` of the same model drains
   every request.

Timings printed on the way are smoke, not a benchmark. The last line
of standard output is the JSON verdict; a failed check raises before
it is printed.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.core import (TenantSpec, VNPUConfig, VNPUManager,  # noqa: E402
                        compile_neuisa)
from repro.core.sim_jax import sweep_collocations  # noqa: E402
from repro.core.simulator import Simulator  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.ref import gqa_attention_ref, ragged_matmul_ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.npu.hw_config import DEFAULT_CORE  # noqa: E402
from repro.npu.workloads import get_workload  # noqa: E402
from repro.serve import NPUCluster, PoissonArrivals, ServingSession  # noqa: E402
from repro.serve import tracing  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402

SERVE_ARCH = "qwen2-0.5b"
MOE_ARCH = "qwen2-moe-a2.7b"
BATCH, PROMPT_LEN, N_NEW, MAX_SEQ = 4, 128, 32, 512
MOE_ROWS = 16 * 128 * 4      # a (16, 128) prefill, top-4

# Limits are on max|x - ref| / max(1, max|ref|) over the compared logits.
# f32 at "highest" precision differs between the cached and the no-cache
# path only in summation order (~1e-6 relative); a bf16 pass anywhere on
# the reference path would show as ~1e-2.
F32_LOGIT_TOL = 1e-3
# bf16 activations carry ~2^-9 relative rounding into each of 24 layers.
BF16_LOGIT_TOL = 1e-1
# Kernels vs oracles in bf16: the bound the kernel tests use.
BF16_KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)
# The fluid sweep is exact against the discrete oracle without
# harvesting (tests/test_sim_jax.py); device vs CPU differ only in
# float rounding.
ORACLE_BAND = (0.98, 1.02)
CPU_RTOL = 1e-3

FLEET_PAIRS = (("RsNt", "DLRM"), ("BERT", "ENet"), ("ENet", "TFMR"))
FLEET_SPLITS = (((2, 2), (2, 2)), ((3, 1), (3, 1)))
FLEET_BW = (0.75, 1.0, 2.0)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _smoke(msg: str) -> None:
    print(f"[smoke, not a benchmark] {msg}", flush=True)


def _on_default_platform(x: jax.Array, what: str) -> None:
    want = jax.devices()[0].platform
    got = {d.platform for d in x.devices()}
    _check(got == {want}, f"{what} ran on {got}, not on {want}")


def _rel_err(x: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(x - ref)) / max(1.0, np.max(np.abs(ref))))


def decode_logits(eng: ServeEngine, prompts: np.ndarray,
                  forced: np.ndarray) -> np.ndarray:
    """Next-token logits of the cached path at every generated position:
    prefill over ``prompts``, then one decode step per token of
    ``forced`` but the last. Returns (B, n_new, V) float32."""
    prefill = jax.jit(eng.model.prefill)
    decode = jax.jit(eng.model.decode_step)
    b, s = prompts.shape
    cache = eng.model.init_cache(b, eng.max_seq, eng.dtype)
    lg, cache = prefill(eng.params, {"tokens": jnp.asarray(prompts)}, cache)
    steps = [lg[:, -1]]
    for i in range(forced.shape[1] - 1):
        lg, cache = decode(eng.params, cache, {
            "tokens": jnp.asarray(forced[:, i:i + 1]),
            "cache_index": jnp.asarray(s + i, jnp.int32)})
        steps.append(lg[:, 0])
    return np.asarray(jnp.stack(steps, axis=1), np.float32)


def check_serve(cfg, batch: int = BATCH, prompt_len: int = PROMPT_LEN,
                n_new: int = N_NEW, max_seq: int = MAX_SEQ,
                seed: int = 0) -> None:
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, prompt_len), dtype=np.int32)

    eng = ServeEngine(cfg, dtype=jnp.bfloat16, max_seq=max_seq, seed=seed)
    _on_default_platform(jax.tree_util.tree_leaves(eng.params)[0],
                         "bf16 engine params")
    t0 = time.perf_counter()
    first = eng.generate(prompts, n_new)
    t_first = time.perf_counter() - t0
    compiled = tracing.compiles()["compiles"]
    again = eng.generate(prompts, n_new)
    toks = first.tokens
    _check(toks.shape == (batch, n_new), f"tokens shape {toks.shape}")
    _check(int(toks.min()) >= 0 and int(toks.max()) < cfg.vocab_size,
           "generated token outside the vocabulary")
    _check(np.array_equal(toks, again.tokens),
           "two identical greedy generate calls disagree")
    recompiled = tracing.compiles()["compiles"] - compiled
    _check(recompiled == 0,
           f"a repeated generate call compiled {recompiled} executables")
    _smoke(f"serve bf16 B={batch} prompt={prompt_len} new={n_new}: first "
           f"generate incl. compile {t_first:.2f} s; warm host prefill "
           f"{again.prefill_s * 1e3:.2f} ms, host decode "
           f"{again.tokens_per_s:.1f} tok/s")

    with jax.default_matmul_precision("highest"):
        ref = ServeEngine(cfg, dtype=jnp.float32, max_seq=max_seq,
                          seed=seed)
        ref_toks = ref.generate(prompts, n_new).tokens
        steps = decode_logits(ref, prompts, ref_toks)
        full, _ = jax.jit(ref.model.logits)(
            ref.params,
            {"tokens": jnp.asarray(np.concatenate([prompts, ref_toks], 1))})
        full = np.asarray(full[:, prompt_len - 1:prompt_len - 1 + n_new],
                          np.float32)
    err = _rel_err(steps, full)
    print(f"serve f32 cached-vs-full logits rel err {err:.3e} "
          f"(limit {F32_LOGIT_TOL:g})", flush=True)
    _check(err <= F32_LOGIT_TOL, f"f32 decode logits off by {err:.3e}")
    _check(np.array_equal(steps.argmax(-1), ref_toks)
           and np.array_equal(full.argmax(-1), ref_toks),
           "greedy argmax of cached and full f32 logits disagree")

    lg, _ = jax.jit(eng.model.prefill)(
        eng.params, {"tokens": jnp.asarray(prompts)},
        eng.model.init_cache(batch, max_seq, eng.dtype))
    last = np.asarray(lg[:, -1], np.float32)
    err = _rel_err(last, steps[:, 0])
    print(f"serve bf16-vs-f32 last prefill logits rel err {err:.3e} "
          f"(limit {BF16_LOGIT_TOL:g}); bf16 tokens equal to f32 greedy: "
          f"{float(np.mean(toks == ref_toks)):.3f}", flush=True)
    _check(err <= BF16_LOGIT_TOL, f"bf16 prefill logits off by {err:.3e}")


def _compiled_kernel(fn, *args) -> None:
    text = fn.lower(*args).compile().as_text()
    _check("tpu_custom_call" in text,
           f"{fn.__name__} compiled without a Mosaic kernel")


def check_kernels(seed: int = 0) -> None:
    dense, moe = ARCHS[SERVE_ARCH], ARCHS[MOE_ARCH]
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf16 = jnp.bfloat16
    q = jax.random.normal(
        k[0], (BATCH, MAX_SEQ, dense.n_heads, dense.d_head), bf16)
    kv_shape = (BATCH, MAX_SEQ, dense.n_kv_heads, dense.d_head)
    kk = jax.random.normal(k[1], kv_shape, bf16)
    vv = jax.random.normal(k[2], kv_shape, bf16)
    t0 = time.perf_counter()
    _compiled_kernel(ops.flash_attention, q, kk, vv)
    out = ops.flash_attention(q, kk, vv)
    out.block_until_ready()
    _smoke(f"flash_attention {tuple(q.shape)}: compile+first call "
           f"{time.perf_counter() - t0:.2f} s")
    _on_default_platform(out, "flash_attention")
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(gqa_attention_ref(q, kk, vv), np.float32),
        **BF16_KERNEL_TOL)

    x = jax.random.normal(k[3], (MOE_ROWS, moe.d_model), bf16)
    w = jax.random.normal(
        k[4], (moe.n_experts, moe.d_model, moe.d_expert), bf16)
    rng = np.random.default_rng(seed)
    sizes = rng.multinomial(MOE_ROWS, rng.dirichlet(np.ones(moe.n_experts)))
    sizes[::7] = 0                       # experts that got no rows
    sizes[1] += MOE_ROWS - sizes.sum()
    sizes = jnp.asarray(sizes, jnp.int32)
    gmm = jax.jit(ops.gmm)
    t0 = time.perf_counter()
    _compiled_kernel(gmm, x, w, sizes)
    out = gmm(x, w, sizes)
    out.block_until_ready()
    _smoke(f"ragged gmm {tuple(x.shape)}x{tuple(w.shape)}: "
           f"compile+first call {time.perf_counter() - t0:.2f} s")
    _on_default_platform(out, "gmm")
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ragged_matmul_ref(x, w, sizes), np.float32),
        **BF16_KERNEL_TOL)


def _oracle_makespan(w1: str, w2: str, n_requests: int) -> float:
    core = DEFAULT_CORE
    mgr = VNPUManager(core=core)
    specs = []
    for name in (w1, w2):
        v = mgr.create(VNPUConfig(2, 2, hbm_bytes=1 << 30))
        specs.append(TenantSpec(
            compile_neuisa(get_workload(name, core), core), v, n_requests))
    return Simulator(specs, policy="neu10_nh", core=core).run().makespan


def check_fleet(n_requests: int = 4) -> None:
    core = DEFAULT_CORE
    progs = [(compile_neuisa(get_workload(a, core), core),
              compile_neuisa(get_workload(b, core), core))
             for a, b in FLEET_PAIRS]

    def sweep():
        out = sweep_collocations(progs, FLEET_SPLITS, bw_points=FLEET_BW,
                                 n_requests=n_requests, harvest=False,
                                 core=core)
        return out["makespan"]

    t0 = time.perf_counter()
    on_dev = sweep()
    on_dev.block_until_ready()
    _smoke(f"sweep_collocations {tuple(on_dev.shape)}: compile+first call "
           f"{time.perf_counter() - t0:.2f} s")
    _on_default_platform(on_dev, "sweep_collocations")
    with jax.default_device(jax.devices("cpu")[0]):
        on_cpu = np.asarray(sweep())
    ms = np.asarray(on_dev)
    _check(ms.shape == (len(FLEET_PAIRS), len(FLEET_SPLITS), len(FLEET_BW)),
           f"sweep shape {ms.shape}")

    oracle = np.array([_oracle_makespan(a, b, n_requests)
                       for a, b in FLEET_PAIRS])
    half = ms[:, 0, FLEET_BW.index(1.0)]
    ratio = half / oracle
    cpu_err = float(np.max(np.abs(ms - on_cpu) / on_cpu))
    print(f"fleet sweep/oracle {np.round(ratio, 6).tolist()}; "
          f"device vs cpu max rel diff {cpu_err:.3e}", flush=True)
    lo, hi = ORACLE_BAND
    _check(bool(np.all((lo < ratio) & (ratio < hi))),
           f"sweep off the discrete oracle: {ratio}")
    _check(np.argsort(oracle).tolist() == np.argsort(half).tolist(),
           "sweep ranks the pairs unlike the oracle")
    _check(cpu_err <= CPU_RTOL, f"device and cpu sweeps differ by {cpu_err}")


def check_session(cfg, n: int = 16, seed: int = 0) -> None:
    sess = ServingSession(NPUCluster(policy="neu10"))
    chat = sess.register_generative("chat", cfg, prompt_len=PROMPT_LEN,
                                    gen_lens=N_NEW, eu_budget=4)
    injected = sess.submit_arrivals(
        chat, PoissonArrivals(rate_rps=50.0, n=n, seed=seed))
    sess.drain()
    rep = sess.report(chat)[0]
    print(f"session: {rep.requests_done}/{injected} requests done, "
          f"simulated ttft p95 {rep.ttft_p95_ms:.3f} ms", flush=True)
    _check(injected == n and rep.requests_done == n and rep.queued == 0,
           f"session completed {rep.requests_done} of {n}")


def main() -> None:
    dev = jax.devices()[0]
    count = len(jax.devices())
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind}). There is no CPU path.")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count}", flush=True)
    print(f"compile cache: {use_compile_cache(ROOT)}", flush=True)

    cfg = ARCHS[SERVE_ARCH]
    for name, phase in (("serve", lambda: check_serve(cfg)),
                        ("kernels", check_kernels),
                        ("fleet", check_fleet),
                        ("session", lambda: check_session(cfg))):
        t0 = time.perf_counter()
        phase()
        _smoke(f"phase {name} passed in {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()

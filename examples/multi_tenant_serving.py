"""Multi-tenant serving of the ASSIGNED architectures on virtualized
NPUs — the paper's §V-F scenario with our model zoo, on the online
control plane.

    PYTHONPATH=src python examples/multi_tenant_serving.py

Three layers run side by side:
1. FUNCTIONAL: real token generation (greedy) through the JAX serving
   engine for each tenant (reduced smoke configs).
2. TIMING/SLO (closed loop): the policy registry schedules the same
   tenants' traces on one NPU core under all four paper policies.
3. ONLINE (open loop): a ServingSession admits Poisson request
   traffic, registers a second tenant mid-run, re-sizes it under an
   SLO autoscale hook, and deregisters it — without restarting the
   simulation.
"""
import numpy as np

from repro.configs import ARCHS, SMOKES
from repro.core.policies import available_policies
from repro.npu.trace import lm_trace
from repro.serve.engine import ServeEngine
from repro.serve.session import (NPUCluster, PoissonArrivals, SLOAutoscaler,
                                 ServingSession, run_closed_loop)


def functional_layer() -> None:
    print("=== functional layer: real generation (reduced configs) ===")
    for arch in ("qwen2-0.5b", "zamba2-7b", "musicgen-large"):
        cfg = SMOKES[arch]
        eng = ServeEngine(cfg, max_seq=96)
        B, S = 2, 24
        shape = ((B, cfg.n_codebooks, S) if cfg.family == "audio"
                 else (B, S))
        prompt = np.random.default_rng(0).integers(
            0, cfg.vocab_size, shape).astype(np.int32)
        out = eng.generate(prompt, n_new=8)
        print(f"  {arch:16s} prefill {out.prefill_s*1e3:7.1f}ms "
              f"decode {out.tokens_per_s:7.1f} tok/s "
              f"tokens={out.tokens.reshape(B, -1)[0][:6]}")


def timing_layer() -> None:
    print("\n=== timing/SLO layer: every registered policy ===")
    # qwen3-14b decode (a §V-F-style memory-bound LLM that fits one
    # 64 GB pNPU next to its neighbor) + qwen2-0.5b prefill
    llm = lm_trace(ARCHS["qwen3-14b"], batch=8, seq=2048, phase="decode")
    small = lm_trace(ARCHS["qwen2-0.5b"], batch=8, seq=512, phase="prefill")
    for policy in available_policies():
        cluster = NPUCluster(policy=policy)
        cluster.register("qwen3-14b/decode", llm, eu_budget=4)
        cluster.register("qwen2-0.5b/prefill", small, eu_budget=4)
        res, reports = run_closed_loop(cluster, n_requests=5)
        line = " | ".join(
            f"{r.name}: p95={r.p95_ms:9.2f}ms thr={r.throughput_rps:7.1f}/s"
            for r in reports)
        print(f"  {policy:9s} {line}")


def online_layer() -> None:
    print("\n=== online layer: open-loop session, mid-run lifecycle ===")
    llm = lm_trace(ARCHS["qwen3-14b"], batch=8, seq=2048, phase="decode")
    small = lm_trace(ARCHS["qwen2-0.5b"], batch=8, seq=512, phase="prefill")

    cluster = NPUCluster(policy="neu10")
    sess = ServingSession(cluster, autoscaler=SLOAutoscaler(max_eus=6))
    t_llm = sess.register("qwen3-14b/decode", llm, eu_budget=4)
    sess.submit_arrivals(t_llm, PoissonArrivals(rate_rps=6.0, n=120, seed=0))
    sess.run_until(5.0)
    r = sess.report(t_llm)[0]
    print(f"  t=5s   {r.name}: {r.requests_done} done, "
          f"p95={r.p95_ms:.2f}ms thr={r.throughput_rps:.1f}/s")

    # a second tenant shows up mid-run with a latency SLO
    t_sm = sess.register("qwen2-0.5b/prefill", small, eu_budget=2,
                         slo_p95_ms=1.0)
    sess.submit_arrivals(t_sm, PoissonArrivals(
        rate_rps=400.0, n=2000, seed=1, start_s=sess.now_s))
    before = t_sm.eu_budget
    sess.run_until(sess.now_s + 5.0)
    sess.run_until(sess.now_s + 5.0)  # second window lets the hook act
    r = sess.report(t_sm)[0]
    print(f"  +10s   {r.name}: {r.requests_done} done, p95={r.p95_ms:.2f}ms "
          f"(SLO 1.0ms) autoscaled {before}->{t_sm.eu_budget} EUs "
          f"({t_sm.vnpu.config.n_me}ME/{t_sm.vnpu.config.n_ve}VE)")

    # ... and leaves again; the LLM keeps serving, never restarted
    sess.deregister(t_sm)
    sess.drain()
    r = sess.report(t_llm)[0]
    print(f"  drain  {r.name}: {r.requests_done} done, "
          f"p95={r.p95_ms:.2f}ms thr={r.throughput_rps:.1f}/s "
          f"(t={sess.now_s:.1f}s)")


if __name__ == "__main__":
    functional_layer()
    timing_layer()
    online_layer()

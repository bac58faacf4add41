"""Training driver: jit'd train_step + data prefetch + async
checkpointing + crash-resume + straggler monitoring.

Single-process on whatever local devices exist; the same loop
drives the production mesh when `mesh` is passed — steps are jit'd
with the sharding rules from repro.distributed.sharding.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig
from repro.data.pipeline import SyntheticLMData, make_batch_iterator
from repro.distributed.fault import StragglerMonitor
from repro.launch.steps import init_train_state, make_train_step
from repro.models.registry import build_model


@dataclass
class TrainConfig:
    steps: int = 100
    batch: int = 8
    seq: int = 256
    peak_lr: float = 3e-4
    warmup: int = 20
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    seed: int = 0
    dtype: Any = jnp.float32
    remat: bool = True


def train(cfg: ModelConfig, tc: TrainConfig,
          log_fn=print) -> Dict[str, Any]:
    model = build_model(cfg, remat=tc.remat)
    key = jax.random.PRNGKey(tc.seed)
    state = init_train_state(model, key, tc.dtype)

    start_step = 0
    ckpt = None
    if tc.ckpt_dir:
        ckpt = Checkpointer(tc.ckpt_dir)
        restored, step = ckpt.restore(state)
        if restored is not None:
            state = restored
            start_step = step
            log_fn(f"[train] resumed from step {step}")

    step_fn = jax.jit(
        make_train_step(model, peak_lr=tc.peak_lr, warmup=tc.warmup,
                        stable=max(tc.steps - tc.warmup - tc.steps // 5, 1),
                        decay=max(tc.steps // 5, 1)),
        donate_argnums=(0,))

    data = SyntheticLMData(cfg, tc.batch, tc.seq, seed=tc.seed)
    it = make_batch_iterator(data, start_step=start_step)
    monitor = StragglerMonitor(n_hosts=1)

    losses: List[float] = []
    t_start = time.time()
    tokens_per_step = tc.batch * tc.seq
    try:
        for step in range(start_step, tc.steps):
            batch = next(it)
            t0 = time.time()
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            monitor.observe(step, 0, dt)
            losses.append(loss)
            if step % tc.log_every == 0 or step == tc.steps - 1:
                log_fn(f"[train] step {step:5d} loss {loss:.4f} "
                       f"lr {float(metrics['lr']):.2e} "
                       f"{tokens_per_step / dt:,.0f} tok/s")
            if ckpt and (step + 1) % tc.ckpt_every == 0:
                ckpt.save(step + 1, state)
        if ckpt:
            ckpt.save(tc.steps, state, blocking=True)
    finally:
        it.close()
    return {
        "final_loss": losses[-1] if losses else float("nan"),
        "losses": losses,
        "wall_s": time.time() - t_start,
        "state": state,
    }

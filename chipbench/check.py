"""Whether what the timed path served is correct, by the plain reference.

After the window closes, a sample of the requests it finished, drawn
from the seed, is replayed through the configuration's reference: the
prompt and the served tokens as one sequence, at the cell's ``max_seq``
(padded; attention is causal, so padding changes nothing before it).
For each served token the reference gives the logit of its own best
token and of the served one; the number compared is the widest gap
``best - served`` over all the sampled tokens (0 where greedy decoding
picked the reference's own best). The sample always holds a request of
the longest call.

``verdict`` decides ``correct`` from the readings and the cell's
limits. ``control`` gives the same reading for the reference computed in fp8 in
the program's place: at each of the same positions, the gap of the
token that the fp8 pass ranks first.
"""
from __future__ import annotations

import importlib
from functools import partial
from typing import Any, Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import references

CHECK_STREAM = 3


def sample(calls: Sequence[Any], n: int, seed: int) -> List[Tuple[int, int]]:
    """(call index, row) of ``n`` requests: one of the longest call, the
    rest uniformly from every request of the window."""
    rng = np.random.default_rng([seed, CHECK_STREAM])
    longest = max(range(len(calls)),
                  key=lambda i: (calls[i].prompt_len + calls[i].n_new, -i))
    picks = [(longest, int(rng.integers(calls[longest].batch)))]
    every = [(i, r) for i, c in enumerate(calls) for r in range(c.batch)
             if (i, r) != picks[0]]
    n_more = min(n - 1, len(every))
    for j in rng.choice(len(every), size=n_more, replace=False):
        picks.append(every[int(j)])
    return picks


@partial(jax.jit, static_argnums=(0, 1, 4))
def _gaps(ref_mod_name: str, dims, weights, seq, control: bool):
    ref = importlib.import_module(ref_mod_name)
    lg = ref.logits(dims, weights, seq)                     # (T, V)
    best = jnp.max(lg, axis=-1)
    served = jnp.take_along_axis(lg, jnp.roll(seq, -1)[:, None], -1)[:, 0]
    out = {"served": best - served}
    if control:
        ctl = jnp.argmax(ref.logits(dims, weights, seq, fp8=True), axis=-1)
        out["control"] = best - jnp.take_along_axis(lg, ctl[:, None], -1)[:, 0]
    return out


def compare(cfg: Mapping[str, Any], weights: Dict[str, Any],
            calls: Sequence[Any], picks: Sequence[Tuple[int, int]],
            max_seq: int, control: bool = False) -> Dict[str, float]:
    """Widest gaps over the sampled requests' served tokens."""
    mod = references.module_name(cfg)
    dims = references.of(cfg).Dims.of(cfg)
    worst = {"served": 0.0, "control": 0.0}
    n_tok = 0
    with jax.default_matmul_precision("highest"):
        for ci, row in picks:
            c = calls[ci]
            seq = np.zeros(max_seq, np.int32)
            seq[:c.prompt_len] = c.prompts[row]
            seq[c.prompt_len:c.prompt_len + c.n_new] = c.tokens[row]
            g = jax.device_get(_gaps(mod, dims, weights, jnp.asarray(seq),
                                     control))
            # position t predicts token t + 1: the served ones
            lo, hi = c.prompt_len - 1, c.prompt_len - 1 + c.n_new
            for k, v in g.items():
                worst[k] = max(worst[k], float(np.max(v[lo:hi])))
            n_tok += c.n_new
    out = {"logit_gap": worst["served"], "tokens_compared": n_tok}
    if control:
        out["control_logit_gap"] = worst["control"]
    return out


def failed_requests(calls: Sequence[Any], vocab: int) -> int:
    """Requests served no token or a token outside the vocabulary."""
    bad = 0
    for c in calls:
        t = c.tokens
        if t.shape != (c.batch, c.n_new):
            bad += c.batch
        else:
            bad += int(np.sum(np.any((t < 0) | (t >= vocab), axis=1)))
    return bad


def verdict(readings: Mapping[str, float], limits: Mapping[str, float],
            failed: int) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and each number compared beside its limit: correct
    when no request failed and no reading is above its limit."""
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in limits.items()}
    return (failed == 0 and all(c["value"] <= c["limit"]
                                for c in checks.values()), checks)

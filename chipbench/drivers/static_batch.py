"""Closed loop, one client, static batches: the traffic of a mix file.

Every call to the system serves ``batch`` requests that share one
prompt length and one output length: the length-bucketed static batch
is the only batching the program has. The (prompt, output) pairs of
the mix's grid are walked in rounds, each a permutation of the whole
grid drawn from the seed, so every round does the same work in another
order. Prompt ids are uniform over the vocabulary, from the seed.

The measured window is made of whole rounds: it runs from the start of
the first call to the end of the round in progress when ``seconds``
have passed, so every window holds the grid's mix exactly.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Iterator, List, Mapping, Optional, Tuple

import jax
import numpy as np

ORDER_STREAM, PROMPT_STREAM, WARM_STREAM = 0, 1, 2


@dataclass
class Call:
    prompt_len: int
    n_new: int
    batch: int
    t_start: float
    t_end: float
    prompts: np.ndarray        # (batch, prompt_len) int32
    tokens: np.ndarray         # (batch, n_new) int32, as served

    @property
    def latency_s(self) -> float:
        return self.t_end - self.t_start


class StaticBatch:
    def __init__(self, traffic: Mapping[str, Any], vocab: int, seed: int
                 ) -> None:
        self.batch = int(traffic["batch"])
        self.prompt_lens = [int(s) for s in traffic["prompt_lens"]]
        self.grid: List[Tuple[int, int]] = [
            (s, int(n)) for s in self.prompt_lens
            for n in traffic["output_lens"]]
        if max(s + n for s, n in self.grid) > traffic["max_seq"]:
            raise ValueError("a grid point does not fit max_seq")
        self.vocab = vocab
        self.seed = seed
        self._order = np.random.default_rng([seed, ORDER_STREAM])
        self._prompts = np.random.default_rng([seed, PROMPT_STREAM])

    def _draw(self, rng: np.random.Generator, s: int) -> np.ndarray:
        return rng.integers(0, self.vocab, (self.batch, s), dtype=np.int32)

    def rounds(self) -> Iterator[List[Tuple[int, int]]]:
        while True:
            perm = self._order.permutation(len(self.grid))
            yield [self.grid[i] for i in perm]

    def warm_up(self, generate: Callable[[np.ndarray, int], np.ndarray]
                ) -> None:
        """Compile every prefill shape of the grid and the decode step."""
        rng = np.random.default_rng([self.seed, WARM_STREAM])
        for s in self.prompt_lens:
            generate(self._draw(rng, s), 2)
        settle()

    def run(self, generate: Callable[[np.ndarray, int], np.ndarray],
            seconds: float, max_rounds: Optional[int] = None) -> List[Call]:
        """Whole rounds until ``seconds`` have passed (or ``max_rounds``)."""
        calls: List[Call] = []
        t0 = time.perf_counter()
        for k, rnd in enumerate(self.rounds()):
            for s, n in rnd:
                with jax.profiler.TraceAnnotation("chipbench.prepare"):
                    prompts = self._draw(self._prompts, s)
                t_start = time.perf_counter()
                with jax.profiler.TraceAnnotation("chipbench.call"):
                    tokens = generate(prompts, n)
                t_end = time.perf_counter()
                with jax.profiler.TraceAnnotation("chipbench.post"):
                    calls.append(Call(s, n, self.batch, t_start, t_end,
                                      prompts, np.asarray(tokens)))
            if (time.perf_counter() - t0 >= seconds
                    or (max_rounds is not None and k + 1 >= max_rounds)):
                return calls
        raise AssertionError("unreachable")


Driver = StaticBatch


def settle() -> None:
    """Wait until the device has run everything queued so far (a call
    returns before its last, discarded decode step has run)."""
    (jax.numpy.zeros(()) + 1).block_until_ready()

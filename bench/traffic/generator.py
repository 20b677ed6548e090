"""The one traffic generator: step-addressed, seeded Zipf token batches.

A copy of the program's ``data/pipeline.py`` ``Dataset._rng``/``_tokens``/
``batch`` (the same draws in the same order), so that no later change to
the program can move the traffic. A mix is a JSON file beside this one:

  seq_len       tokens per row (target length; the source length too)
  global_batch  rows per step over all chips
  zipf_a        skew of the token ids (<= 1: uniform)
  src_zipf_a    skew of the encoder's source ids (null: as zipf_a)
  burst_steps   the first steps draw at burst_zipf_a instead
  burst_zipf_a

``batch(step)`` is what the trainer calls; each call is timed on the host
clock and wrapped in a ``bench.batch`` trace span.
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np


class Traffic:
    def __init__(self, mix: dict, vocab: int, seed: int, encdec: bool):
        self.vocab = vocab
        self.seq_len = int(mix["seq_len"])
        self.global_batch = int(mix["global_batch"])
        self.zipf_a = float(mix["zipf_a"])
        src = mix.get("src_zipf_a")
        self.src_zipf_a = None if src is None else float(src)
        self.burst_steps = int(mix.get("burst_steps", 0))
        self.burst_zipf_a = float(mix.get("burst_zipf_a", 0.0))
        self.seed = seed
        self.encdec = encdec
        self.seconds = []      # host seconds of each batch() call

    def _rng(self, step: int) -> np.random.Generator:
        return np.random.default_rng(np.random.SeedSequence([self.seed, step]))

    def _tokens(self, rng, shape, a: Optional[float] = None) -> np.ndarray:
        a = self.zipf_a if a is None else a
        if a <= 1.0:
            return rng.integers(0, self.vocab, size=shape, dtype=np.int64) \
                .astype(np.int32)
        ranks = rng.zipf(a, size=shape)
        return ((ranks - 1) % self.vocab).astype(np.int32)

    def draw(self, step: int) -> dict:
        rng = self._rng(step)
        b, s = self.global_batch, self.seq_len
        a = self.burst_zipf_a if step < self.burst_steps else None
        toks = self._tokens(rng, (b, s + 1), a)
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if self.encdec:
            out["src_tokens"] = self._tokens(rng, (b, s), self.src_zipf_a)
        return out

    def batch(self, step: int) -> dict:
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.batch"):
            out = self.draw(step)
        self.seconds.append(time.perf_counter() - t)
        return out

    @property
    def target_tokens(self) -> int:
        """Target tokens of one step over all chips."""
        return self.global_batch * self.seq_len

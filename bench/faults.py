"""Faults planted in the timed path, for the benchmark's own tests: each
must make a run's ``correct`` come out false.

  * ``token``: a token altered where it is produced: the program's sampler
    returns the next token id for every request's sixth output token;
  * ``stale_cache``: a step that returns its state unchanged: the mixed and
    decode steps hand back the KV cache they were given, so nothing the
    prompt or the decoded tokens wrote is ever read.
"""

from __future__ import annotations

from bench import program as PG

M = PG.M  # the program's model module


class Fault:
    def __init__(self, kind: str):
        if kind not in ("token", "stale_cache"):
            raise ValueError(kind)
        self.kind, self._saved = kind, {}

    def install(self) -> None:
        names = {"token": ("sample_tokens",),
                 "stale_cache": ("mixed_step", "decode_step")}[self.kind]
        self._saved = {n: getattr(M, n) for n in names}
        for n in names:
            setattr(M, n, getattr(self, "_" + n)(self._saved[n]))

    def remove(self) -> None:
        for n, f in self._saved.items():
            setattr(M, n, f)
        self._saved = {}

    @staticmethod
    def _sample_tokens(orig):
        def sample(logits, temps, top_k, top_p, seeds, counters):
            tok = orig(logits, temps, top_k, top_p, seeds, counters)
            return tok + (counters == 5).astype(tok.dtype)
        return sample

    @staticmethod
    def _mixed_step(orig):
        def step(params, tokens, pos, n_real, caches, *a, **kw):
            logits, _ = orig(params, tokens, pos, n_real, caches, *a, **kw)
            return logits, caches
        return step

    @staticmethod
    def _decode_step(orig):
        def step(params, tokens, pos, caches, *a, **kw):
            logits, _ = orig(params, tokens, pos, caches, *a, **kw)
            return logits, caches
        return step

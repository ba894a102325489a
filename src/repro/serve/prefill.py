"""Prefill strategies: how a request's prompt gets written into its cache.

``ChunkedPrefill`` is the batched path: the prompt is split into fixed-size
chunks and each chunk lowers through ONE jitted call that embeds, attends
(through the cache, so later chunks see earlier ones), and scatters the
quantized K/V into the request's cache rows — ``model.prefill_into_slot``
against the dense slot backend, ``model.prefill_into_pages`` against the
paged backend (the request's block-table row is a traced argument, so one
trace serves every page assignment). A prompt of length S costs
ceil(S / chunk) jitted calls, versus S full ``(n_slots, 1)`` decode steps on
the pre-refactor path. The chunk size is fixed, so there is exactly one
trace (per backend) regardless of prompt length; the final chunk is
right-padded and ``last_idx`` selects the real last-token logits.

``StepwisePrefill`` is that pre-refactor path, kept as (a) the fallback for
recurrent-state families whose caches absorb every token unconditionally and
(b) the bit-exactness regression baseline the chunked path is tested against.

Both strategies call ``cache.prepare(slot, n)`` before writing n rows — the
paged backend draws physical pages on demand there — and RETURN the last
real prompt token's logits, which the engine feeds to the SAME batched
sampler its decode step fuses (``models.model.sample_tokens``, counter 0 of
the request's PRNG stream): the first output token costs no decode step and
no duplicate ``prompt[-1]`` cache row, and greedy/stochastic behavior is
identical between the first token and every later one (see ServeEngine).

Both also SKIP the already-cached prefix: the slot's write position at
prefill time is the number of prompt tokens the cache manager has already
made resident (always 0 on slot/paged; the prefix backend maps matched
pages at acquire and advances ``pos`` past them — serve/prefix.py), so a
prompt with a shared prefix costs O(S_new/chunk) jitted calls, not
O(S/chunk). Bit-exactness is unaffected: a suffix chunk at offset ``pos``
is numerically the same computation whether the earlier rows were written
by this request or mapped from a shared page.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import PrecisionPolicy
from repro.models import model as M
from repro.models.model import ArchConfig
from repro.serve.boundary import host_copy


class PrefillCursor:
    """One request's in-progress prompt, chunked into mixed steps.

    The continuous-batching engine does not run ``ChunkedPrefill.prefill``'s
    blocking loop; it keeps a cursor per admitted-but-not-yet-prefilled slot
    and, each step, asks the scheduler to split the mixed-step token budget
    across the live cursors (``Scheduler.allot``). ``take(n)`` hands out the
    next ``n`` prompt tokens; when ``done``, the slot flips to a decode lane
    and its request's first output token samples from the same last-token
    logits the serialized prefill path returns.

    ``off`` starts at the slot's resident position (a matched shared prefix
    on the prefix backend is skipped exactly as in ``ChunkedPrefill``);
    ``order`` is the admission sequence number FCFS allotment sorts by.
    """

    __slots__ = ("req", "prompt", "slot", "order", "off", "chunks")

    def __init__(self, req, prompt: np.ndarray, *, slot: int, order: int,
                 off: int = 0):
        self.req = req
        self.prompt = np.asarray(prompt, np.int32)
        self.slot = slot
        self.order = order
        self.off = int(off)
        self.chunks = 0  # chunks taken so far (trace span index)

    @property
    def remaining(self) -> int:
        return len(self.prompt) - self.off

    @property
    def done(self) -> bool:
        return self.off >= len(self.prompt)

    def take(self, n: int) -> np.ndarray:
        """Consume and return the next ``min(n, remaining)`` prompt tokens."""
        n = min(int(n), self.remaining)
        chunk = self.prompt[self.off : self.off + n]
        self.off += n
        self.chunks += 1
        return chunk


class ChunkedPrefill:
    """Single-request batched/chunked prefill (slot or paged backend)."""

    name = "chunked"

    def __init__(self, params, cfg: ArchConfig, policy: PrecisionPolicy, *,
                 impl="auto", chunk: int = 16,
                 page_size: Optional[int] = None):
        if not self.supports(cfg):
            raise NotImplementedError(
                f"chunked prefill unsupported for family {cfg.family!r} "
                f"(supported: {M.PREFILL_CHUNKABLE_FAMILIES}); use "
                f"StepwisePrefill")
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.params = params
        self.chunk = chunk
        self.page_size = page_size
        self.jit_calls = 0  # jitted prefill invocations (the O(S/chunk) claim)
        self.tracer = None  # set by the engine; chunk spans when attached
        # two traces: non-final chunks only fill the cache (no final-norm /
        # vocab-head matmul); the final chunk also returns last-token logits.
        # `ref` is the request's cache address: slot index (dense) or the
        # slot's block-table row (paged) — same argument slot either way.
        # Named functions: a profiler trace shows the programs as
        # jit_serve_prefill_last / jit_serve_prefill_chunk.
        if page_size is None:
            into = M.prefill_into_slot
        else:
            into = functools.partial(M.prefill_into_pages, page_size=page_size)

        def serve_prefill_last(p, toks, ref, pos, last, caches):
            return into(p, toks, ref, pos, caches, cfg, policy, last_idx=last,
                        impl=impl)

        def serve_prefill_chunk(p, toks, ref, pos, caches):
            return into(p, toks, ref, pos, caches, cfg, policy, head=False,
                        impl=impl)

        self._fn_last = jax.jit(serve_prefill_last)
        self._fn_mid = jax.jit(serve_prefill_chunk)

    @staticmethod
    def supports(cfg: ArchConfig) -> bool:
        return cfg.family in M.PREFILL_CHUNKABLE_FAMILIES

    def prefill(self, cache, slot: int, prompt: np.ndarray, *,
                rid: Optional[int] = None):
        """Write ``prompt`` into ``slot`` starting at its current position.
        Returns the last real prompt token's logits (1, 1, V). Tokens the
        cache already holds (``cache.pos[slot]`` > 0: a matched shared
        prefix) are skipped — only the suffix is chunked through the jits."""
        start = int(cache.pos[slot])
        prompt = prompt[start:]
        S = len(prompt)
        logits = None
        off = 0
        idx = 0
        while off < S:
            n = min(self.chunk, S - off)
            t0 = time.perf_counter() if self.tracer is not None else 0.0
            toks = np.zeros((1, self.chunk), np.int32)
            toks[0, :n] = prompt[off : off + n]
            cache.prepare(slot, n)  # paged backend draws pages on demand
            # the block-table row crosses the jit boundary as a SNAPSHOT
            # (host_copy): prepare() for the next chunk mutates the live
            # table while this chunk's dispatch may still be in flight
            ref = (host_copy(cache.block_tables[slot]) if cache.paged
                   else jnp.int32(slot))
            args = (self.params, jnp.asarray(toks), ref,
                    jnp.int32(cache.pos[slot]))
            if off + n >= S:  # final chunk: last-token logits + pad scrub
                logits, cache.caches = self._fn_last(
                    *args, jnp.int32(n - 1), cache.caches)
            else:
                _, cache.caches = self._fn_mid(*args, cache.caches)
            cache.advance(slot, n)
            self.jit_calls += 1
            if self.tracer is not None:
                # host-side chunk cost (build + dispatch; async device work
                # overlaps) — one span per jitted chunk call
                self.tracer.span(
                    f"prefill_chunk[{idx}]", cat="request", t0=t0,
                    t1=time.perf_counter(), track=slot + 1,
                    rid=rid, slot=slot, tokens=n, offset=start + off)
            off += n
            idx += 1
        return logits


class StepwisePrefill:
    """Token-by-token prefill through the engine's full-batch decode step.

    ``step_fn`` maps an ``(n_slots, 1)`` token batch to that step's logits
    — the engine passes an adapter over its fused decode+sample jit that
    returns the logits and discards the sampled lane tokens (sampling
    during a prefill step is idle-lane work by definition). Other slots
    receive token 0; their write positions do not advance, so any transient
    row writes are overwritten by their next real step — or, on the paged
    backend, land in the scratch page their unallocated block-table entries
    point at. This is the pre-refactor data path, byte for byte.
    """

    name = "stepwise"

    def __init__(self, step_fn: Callable[[np.ndarray], jax.Array], n_slots: int):
        self._step = step_fn
        self.n_slots = n_slots
        self.chunk = 1
        self.jit_calls = 0
        # accepted for interface parity; per-TOKEN chunk spans would flood
        # the ring (chunk == 1), so the engine-level prefill span is the
        # stepwise path's trace granularity
        self.tracer = None

    @staticmethod
    def supports(cfg: ArchConfig) -> bool:
        return True

    def prefill(self, cache, slot: int, prompt: np.ndarray, *,
                rid: Optional[int] = None):
        logits = None
        for tok in prompt[int(cache.pos[slot]):]:  # skip the matched prefix
            toks = np.zeros((self.n_slots, 1), np.int32)
            toks[slot, 0] = tok
            cache.prepare(slot, 1)
            logits = self._step(toks)
            cache.advance(slot, 1)
            self.jit_calls += 1
        return None if logits is None else logits[slot : slot + 1, -1:]


def make_prefiller(mode: str, params, cfg: ArchConfig,
                   policy: PrecisionPolicy, *, impl, chunk: int,
                   step_fn: Callable, n_slots: int,
                   page_size: Optional[int] = None):
    """Resolve the prefill strategy: ``auto`` picks chunked when the family
    supports it and falls back to stepwise (hybrid/rwkv/encdec/vlm).
    ``page_size`` (set by the engine when the cache backend is paged) makes
    the chunked path lower through the page pool."""
    if mode == "auto":
        mode = "chunked" if ChunkedPrefill.supports(cfg) else "stepwise"
    if mode == "chunked":
        return ChunkedPrefill(params, cfg, policy, impl=impl, chunk=chunk,
                              page_size=page_size)
    if mode == "stepwise":
        return StepwisePrefill(step_fn, n_slots)
    raise ValueError(f"unknown prefill mode {mode!r} "
                     f"(expected auto | chunked | stepwise)")

"""Serving facade: session-based request lifecycle over a modular stack.

The engine is a thin composition of the serving subsystem's parts — this
module owns ONLY the decode loop, lifecycle bookkeeping, and observability:

  * :mod:`repro.serve.api`                   — the client surface:
    ``SamplingParams`` (greedy | temperature/top-k/top-p, per-request seed,
    stop sequences), ``Request`` lifecycle state, ``RequestHandle``
    (streaming iterator / result / cancel);
  * :mod:`repro.serve.cache`                 — cache rows/pages, per-slot
    write positions, recycling, capacity checks. Backend-selected:
    ``cache="slot"`` (dense per-slot stripes), ``cache="paged"`` (global
    page pool + block tables), or ``cache="prefix"`` (paged + radix-indexed
    copy-on-write prefix sharing, serve/prefix.py);
  * :class:`repro.serve.scheduler.Scheduler` — admission order (pluggable:
    ``fcfs`` / ``spf`` / ``bestfit`` / ``priority`` / any instance);
  * :mod:`repro.serve.prefill`               — how prompts enter the cache
    (batched/chunked via ``model.prefill_into_slot`` /
    ``model.prefill_into_pages``, or token-by-token).

Request lifecycle (API v1): ``submit(prompt, params, priority=, deadline=)``
returns a :class:`RequestHandle`; the caller owns the loop via ``step()`` /
``drain()`` / ``close()`` (``handle.tokens()`` streams by stepping on
demand; ``handle.cancel()`` releases cache resources mid-decode —
refcounted pages a surviving sharer still reads are decref'd, never
zeroed). ``run()`` is a thin batch-mode compat wrapper over submit+drain.

Decode remains ONE jitted call per step: ``models.model.decode_step`` over
``n_slots`` static slots with per-slot cache positions (continuous
batching: admission happens while other slots keep decoding), now fused
with the ONE batched sampler ``models.model.sample_tokens`` — per-slot
temperature/top-k/top-p/seed vectors and a counter-based PRNG key ride the
same jit, so greedy slots still lower to the old argmax (bit-identical
tokens) and stochastic slots stay reproducible and slot-independent. The
FIRST output token of every request is sampled from the prefill's own
last-token logits through that same sampler (the old engine had a second,
hand-rolled argmax here). Completion, stop-sequence hits, and cancellation
all route through one ``_release`` path that recycles cache resources,
stamps lifecycle timestamps, and harvests kernel stats. ``metrics()``
snapshots TTFT/TPOT percentiles (``slo/`` namespace, streaming histograms;
TTFT keeps its queue-wait vs prefill-time split), throughput, lifecycle
counters (cancelled / stopped_on_sequence / deadline_misses), queue depth,
page-pool health, and straggler counts.

``mixed=True`` (chunkable families only) switches the loop to CONTINUOUS
batching — the engine-loop restructuring the serialized mode's step
anatomy cannot express:

  * **Mixed steps** (``models.model.mixed_step``, packed: only the
    ``mixed_budget + n_slots`` rows that can carry a token are computed):
    prefill chunks ride the decode batch under a per-step token budget
    (``mixed_budget``, at most ``MIXED_MAX_CHUNKS`` chunks), so a
    long prompt no longer monopolizes the device between decode steps —
    in-flight streams keep their inter-token cadence while the newcomer
    prefills ``Scheduler.allot``-sized chunks per step.
  * **Ahead-of-time dispatch**: up to ``inflight`` steps are issued before
    the first result is read back. Each step's next-token input is the
    PREVIOUS step's on-device sampled output (``_chain`` — no host round
    trip), host bookkeeping crosses the boundary through a
    :class:`~repro.serve.boundary.SnapshotRing` (the pipelined form of the
    ``host_copy`` discipline), and the only host sync in the hot loop is
    retiring the oldest ticket. Sampling-counter and budget state is
    advanced speculatively at dispatch; a release (stop hit, cancel, slot
    turnover) simply invalidates the slot's still-in-flight tickets — the
    retire path drops them by request identity.

Token streams are bit-identical to the serialized engine on all three
cache backends: mixed-step rows are independent and pad rows never write
(packed, ``mixed_step``) or are scrubbed (padded, ``mixed_step_padded``),
and the counter-based sampler makes each stream a pure function of
(params, prompt, sampling params).
"""

from __future__ import annotations

import collections
import time
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import PrecisionPolicy
from repro.kernels import dispatch
from repro.models import model as M
from repro.models.attention import PAD_POS
from repro.models.model import ArchConfig
from repro.serve.api import (
    ACTIVE,
    CANCELLED,
    DONE,
    QUEUED,
    STOPPED,
    Request,
    RequestHandle,
    SamplingParams,
    as_params,
    check_stop,
)
from repro.serve.boundary import SnapshotRing, host_copy
from repro.serve.cache import PagedKVCache, SlotCache, make_cache
from repro.serve.prefill import ChunkedPrefill, PrefillCursor, make_prefiller
from repro.serve.scheduler import Scheduler, make_scheduler
from repro.serve.spec import DraftPolicy, make_spec
from repro.serve.stats import LatencyHistogram
from repro.serve.trace import ENGINE_TRACK, Tracer, slot_track

#: most prefill chunks one packed mixed step carries: its static number of
#: prefill query blocks (``model.mixed_step``'s P)
MIXED_MAX_CHUNKS = 2


class StepMonitor:
    """EMA step-time watchdog: flags straggler steps (> factor x EMA).
    At multi-host scale the flag feeds the coordinator's slow-host logic;
    here it logs and counts (DESIGN.md Sec. 9)."""

    def __init__(self, factor: float = 3.0, alpha: float = 0.1):
        self.factor, self.alpha = factor, alpha
        self.ema: Optional[float] = None
        self.stragglers = 0

    def observe(self, dt: float) -> bool:
        slow = self.ema is not None and dt > self.factor * self.ema
        self.ema = dt if self.ema is None else (1 - self.alpha) * self.ema + self.alpha * dt
        if slow:
            self.stragglers += 1
        return slow


class KernelStatsAccumulator:
    """Per-engine view of the process-wide dispatch counters.

    Instead of one construction-time snapshot diffed at read time (which a
    ``dispatch.reset_dispatch_counts()`` anywhere in the process silently
    wipes), deltas are harvested incrementally into an engine-owned counter:
    a reset observed between harvests loses at most the dispatches of that
    window, never the accumulated history, and per-engine counts are
    monotone by construction.
    """

    def __init__(self):
        self._counts: collections.Counter = collections.Counter()
        self._last = dict(dispatch.DISPATCH_COUNTS)

    def harvest(self) -> None:
        cur = dict(dispatch.DISPATCH_COUNTS)
        for k, v in cur.items():
            prev = self._last.get(k, 0)
            # v < prev means the process-wide counter was reset since the
            # last harvest: everything currently on it happened after.
            d = v - prev if v >= prev else v
            if d > 0:
                self._counts[k] += d
        self._last = cur

    def stats(self) -> dict[str, int]:
        self.harvest()
        return {str(k): v for k, v in sorted(self._counts.items(),
                                             key=lambda kv: str(kv[0]))}

    def op_stats(self) -> dict:
        """Per-OP rollup for ``metrics()``: ``kernels/<op>_calls``, the cell
        counts summed over the op's permutations."""
        self.harvest()
        calls: collections.Counter = collections.Counter()
        for k, v in self._counts.items():
            calls[k.op] += v
        return {f"kernels/{op}_calls": calls[op] for op in sorted(calls)}


class ServeEngine:
    """Continuous batching over ``n_slots`` static cache slots."""

    def __init__(self, params, cfg: ArchConfig, policy: PrecisionPolicy, *,
                 n_slots: int = 4, s_max: int = 64, impl="auto",
                 scheduler: Union[str, Scheduler, None] = "fcfs",
                 prefill: str = "auto", prefill_chunk: int = 16,
                 cache: Union[str, SlotCache, PagedKVCache, None] = "slot",
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 fused_attn: Optional[bool] = None,
                 mixed: bool = False,
                 mixed_budget: Optional[int] = None,
                 inflight: int = 2,
                 spec: Union[str, DraftPolicy, None] = None,
                 spec_k: int = 4,
                 trace: Optional[Tracer] = None):
        self.params, self.cfg, self.policy = params, cfg, policy
        #: optional event sink (serve/trace.py). None = zero overhead: every
        #: emission site is behind an `is not None` check.
        self.trace = trace
        # fused decode default-on where the attn_decode bench gate holds
        # (>= 1.1x on every measured KV dtype; benchmarks/lm_serving.py
        # run_attn_decode asserts greedy token-equality fused vs unfused).
        # vlm keeps the unfused default pending a gate measurement of the
        # mrope path; fused_attn=False stays the escape hatch.
        if fused_attn is None:
            fused_attn = cfg.family in M.PREFILL_CHUNKABLE_FAMILIES
        self.fused_attn = bool(fused_attn)
        fused_attn = self.fused_attn
        # fail at construction, not mid-decode, if the policy needs a kernel
        # cell outside the registered 27-permutation library
        dispatch.ensure_policy_supported(policy)
        self.n_slots, self.s_max = n_slots, s_max
        self.impl = impl
        self.cache = make_cache(cache, cfg, policy, n_slots, s_max,
                                page_size=page_size, n_pages=n_pages)
        self.scheduler = make_scheduler(scheduler)
        self.monitor = StepMonitor()
        self._kstats = KernelStatsAccumulator()
        self.slot_req: list[Optional[Request]] = [None] * n_slots
        self.slot_remaining = np.zeros(n_slots, np.int32)

        # per-slot sampling state: the vectors the fused sampler consumes.
        # Idle slots carry temp=0 (greedy argmax, token discarded), so one
        # trace serves every mix of greedy/stochastic/idle lanes.
        self._temps = np.zeros(n_slots, np.float32)
        self._top_ks = np.zeros(n_slots, np.int32)
        self._top_ps = np.ones(n_slots, np.float32)
        self._seeds = np.zeros(n_slots, np.uint32)
        self._counters = np.zeros(n_slots, np.int32)

        def decode_and_sample(p, tok, pos, caches, samp, bt=None):
            logits, new_caches = M.decode_step(
                p, tok, pos, caches, cfg, policy, impl=impl, block_tables=bt,
                fused_attn=fused_attn)
            nxt = M.sample_tokens(logits[:, -1], *samp)
            return nxt, logits, new_caches

        # every program is jitted from a function named for it, so a profiler
        # trace shows it as jit_<name>(<hash>): serve_decode, serve_sample,
        # serve_mixed_step, serve_decode_step, serve_spec_draft,
        # serve_spec_verify (and the prefiller's serve_prefill_*)
        if self.cache.paged:
            def serve_decode(p, tok, pos, bt, caches, samp):
                return decode_and_sample(p, tok, pos, caches, samp, bt=bt)
        else:
            def serve_decode(p, tok, pos, caches, samp):
                return decode_and_sample(p, tok, pos, caches, samp)
        self._decode = jax.jit(serve_decode)

        # the SAME sampler, traced once more at B=1 for the prefill's
        # last-token logits (the first output token of every request)
        def serve_sample(logits, temps, top_ks, top_ps, seeds, counters):
            return M.sample_tokens(logits, temps, top_ks, top_ps, seeds,
                                   counters)

        self._sample = jax.jit(serve_sample)
        self.prefiller = make_prefiller(
            prefill, params, cfg, policy, impl=impl, chunk=prefill_chunk,
            step_fn=lambda toks: self._step(toks)[1], n_slots=n_slots,
            page_size=self.cache.page_size if self.cache.paged else None)
        self.prefiller.tracer = trace  # chunked path emits per-chunk spans
        #: last cache-counter snapshot (trace mode): per-step deltas of page
        #: draws / COW copies / evictions ride the step span's args
        self._cache_ctr_last = self.cache.counters() if trace else None

        # --- continuous batching (mixed steps + ahead-of-time dispatch) ----
        self.mixed = bool(mixed)
        if self.mixed and not isinstance(self.prefiller, ChunkedPrefill):
            raise ValueError(
                f"mixed=True needs the chunked prefill path; family "
                f"{cfg.family!r} (prefill={self.prefiller.name!r}) serves "
                f"serialized only")
        self.mixed_budget = int(prefill_chunk if mixed_budget is None
                                else mixed_budget)
        if self.mixed_budget < 1:
            raise ValueError(f"mixed_budget must be >= 1, got {mixed_budget}")
        self.inflight_depth = int(inflight)
        if self.inflight_depth < 1:
            raise ValueError(f"inflight must be >= 1, got {inflight}")
        #: slot -> PrefillCursor: admitted requests whose prompts are still
        #: entering the cache, chunk by budget-allotted chunk
        self._prefilling: dict[int, PrefillCursor] = {}
        self._admit_seq = 0  # cursor ordering for Scheduler.allot
        #: dispatched-but-not-retired steps, oldest first. Each ticket is
        #: (device next-token vector, [(slot, request, emits), ...]); depth
        #: is bounded by ``inflight``.
        self._tickets: collections.deque = collections.deque()
        #: the previous dispatch's on-device sampled tokens — next step's
        #: decode-lane input, chained device-to-device (no host round trip)
        self._chain = jnp.zeros((n_slots,), jnp.int32)
        #: dispatch-owned speculative token budget per slot (the retire-side
        #: twin is slot_remaining, owned by _emit)
        self._spec_remaining = np.zeros(n_slots, np.int32)
        self._ring = SnapshotRing(self.inflight_depth + 2)
        self._progress = 0  # admissions+dispatches+retires+releases (drain)
        self._mixed_steps = 0
        #: mixed steps run packed (model.mixed_step) for PACKED_FAMILIES;
        #: moe / mla_moe keep the padded (n_slots, budget) batch
        self._packed = packed = cfg.family in M.PACKED_FAMILIES
        if self.mixed:
            ps = self.cache.page_size if self.cache.paged else None
            W = self.mixed_budget

            def mixed_and_sample(p, host_toks, chain, use_chain, pos, rows,
                                 caches, samp, bt=None):
                # decode lanes take their input from the DEVICE chain (the
                # previous step's sampled output); prefill/idle lanes keep
                # the host-provided rows
                if packed:
                    lanes, emit, starts = rows
                    toks = host_toks.at[W:].set(jnp.where(use_chain, chain, 0))
                    logits, new_caches = M.mixed_step(
                        p, toks, pos, lanes, caches, cfg, policy, emit=emit,
                        starts=starts, impl=impl, block_tables=bt)
                else:
                    toks = host_toks.at[:, 0].set(
                        jnp.where(use_chain, chain, host_toks[:, 0]))
                    logits, new_caches = M.mixed_step_padded(
                        p, toks, pos, rows, caches, cfg, policy, impl=impl,
                        block_tables=bt, page_size=ps)
                nxt = M.sample_tokens(logits[:, 0], *samp)
                return nxt, new_caches

            def chain_and_sample(p, chain, pos, caches, samp, bt=None):
                # pure-decode fast path: S=1, fused attention eligible
                logits, new_caches = M.decode_step(
                    p, chain[:, None], pos, caches, cfg, policy, impl=impl,
                    block_tables=bt, fused_attn=fused_attn)
                nxt = M.sample_tokens(logits[:, -1], *samp)
                return nxt, new_caches

            if self.cache.paged:
                def serve_mixed_step(p, toks, chain, uc, pos, rows, bt, caches,
                                     samp):
                    return mixed_and_sample(p, toks, chain, uc, pos, rows,
                                            caches, samp, bt=bt)

                def serve_decode_step(p, chain, pos, bt, caches, samp):
                    return chain_and_sample(p, chain, pos, caches, samp, bt=bt)
            else:
                def serve_mixed_step(p, toks, chain, uc, pos, rows, caches,
                                     samp):
                    return mixed_and_sample(p, toks, chain, uc, pos, rows,
                                            caches, samp)

                def serve_decode_step(p, chain, pos, caches, samp):
                    return chain_and_sample(p, chain, pos, caches, samp)
            self._mixed = jax.jit(serve_mixed_step)
            self._chain_decode = jax.jit(serve_decode_step)

        # --- speculative decoding (serve/spec.py) --------------------------
        self.spec = make_spec(spec)
        self.spec_k = int(spec_k)
        self._spec_rounds = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._h_spec_len = LatencyHistogram()
        if self.spec is not None:
            if self.mixed:
                raise ValueError(
                    "spec and mixed are mutually exclusive: acceptance makes "
                    "the tokens a step retires dynamic (1..k+1), which "
                    "ahead-of-time dispatch cannot express — its in-flight "
                    "steps pre-commit counters and chain inputs")
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            self.spec.build(self)
            k = self.spec_k
            dcfg, dpolicy = self.spec.cfg, self.spec.policy

            def draft_loop(p, tok0, pos, caches, samp, bt=None):
                # k chained draft steps in ONE jit (lax.scan): step j writes
                # cache row pos+j and samples at counter+j — the exact PRNG
                # cell verify scores at offset j, so a draft whose logits
                # match the target's is always accepted. fused_attn stays
                # off: drafts must track the (unfused) verify numerics, and
                # a ulp drift here costs acceptance for nothing.
                temps, top_ks, top_ps, seeds, counters = samp

                def body(carry, j):
                    tok, caches = carry
                    logits, caches = M.decode_step(
                        p, tok[:, None], pos + j, caches, dcfg, dpolicy,
                        impl=impl, block_tables=bt, fused_attn=False)
                    nxt = M.sample_tokens(logits[:, -1], temps, top_ks,
                                          top_ps, seeds, counters + j)
                    return (nxt, caches), nxt

                (_, caches), drafts = jax.lax.scan(
                    body, (tok0, caches), jnp.arange(k, dtype=jnp.int32))
                return drafts.T, caches

            if self.spec.shares_cache and self.cache.paged:
                def serve_spec_draft(p, tok0, pos, bt, caches, samp):
                    return draft_loop(p, tok0, pos, caches, samp, bt=bt)
            else:
                def serve_spec_draft(p, tok0, pos, caches, samp):
                    return draft_loop(p, tok0, pos, caches, samp)
            self._spec_draft = jax.jit(serve_spec_draft)

            spec_ps = self.cache.page_size if self.cache.paged else None

            def verify(p, toks, pos, n_real, caches, samp, bt=None):
                return M.spec_verify_step(
                    p, toks, pos, n_real, *samp, caches, cfg, policy,
                    impl=impl, block_tables=bt, page_size=spec_ps)

            if self.cache.paged:
                def serve_spec_verify(p, toks, pos, nr, bt, caches, samp):
                    return verify(p, toks, pos, nr, caches, samp, bt=bt)
            else:
                def serve_spec_verify(p, toks, pos, nr, caches, samp):
                    return verify(p, toks, pos, nr, caches, samp)
            self._spec_verify = jax.jit(serve_spec_verify)

        # metrics accumulators
        self._decode_steps = 0
        self._tokens_out = 0
        self._completed = 0
        self._cancelled = 0
        self._stopped_on_seq = 0
        self._deadline_misses = 0
        # streaming SLO histograms (no unbounded per-request lists):
        # TTFT + its queue/prefill split, and TPOT (inter-token gaps)
        self._h_ttft = LatencyHistogram()
        self._h_ttft_queue = LatencyHistogram()
        self._h_ttft_prefill = LatencyHistogram()
        self._h_tpot = LatencyHistogram()
        self._serve_seconds = 0.0
        self._run_t0: Optional[float] = None  # set while a step is active
        self._next_rid = 0
        self._closed = False
        if trace is not None:
            trace.watch_gc()  # host.gc spans; close() stops watching

    # --- kernel-matrix observability --------------------------------------

    def kernel_cells(self) -> list[str]:
        """The library cells this engine's precision policy routes through."""
        return [str(k) for k in dispatch.cells_for_policy(self.policy)]

    def kernel_stats(self) -> dict[str, int]:
        """Which cells of the 27-permutation matrix were exercised since this
        engine's construction. Counts are harvested incrementally per engine,
        so a process-wide ``dispatch.reset_dispatch_counts()`` no longer
        erases history (the old documented caveat is now a guarantee). The
        remaining caveats: dispatch happens at jit *trace* time, so treat
        counts as a coverage signal (cell was hit / retraced), not call
        volume; and dispatches of other engines in the same process between
        this engine's steps still land here."""
        return self._kstats.stats()

    # --- tracing helpers ----------------------------------------------------

    def _cache_deltas(self) -> dict:
        """Per-step deltas of the cache backend's O(1) monotone counters
        (pages drawn, COW copies, evictions, ...) since the previous step
        span — only touched while tracing."""
        cur = self.cache.counters()
        last = self._cache_ctr_last
        self._cache_ctr_last = cur
        return {k: v - last.get(k, 0) for k, v in cur.items()
                if v - last.get(k, 0)}

    def _trace_queued_exit(self, req: Request) -> None:
        """A request cancelled while still QUEUED never owned a slot, so its
        terminal events land on the engine track (same completeness contract:
        every traced request ends in a ``request`` span + ``release``)."""
        if self.trace is None:
            return
        self.trace.span("request", cat="request", t0=req.t_submit,
                        t1=req.t_done, track=ENGINE_TRACK, rid=req.rid)
        self.trace.instant("release", cat="request", track=ENGINE_TRACK,
                           ts=req.t_done, rid=req.rid, status=req.status,
                           tokens=0)

    # --- request lifecycle: submission --------------------------------------

    def submit(self, prompt, params: Optional[SamplingParams] = None, *,
               priority: int = 0, deadline: Optional[float] = None,
               rid: Optional[int] = None,
               on_token: Optional[Callable] = None) -> RequestHandle:
        """Enqueue one request; returns a :class:`RequestHandle`.

        ``params`` defaults to greedy ``SamplingParams()``. ``priority``
        (higher admits first) and ``deadline`` (seconds from now; misses are
        counted in ``metrics()``) are consumed by the ``"priority"``
        scheduler and ignored by ordering-strict policies. Nothing decodes
        until someone calls :meth:`step` / :meth:`drain` (or consumes the
        handle). Raises :class:`~repro.serve.cache.CapacityError` if the
        request can NEVER fit (reject-at-submit); merely having to wait for
        capacity queues instead."""
        params = params if params is not None else SamplingParams()
        prompt = np.asarray(prompt, np.int32)
        if rid is None:
            rid = self._next_rid
        req = Request(rid=rid, prompt=prompt, max_new=params.max_new,
                      params=params, priority=priority, deadline=deadline,
                      on_token=on_token)
        return self._submit_request(req)

    def _submit_request(self, req: Request) -> RequestHandle:
        """Shared submission path (``submit()`` and the ``run()`` compat
        wrapper): normalize params, validate capacity, stamp ``t_submit``,
        hand to the scheduler."""
        if self._closed:
            raise RuntimeError("engine is closed")
        if req.params is None:  # legacy batch construction: greedy defaults
            req.params = SamplingParams(max_new=req.max_new)
        req.max_new = req.params.max_new
        if len(req.prompt) == 0:
            # reject HERE, not mid-_admit: failing after acquire() would
            # leave a busy slot bound to a request with no tokens to feed,
            # wedging every later step()
            raise ValueError("prompt must hold at least one token")
        self.cache.check_admissible(len(req.prompt) + req.max_new)
        now = time.perf_counter()
        req.t_submit = now
        req.t_deadline = None if req.deadline is None else now + req.deadline
        req.status = QUEUED
        req.out = []
        self._next_rid = max(self._next_rid, req.rid + 1)
        self.scheduler.submit([req])
        if self.trace is not None:
            self.trace.instant("submit", cat="request", track=ENGINE_TRACK,
                               ts=now, rid=req.rid,
                               prompt_tokens=len(req.prompt),
                               max_new=req.max_new)
        return RequestHandle(self, req)

    def cancel(self, req: Request) -> bool:
        """Cancel a queued or active request, releasing whatever it holds.

        Queued: removed from the scheduler (no cache state exists yet).
        Active: its slot routes through the same ``_release`` path as
        completion — on the paged backends its pages are decref'd and only
        pages with no other reader are zeroed/recycled, so cancelling one
        of two prefix sharers never perturbs the survivor. Returns False if
        the request had already finished (idempotent)."""
        if req.finished:
            return False
        if req.status == QUEUED:
            if not self.scheduler.remove(req):
                return False  # unknown request (never submitted here)
            req.status = CANCELLED
            req.t_done = time.perf_counter()
            self._cancelled += 1
            self._trace_queued_exit(req)
            return True
        self._release(req.slot, CANCELLED)
        return True

    def close(self) -> None:
        """Cancel everything in flight and refuse further submissions.
        Idempotent; the caches/jits stay warm for inspection but the engine
        will not serve again."""
        if self._closed:
            return
        while self.scheduler.pending():
            req = self.scheduler.next_request()
            req.status = CANCELLED
            req.t_done = time.perf_counter()
            self._cancelled += 1
            self._trace_queued_exit(req)
        for s, r in enumerate(self.slot_req):
            if r is not None:
                self._release(s, CANCELLED)
        self._tickets.clear()  # in-flight steps: nobody left to emit for
        if self.trace is not None:
            self.trace.unwatch_gc()
        self._closed = True

    # --- request lifecycle: the loop ----------------------------------------

    def _step(self, toks: np.ndarray):
        """One fused decode+sample step with per-slot cache positions.

        ``pos``, the block tables, and the per-slot sampling vectors cross
        the jit boundary through ``host_copy``: ``jnp.asarray`` zero-copy-
        aliases numpy buffers on the CPU backend, and dispatch is async —
        handing the live bookkeeping buffers to the decode while the caller
        then advances positions / draws pages / rewrites sampling state is
        a data race (see serve.boundary). Returns (sampled (B,) int32,
        logits (B, 1, V))."""
        t0 = time.perf_counter()
        samp = (host_copy(self._temps), host_copy(self._top_ks),
                host_copy(self._top_ps), host_copy(self._seeds),
                host_copy(self._counters))
        if self.cache.paged:
            nxt, logits, self.cache.caches = self._decode(
                self.params, jnp.asarray(toks), host_copy(self.cache.pos),
                host_copy(self.cache.block_tables), self.cache.caches, samp)
        else:
            nxt, logits, self.cache.caches = self._decode(
                self.params, jnp.asarray(toks), host_copy(self.cache.pos),
                self.cache.caches, samp)
        self.monitor.observe(time.perf_counter() - t0)
        return nxt, logits

    def _release(self, slot: int, status: str = DONE) -> None:
        """THE exit path — completion, stop-sequence hit, and cancellation
        all converge here: recycle the slot's cache resources (refcounted
        pages a sharer still reads are decref'd, never zeroed), clear the
        slot's sampling lanes back to idle/greedy, stamp lifecycle
        timestamps, count the outcome, and harvest kernel stats."""
        r = self.slot_req[slot]
        now = time.perf_counter()
        r.status = status
        r.t_done = now
        if self.trace is not None:
            # terminal span chain, emitted now that every end is known: the
            # decode span exists only if a first token was ever produced
            # (r.t_first pre-defensive-stamp), the request span always.
            if r.t_first != 0.0:
                self.trace.span("decode", cat="request", t0=r.t_first, t1=now,
                                track=slot_track(slot), rid=r.rid,
                                tokens=len(r.out))
            self.trace.span("request", cat="request", t0=r.t_submit, t1=now,
                            track=slot_track(slot), rid=r.rid)
            self.trace.instant("release", cat="request",
                               track=slot_track(slot), ts=now, rid=r.rid,
                               status=status, tokens=len(r.out))
        if r.t_first == 0.0:  # defensive: released before any token
            r.t_first = now
        self.slot_req[slot] = None
        self.slot_remaining[slot] = 0
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._top_ps[slot] = 1.0
        self._seeds[slot] = 0
        self._counters[slot] = 0
        # continuous mode: drop the slot's prefill cursor and speculative
        # budget; its still-in-flight tickets retire as no-ops (the retire
        # path checks request identity before emitting)
        self._prefilling.pop(slot, None)
        self._spec_remaining[slot] = 0
        self._progress += 1
        self.cache.release(slot)
        if self.spec is not None:
            self.spec.on_release(slot, self)
        if status == CANCELLED:
            self._cancelled += 1
        else:
            self._completed += 1
        if status == STOPPED:
            self._stopped_on_seq += 1
        # an SLO miss is a request WE finished too late; a client-initiated
        # cancel is not a miss (and must count the same whether the request
        # was still queued or already decoding when cancelled)
        if (status != CANCELLED and r.t_deadline is not None
                and now > r.t_deadline):
            self._deadline_misses += 1
        self._kstats.harvest()

    def _emit(self, slot: int, tok: int) -> None:
        """Record one generated token for the request bound to ``slot``,
        releasing the slot on budget exhaustion or a stop-sequence hit."""
        r = self.slot_req[slot]
        tok = int(tok)
        r.out.append(tok)
        self.slot_remaining[slot] -= 1
        if not self.mixed:
            # counter-based PRNG: next index. In continuous mode the
            # DISPATCH side owns this speculatively (steps in flight have
            # already consumed counters past len(r.out)) — never clobber it
            # from the retire side.
            self._counters[slot] = len(r.out)
        self._tokens_out += 1
        now = time.perf_counter()
        if len(r.out) == 1:
            r.t_first = now  # stamped HERE, so max_new=1 requests get one too
            self._h_ttft.observe(now - r.t_submit)
            self._h_ttft_queue.observe(r.t_admit - r.t_submit)
            self._h_ttft_prefill.observe(now - r.t_admit)
            if self.trace is not None:
                self.trace.instant("first_token", cat="request",
                                   track=slot_track(slot), ts=now, rid=r.rid,
                                   ttft_s=now - r.t_submit)
        else:
            self._h_tpot.observe(now - r.t_last_tok)
        r.t_last_tok = now
        if r.on_token:
            r.on_token(r.rid, tok)
        if r.status != ACTIVE:  # the callback cancelled us mid-emit
            return
        if check_stop(r.out, r.params.stop):
            self._release(slot, STOPPED)
        elif self.slot_remaining[slot] <= 0:
            self._release(slot, DONE)

    def _admit(self) -> int:
        """Admit waiting requests into free capacity (continuous batching:
        admission runs between decode steps, while other slots decode).

        The scheduler picks under the cache's admission predicate — on the
        paged backend that is the free-page budget, not just a free slot —
        and its admission-cost metric (the prefix backend charges only the
        UNMATCHED pages). The FIRST output token is sampled here from the
        prefill's own last-token logits, through the same batched sampler
        the decode step fuses (counter 0 of the request's PRNG stream).
        Returns how many requests it admitted."""
        fits = lambda r: self.cache.can_admit(  # noqa: E731
            len(r.prompt) + r.max_new, prompt=r.prompt)
        cost = lambda r: self.cache.admission_cost(  # noqa: E731
            len(r.prompt) + r.max_new, prompt=r.prompt)
        admitted = 0
        while self.scheduler.pending():
            req = self.scheduler.next_request(fits, cost)
            if req is None:  # defensive: a custom scheduler declined to pick
                return admitted
            slot = self.cache.acquire(len(req.prompt) + req.max_new,
                                      prompt=req.prompt)
            if slot is None:  # no slot / page budget: requeue at the front
                self.scheduler.requeue(req)
                return admitted
            req.status = ACTIVE
            req.slot = slot
            req.t_admit = time.perf_counter()
            if self.trace is not None:
                # the queue-wait span lands HERE (not at submit) because the
                # slot — hence the track — is unknown until admission
                self.trace.span("queued", cat="request", t0=req.t_submit,
                                t1=req.t_admit, track=slot_track(slot),
                                rid=req.rid, priority=req.priority)
            p = as_params(req)
            self._temps[slot] = p.temperature
            self._top_ks[slot] = p.top_k
            self._top_ps[slot] = p.top_p
            self._seeds[slot] = p.seed
            self._counters[slot] = 0
            self.slot_req[slot] = req
            self.slot_remaining[slot] = req.max_new
            self._progress += 1
            admitted += 1
            if self.mixed:
                # continuous mode: no blocking prefill here — park a cursor
                # and let the mixed steps carry the prompt in under the
                # token budget. The first output token is sampled by the
                # dispatch that carries the FINAL chunk (counter 0, from
                # the same last-token logits the serialized path uses).
                self._admit_seq += 1
                self._spec_remaining[slot] = req.max_new
                self._prefilling[slot] = PrefillCursor(
                    req, req.prompt, slot=slot, order=self._admit_seq,
                    off=int(self.cache.pos[slot]))
                continue
            # prefix backend: acquire() mapped the matched prefix and set
            # pos[slot] past it; the prefiller skips those tokens and the
            # post-prefill commit publishes the new full pages to the index
            logits = self.prefiller.prefill(self.cache, slot, req.prompt,
                                            rid=req.rid)
            self.cache.commit(slot, req.prompt)
            if self.spec is not None:
                # draft-side admission (e.g. DraftModel prefills its own
                # cache); runs before the first emit so round one can draft
                self.spec.on_admit(slot, req.prompt, self)
            if self.trace is not None:
                self.trace.span("prefill", cat="request", t0=req.t_admit,
                                t1=time.perf_counter(),
                                track=slot_track(slot), rid=req.rid,
                                tokens=len(req.prompt))
            first = self._sample(
                logits[:, -1],
                jnp.float32([p.temperature]), jnp.int32([p.top_k]),
                jnp.float32([p.top_p]), jnp.uint32([p.seed]),
                jnp.int32([0]))
            self._emit(slot, int(np.asarray(first)[0]))
        return admitted

    def _active(self) -> bool:
        return any(r is not None for r in self.slot_req)

    # --- continuous mode: ahead-of-time dispatch ----------------------------

    def _samp_snapshot(self):
        """Ring-buffered snapshots of the per-slot sampling vectors (the
        pipelined analogue of _step's host_copy calls — see SnapshotRing)."""
        return (self._ring.take("temps", self._temps),
                self._ring.take("top_ks", self._top_ks),
                self._ring.take("top_ps", self._top_ps),
                self._ring.take("seeds", self._seeds),
                self._ring.take("counters", self._counters))

    def _packed_rows(self, chunks, decode_lanes):
        """Host half of a packed mixed step (``model.mixed_step``): rows
        ``[0, budget)`` hold the chunks back to back, row ``budget + s`` is
        slot s's decode row; every other row is a pad at ``PAD_POS``.
        Returns (tokens, use_chain, pos, (lanes, emit, starts))."""
        W, n = self.mixed_budget, self.n_slots
        toks = np.zeros(W + n, np.int32)
        pos = np.full(W + n, PAD_POS, np.int32)
        lanes = np.concatenate([np.zeros(W, np.int32),
                                np.arange(n, dtype=np.int32)])
        emit = np.arange(W, W + n, dtype=np.int32)
        starts = np.full(MIXED_MAX_CHUNKS, W, np.int32)
        use_chain = np.zeros(n, bool)
        row = 0
        for i, (s, chunk) in enumerate(chunks):
            k = len(chunk)
            toks[row:row + k] = chunk
            pos[row:row + k] = self.cache.pos[s] + np.arange(k)
            lanes[row:row + k] = s
            starts[i] = row
            emit[s] = row + k - 1
            row += k
        for s in decode_lanes:
            pos[W + s] = self.cache.pos[s]
            use_chain[s] = True
        return toks, use_chain, pos, (lanes, emit, starts)

    def _padded_rows(self, chunks, decode_lanes):
        """Host half of a padded mixed step (``model.mixed_step_padded``):
        lane s carries slot s's chunk or decode token. Returns (tokens,
        use_chain, pos, n_real)."""
        host_toks = np.zeros((self.n_slots, self.mixed_budget), np.int32)
        n_real = np.zeros(self.n_slots, np.int32)
        use_chain = np.zeros(self.n_slots, bool)
        for s, chunk in chunks:
            host_toks[s, :len(chunk)] = chunk
            n_real[s] = len(chunk)
        for s in decode_lanes:
            n_real[s] = 1
            use_chain[s] = True
        return host_toks, use_chain, self.cache.pos, n_real

    def _dispatch(self) -> bool:
        """Issue ONE step without waiting for its result (continuous mode).

        Decode lanes feed on the device-side ``_chain`` (the previous
        dispatch's sampled output — no host readback); prefill lanes carry
        their scheduler-allotted chunk of prompt tokens. A step with chunks
        is a mixed step: PACKED (``model.mixed_step``: budget + n_slots
        rows, at most ``MIXED_MAX_CHUNKS`` chunks, the cap passed to
        ``Scheduler.allot``) for ``model.PACKED_FAMILIES``, PADDED
        (``model.mixed_step_padded``: n_slots x budget rows, no cap) for
        moe / mla_moe. Bookkeeping that the host mutates afterwards crosses
        the boundary via the snapshot ring. PRNG counters and per-slot
        budgets advance SPECULATIVELY here — the retire side only
        materializes tokens. Returns False when no lane had work to
        dispatch."""
        decode_lanes = [
            s for s, r in enumerate(self.slot_req)
            if r is not None and s not in self._prefilling
            and self._spec_remaining[s] > 0]
        cursors = list(self._prefilling.values())
        cap = MIXED_MAX_CHUNKS if self._packed else None
        allot = (self.scheduler.allot(cursors, self.mixed_budget, max_chunks=cap)
                 if cursors else [])
        if not decode_lanes and not allot:
            return False
        sp = None
        if self.trace is not None:
            # what the step's decode attention reads, counted at dispatch:
            # each lane's cached context and the pages holding it with the
            # token this step writes
            ctx = [int(self.cache.pos[s]) for s in decode_lanes]
            pages = ({"decode_pages": sum([-(-(n + 1) // self.cache.page_size)
                                           for n in ctx])}
                     if self.cache.paged else {})
            sp = self.trace.scope(
                "mixed_step" if allot else "decode_step", cat="engine",
                step=self._decode_steps, decode_ctx_tokens=sum(ctx), **pages)
            # cursors with work the chunk cap held back this step
            deferred = (len(self.scheduler.allot(cursors, self.mixed_budget))
                        - len(allot)) if allot else 0
        # a step's prefill_chunk spans share its start (readers pair them)
        t0 = time.perf_counter() if sp is None else sp.t0_ns * 1e-9
        #: (slot, request, emits): emits=False for non-final prefill chunks
        lanes: list[tuple[int, Request, bool]] = []
        if allot:
            # mixed step: prefill chunks ride the decode batch (static
            # shapes; one trace per backend)
            chunks: list[tuple[int, np.ndarray]] = []
            writes: list[tuple[int, int]] = []
            commits: list[tuple[int, Request]] = []
            chunkinfo: list[tuple[int, int, int, int, int]] = []
            for cur, n in allot:
                s = cur.slot
                off = cur.off
                chunk = cur.take(n)
                chunks.append((s, chunk))
                self.cache.prepare(s, len(chunk))  # paged: draw pages
                writes.append((s, len(chunk)))
                # the final chunk's lane emits the request's FIRST token
                lanes.append((s, cur.req, cur.done))
                chunkinfo.append((s, cur.req.rid, cur.chunks - 1, off,
                                  len(chunk)))
                if cur.done:
                    commits.append((s, cur.req))
            for s in decode_lanes:
                self.cache.prepare(s, 1)
                writes.append((s, 1))
                lanes.append((s, self.slot_req[s], True))
            # rows and snapshots AFTER every prepare (prepare mutates block
            # tables), BEFORE the advances and the speculative counter bump
            toks, use_chain, pos, rows = (
                self._packed_rows if self._packed else self._padded_rows)(
                    chunks, decode_lanes)
            samp = self._samp_snapshot()
            args = (self.params, jnp.asarray(toks), self._chain,
                    self._ring.take("use_chain", use_chain),
                    self._ring.take("mixed_pos", pos),
                    jax.tree.map(jnp.asarray, rows))
            if self.cache.paged:
                nxt, self.cache.caches = self._mixed(
                    *args, self._ring.take("bt", self.cache.block_tables),
                    self.cache.caches, samp)
            else:
                nxt, self.cache.caches = self._mixed(
                    *args, self.cache.caches, samp)
            self._mixed_steps += 1
            if self.trace is not None:
                # each lane's chunk shares this step's host-dispatch window
                # (device work overlaps by design); chunks of one request
                # stay sequential because steps are sequential host-side
                t1 = time.perf_counter()
                for s, rid, idx, off, n in chunkinfo:
                    self.trace.span(f"prefill_chunk[{idx}]", cat="request",
                                    t0=t0, t1=t1, track=slot_track(s),
                                    rid=rid, slot=s, tokens=n, offset=off)
            for s, n in writes:
                self.cache.advance(s, n)
            for s, req in commits:
                # prompt fully in flight: flip the lane to decode and
                # publish its pages to the prefix index (content writes are
                # ordered before any later reader's gather — single stream)
                del self._prefilling[s]
                self.cache.commit(s, req.prompt)
                if self.trace is not None:
                    # prompt fully dispatched: the prefill span closes here
                    # (admission -> final chunk in flight + pages published)
                    self.trace.span("prefill", cat="request", t0=req.t_admit,
                                    t1=time.perf_counter(),
                                    track=slot_track(s), rid=req.rid,
                                    tokens=len(req.prompt))
        else:
            # pure-decode fast path: S=1, fused attention eligible
            for s in decode_lanes:
                self.cache.prepare(s, 1)
                lanes.append((s, self.slot_req[s], True))
            samp = self._samp_snapshot()
            pos = self._ring.take("pos", self.cache.pos)
            if self.cache.paged:
                nxt, self.cache.caches = self._chain_decode(
                    self.params, self._chain, pos,
                    self._ring.take("bt", self.cache.block_tables),
                    self.cache.caches, samp)
            else:
                nxt, self.cache.caches = self._chain_decode(
                    self.params, self._chain, pos, self.cache.caches, samp)
            for s in decode_lanes:
                self.cache.advance(s, 1)
        self._decode_steps += 1
        # speculative state: steps already in flight have consumed these
        # counter values; the retire side must never rewrite them
        for s, req, emits in lanes:
            if emits:
                self._counters[s] += 1
                self._spec_remaining[s] -= 1
        self._chain = nxt
        self._tickets.append((nxt, lanes))
        self._progress += 1
        now = time.perf_counter()
        if sp is not None:
            # the engine-pipeline view of this dispatch: budget split,
            # in-flight depth, and the step's cache-counter deltas (pages
            # drawn / COW copies / evictions attributed to THIS step)
            # a mixed step also records the rows it computed and the
            # cursors the chunk cap deferred
            rows_args = ({"rows": int(toks.size), "deferred_chunks": deferred}
                         if allot else {})
            sp.close(decode_lanes=len(decode_lanes), prefill_lanes=len(allot),
                     prefill_tokens=int(sum(n for _, n in allot)),
                     budget=self.mixed_budget, inflight=len(self._tickets),
                     **rows_args, **self._cache_deltas())
            self.trace.counter("queue_depth", self.scheduler.pending(),
                               ts=now)
            self.trace.counter("inflight", len(self._tickets), ts=now)
        self.monitor.observe(now - t0)
        return True

    def _retire_one(self) -> None:
        """Materialize the OLDEST in-flight step — the hot loop's single
        host sync. Lanes whose request turned over since dispatch (stop
        hit, cancel, slot reuse) are dropped by identity check."""
        nxt, lanes = self._tickets.popleft()
        sp = None
        if self.trace is not None:
            # the sync-wait itself: a long retire right after short
            # dispatches is the pipeline-bubble signature
            sp = self.trace.scope("retire", cat="engine", lanes=len(lanes),
                                  inflight=len(self._tickets))
        nxt = np.asarray(nxt)  # blocks until the step's results are ready
        if sp is not None:
            sp.close()
            sp = self.trace.scope("serve.emit", cat="engine")
        self._progress += 1
        tokens = 0
        for s, req, emits in lanes:
            if not emits:
                continue
            if self.slot_req[s] is not req or req.status != ACTIVE:
                continue  # released after this step was issued: speculative
            self._emit(s, int(nxt[s]))
            tokens += 1
        if sp is not None:
            sp.close(tokens=tokens)

    # --- speculative decoding: the round ------------------------------------

    def _spec_round(self) -> None:
        """One speculation round over every active slot (serialized mode).

        Slots with at least k+1 budget left PARTICIPATE: the draft policy
        proposes k tokens (one scanned jit), then the target scores all
        k+1 positions in ONE ``spec_verify_step`` call and the longest
        draft==target prefix is accepted host-side — the accepted tokens
        plus the bonus token at the first mismatch retire together, so a
        round emits 1..k+1 tokens per lane. Slots nearer their budget than
        k+1 ride the verify as plain 1-token decode lanes (``n_real=1``),
        so a round is never narrower than a serialized step. Rejected rows
        roll back through the cache manager's ``truncate`` verb: positions
        rewind, now-empty pages return to the pool."""
        k = self.spec_k
        W = k + 1
        t0 = time.perf_counter()
        toks = np.zeros((self.n_slots, W), np.int32)
        n_real = np.zeros(self.n_slots, np.int32)
        participants: list[int] = []
        active: list[int] = []
        for s, r in enumerate(self.slot_req):
            if r is None:
                continue
            active.append(s)
            toks[s, 0] = r.out[-1]
            if self.slot_remaining[s] >= W:
                participants.append(s)
                n_real[s] = W
                self.cache.prepare(s, W)  # paged: draw the whole window
            else:
                n_real[s] = 1
                self.cache.prepare(s, 1)
        samp = (host_copy(self._temps), host_copy(self._top_ks),
                host_copy(self._top_ps), host_copy(self._seeds),
                host_copy(self._counters))
        drafts = None
        if participants:
            # non-participants draft at the out-of-range position sentinel:
            # their cache writes scatter-drop, their drafts are junk token
            # ids nobody reads (the verify pad scrub covers their columns)
            src = self.cache.pos if self.spec.shares_cache else self.spec.pos
            dpos = np.full(self.n_slots, 2**30, np.int32)
            for s in participants:
                dpos[s] = src[s]
            tok0 = jnp.asarray(toks[:, 0].copy())
            td0 = time.perf_counter()
            if self.spec.shares_cache:
                if self.cache.paged:
                    drafts, self.cache.caches = self._spec_draft(
                        self.spec.params, tok0, jnp.asarray(dpos),
                        host_copy(self.cache.block_tables),
                        self.cache.caches, samp)
                else:
                    drafts, self.cache.caches = self._spec_draft(
                        self.spec.params, tok0, jnp.asarray(dpos),
                        self.cache.caches, samp)
            else:
                drafts, self.spec.caches = self._spec_draft(
                    self.spec.params, tok0, jnp.asarray(dpos),
                    self.spec.caches, samp)
            drafts = np.asarray(drafts)
            toks[:, 1:] = drafts
            if self.trace is not None:
                self.trace.span("draft", cat="engine", t0=td0,
                                t1=time.perf_counter(), track=ENGINE_TRACK,
                                lanes=len(participants), k=k,
                                policy=self.spec.name)
        tv0 = time.perf_counter()
        if self.cache.paged:
            targets, self.cache.caches = self._spec_verify(
                self.params, jnp.asarray(toks), host_copy(self.cache.pos),
                jnp.asarray(n_real), host_copy(self.cache.block_tables),
                self.cache.caches, samp)
        else:
            targets, self.cache.caches = self._spec_verify(
                self.params, jnp.asarray(toks), host_copy(self.cache.pos),
                jnp.asarray(n_real), self.cache.caches, samp)
        targets = np.asarray(targets)  # the round's one host sync
        self._decode_steps += 1
        self._spec_rounds += 1
        if self.trace is not None:
            self.trace.span("verify", cat="engine", t0=tv0,
                            t1=time.perf_counter(), track=ENGINE_TRACK,
                            lanes=len(active), width=W)
        for s in active:
            r = self.slot_req[s]
            if n_real[s] == W:
                dr, tg = drafts[s], targets[s]
                m = 0
                while m < k and dr[m] == tg[m]:
                    m += 1
                # cache bookkeeping BEFORE emitting: _emit may release the
                # slot (budget / stop / cancel callback) and releasing
                # resets positions wholesale
                self.cache.advance(s, W)
                self.cache.truncate(s, k - m)
                if not self.spec.shares_cache:
                    self.spec.pos[s] = int(self.cache.pos[s])
                self._spec_proposed += k
                self._spec_accepted += m
                self._h_spec_len.observe(m + 1)
                for j in range(m + 1):
                    self._emit(s, int(tg[j]))
                    if self.slot_req[s] is not r or r.status != ACTIVE:
                        break  # released mid-round: drop the unretired tail
            else:
                self.cache.advance(s, 1)
                self._emit(s, int(targets[s, 0]))
            self._progress += 1
        now = time.perf_counter()
        self.monitor.observe(now - t0)
        if self.trace is not None:
            self.trace.span("spec_step", cat="engine", t0=t0, t1=now,
                            track=ENGINE_TRACK, step=self._decode_steps - 1,
                            decode_lanes=len(active),
                            spec_lanes=len(participants),
                            **self._cache_deltas())
            self.trace.counter("queue_depth", self.scheduler.pending(),
                               ts=now)

    def step(self) -> bool:
        """One engine iteration. The caller owns the loop: ``drain()``,
        ``handle.tokens()``, and ``handle.result()`` all lower to repeated
        ``step()`` calls. Returns True while work remains.

        Serialized mode (default): admit (blocking prefill) + one fused
        decode+sample step for every active slot, result read back
        immediately. Continuous mode (``mixed=True``): retire the oldest
        ticket once the in-flight queue is full, admit (non-blocking),
        dispatch one mixed or pure-decode step ahead of time; when nothing
        is dispatchable, retire a ticket instead so the pipeline always
        moves."""
        if self._closed:
            raise RuntimeError("engine is closed")
        t0 = time.perf_counter()
        self._run_t0 = t0
        sp = None
        if self.trace is not None:
            sp = self.trace.scope(
                "serve.step", cat="engine", step=self._decode_steps,
                mode="continuous" if self.mixed else "serialized")
        try:
            if self.mixed and len(self._tickets) >= self.inflight_depth:
                self._retire_one()
            if sp is None or not self.scheduler.pending():
                self._admit()
            else:
                admit = self.trace.scope("serve.admit", cat="engine")
                admit.close(admitted=self._admit())
            if self.mixed:
                if not self._dispatch() and self._tickets:
                    self._retire_one()
            elif self.spec is not None:
                if self._active():
                    self._spec_round()
            else:
                if self._active():
                    # one decode step for every active slot: feed each
                    # slot's last generated token (never prompt[-1] —
                    # prefill already sampled the first token from its own
                    # logits)
                    ts0 = time.perf_counter()
                    lanes = 0
                    toks = np.zeros((self.n_slots, 1), np.int32)
                    for s, r in enumerate(self.slot_req):
                        if r is not None:
                            toks[s, 0] = r.out[-1]
                            self.cache.prepare(s, 1)  # paged: draw a page
                            lanes += 1
                    nxt, _ = self._step(toks)
                    self._decode_steps += 1
                    nxt = np.asarray(nxt)
                    for s in range(self.n_slots):
                        if self.slot_req[s] is None:
                            continue
                        self.cache.advance(s, 1)
                        self._emit(s, int(nxt[s]))
                        self._progress += 1
                    if self.trace is not None:
                        now = time.perf_counter()
                        self.trace.span("step", cat="engine", t0=ts0, t1=now,
                                        track=ENGINE_TRACK,
                                        step=self._decode_steps - 1,
                                        decode_lanes=lanes,
                                        **self._cache_deltas())
                        self.trace.counter("queue_depth",
                                           self.scheduler.pending(), ts=now)
        finally:
            if sp is not None:
                sp.close()
            self._serve_seconds += time.perf_counter() - t0
            self._run_t0 = None
        return bool(self.scheduler.pending() or self._active()
                    or self._tickets)

    def drain(self) -> None:
        """Step until no queued or active work remains.

        A step can be a no-op while work is still pending — queued requests
        the cache cannot admit yet (their capacity frees when a client
        cancels, or never). The old loop busy-spun at 100% CPU in that
        state; now each no-progress step yields the CPU, and a bounded run
        of consecutive no-progress steps (nothing in flight that could
        still unblock us) raises instead of spinning forever."""
        idle = 0
        while True:
            before = self._progress
            more = self.step()
            if not more:
                return
            if self._progress != before:
                idle = 0
                continue
            idle += 1
            time.sleep(0)  # no-op step: yield instead of busy-spinning
            if idle >= 1000:
                raise RuntimeError(
                    f"drain() wedged: {self.scheduler.pending()} queued "
                    f"request(s) cannot be admitted and no in-flight work "
                    f"remains to free capacity (after {idle} no-op steps)")

    def run(self, requests: Sequence[Request], *,
            on_token: Optional[Callable] = None):
        """Batch-mode compat wrapper (the PR-2..4 surface): submit every
        request, drain, return ``{rid: [token, ...]}``. Requests default to
        greedy sampling (via their legacy ``max_new``), so tokens are
        bit-identical to the pre-v1 engines."""
        # validate EVERYTHING before submitting ANYTHING: a can-never-fit
        # request must leave no partial submission (and no active-run
        # marker; metrics() would keep accruing elapsed time otherwise)
        for r in requests:
            need = len(r.prompt) + (r.params.max_new if r.params is not None
                                    else r.max_new)
            self.cache.check_admissible(need)
        for r in requests:
            if on_token is not None:
                r.on_token = on_token
            self._submit_request(r)
        self.drain()
        return {r.rid: r.out for r in requests}

    # --- observability ------------------------------------------------------

    def metrics(self) -> dict:
        """Serving metrics snapshot: SLO latency percentiles (``slo/``
        namespace — TTFT p50/p95/p99 with its queue-wait vs prefill-time
        split, and TPOT inter-token gaps; streaming histograms, O(1) memory
        — serve/stats.py), throughput, lifecycle counters (completed /
        cancelled / stopped_on_sequence / deadline_misses), backlog,
        cache-backend health (page utilization / fragmentation / effective
        bytes-per-token on the paged backend), and the straggler count from
        the StepMonitor — the numbers a deployment scrapes
        (examples/serve_batched.py prints this). Safe to call mid-run (e.g.
        from an on_token callback): the active step's elapsed time is
        included in the throughput denominator."""
        elapsed = self._serve_seconds
        if self._run_t0 is not None:
            elapsed += time.perf_counter() - self._run_t0
        elapsed = max(elapsed, 1e-9)
        return {
            # backend stats mount under cache/ so slot/paged/prefix keys can
            # never collide with (or shadow) the engine's own counters
            **{f"cache/{k}": v for k, v in self.cache.stats().items()},
            "requests_completed": self._completed,
            "cancelled": self._cancelled,
            "stopped_on_sequence": self._stopped_on_seq,
            "deadline_misses": self._deadline_misses,
            "tokens_generated": self._tokens_out,
            "tokens_per_s": self._tokens_out / elapsed,
            "decode_steps": self._decode_steps,
            "mode": "continuous" if self.mixed else "serialized",
            "mixed_steps": self._mixed_steps,
            "mixed_budget": self.mixed_budget if self.mixed else 0,
            "inflight_depth": self.inflight_depth if self.mixed else 0,
            "inflight": len(self._tickets),
            "fused_attn": self.fused_attn,
            # speculative decoding (spec/ namespace; all host counters)
            "spec/enabled": self.spec is not None,
            "spec/policy": self.spec.name if self.spec is not None else "off",
            "spec/k": self.spec_k if self.spec is not None else 0,
            "spec/rounds": self._spec_rounds,
            "spec/proposed": self._spec_proposed,
            "spec/accepted": self._spec_accepted,
            "spec/acceptance_rate": (
                self._spec_accepted / self._spec_proposed
                if self._spec_proposed else 0.0),
            **self._h_spec_len.summary("spec/accepted_len"),
            "prefill_mode": self.prefiller.name,
            "prefill_chunk": self.prefiller.chunk,
            "prefill_jit_calls": self.prefiller.jit_calls,
            **self._h_ttft.summary("slo/ttft"),
            **self._h_ttft_queue.summary("slo/ttft_queue"),
            **self._h_ttft_prefill.summary("slo/ttft_prefill"),
            **self._h_tpot.summary("slo/tpot"),
            "queue_depth": self.scheduler.pending(),
            "active_slots": self.cache.active_slots(),
            "slot_resets": self.cache.resets,
            "step_ema_s": self.monitor.ema or 0.0,
            "stragglers": self.monitor.stragglers,
            "scheduler": self.scheduler.name,
            # per-op kernel rollup: kernels/<op>_calls
            **self._kstats.op_stats(),
            # ring-buffer health when a tracer is attached (dropped > 0
            # means the trace is truncated — resize Tracer(capacity=...))
            **(self.trace.gauges() if self.trace is not None else {}),
        }

"""Request-lifecycle and engine-step tracing for the serving engine.

``metrics()`` answers "how is the engine doing on average"; this module
answers "WHY was that one request slow" — the attribution layer every
tail-latency investigation needs. A :class:`Tracer` is an always-available,
off-by-default event sink (``ServeEngine(trace=Tracer())``): the engine
emits per-request lifecycle spans and per-step pipeline events from the
seams it already owns (``submit()``, ``_admit``, ``_dispatch`` /
``_retire_one``, ``_release``, and the prefillers' chunk loops), and the
tracer stores them in a BOUNDED ring buffer — the same no-unbounded-lists
discipline as :class:`~repro.serve.stats.LatencyHistogram`: a long-lived
engine can trace forever in O(capacity) memory, with the drop count
surfaced as a gauge instead of silently lying.

Event taxonomy (``cat`` / ``name``; the table in ``docs/observability.md``
mirrors this and is what a human should read first):

  * ``cat="request"`` — one span chain per request, on its slot's track:
    ``submit`` (instant) -> ``queued`` (span: submit..admit) ->
    ``prefill`` (span: the prompt entering the cache, with
    ``prefill_chunk[i]`` child spans, one per jitted chunk / mixed-step
    allotment) -> ``first_token`` (instant) -> ``decode`` (span:
    first token..release) -> ``release`` (instant, carries the terminal
    ``status``). Every event carries ``rid``. A request cancelled while
    still queued never owned a slot; its ``request`` span and ``release``
    land on the engine track.
  * ``cat="engine"`` — the step pipeline, on track 0: ``step`` (serialized
    decode step), ``mixed_step`` / ``decode_step`` (continuous-mode
    dispatches: budget split across decode/prefill lanes, in-flight depth,
    page-draw / COW / eviction deltas for the step), ``retire`` (the hot
    loop's single host sync; ``dur`` IS the sync wait), ``serve.step``
    (one ``ServeEngine.step()``, parent of the rest), ``serve.admit``
    (admission, with the count admitted) and ``serve.emit`` (the
    retire-side token delivery: callbacks, stop checks, releases).
  * ``cat="host"`` — ``host.gc``: a collection of generation >= 1, with
    ``gen`` and ``collected``.
  * ``ph="C"`` counters — ``queue_depth`` and ``inflight`` sampled per
    step, rendered as counter tracks by Perfetto.

All timestamps are host-side ``time.perf_counter`` values (the engine's
own lifecycle clock). In continuous mode a dispatch span measures the HOST
cost of issuing the step — device execution overlaps by design; the retire
span's duration is where a stalled device shows up (an ahead-of-time
dispatch bubble is a long ``retire`` right after short dispatches).

Live spans (:meth:`Tracer.scope`) are also written into a running JAX
profiler session as ``jax.profiler.TraceAnnotation`` events with the
same args plus ``pc_ns``, the span's start on the ``perf_counter`` clock in
integer nanoseconds. Every mirrored span therefore ties this clock to the
profiler's session clock (offset = the annotation's start in the profile
minus its ``pc_ns``), and the device trace's idle gaps can be named by
what the engine was doing: ``serve.step`` (one ``ServeEngine.step()``),
``serve.admit``, ``mixed_step`` / ``decode_step`` (dispatch), ``retire``
(the sync wait), ``serve.emit`` (token delivery) and ``host.gc`` (a
garbage collection of generation >= 1, from ``gc.callbacks`` while an
engine holds the tracer). With no profiler session an annotation records
nothing.

Exporters: :meth:`Tracer.export_chrome` writes Chrome/Perfetto
``trace_event`` JSON (one named thread per slot plus the engine-pipeline
thread — open at https://ui.perfetto.dev), :meth:`Tracer.export_jsonl`
writes one event per line for offline analysis, and
:mod:`repro.serve.promexport` renders ``metrics()`` (which mounts
:meth:`Tracer.gauges` under ``trace/``) as a Prometheus text exposition.

Tracing must never perturb serving: emission only READS engine state (no
jit input is touched, so token streams are bit-identical tracing-on vs
tracing-off — gated in ``tests/test_trace.py``). An untraced engine makes
no tracing call. On one TPU v5e chip serving internlm2-1.8b under open-loop
chat traffic (the benchmark's ``internlm2.chat_open`` cell, three seeds
each), a tracer attached with no profiler running moved the median time to
first token by +0.04% (1.644045 s against 1.643437 s) and the p95
inter-token gap by -0.02%, inside the runs' own spread; output tokens per
second were identical.
"""

from __future__ import annotations

import collections
import gc
import json
import time
import weakref
from typing import Iterable, NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: track ids: the engine pipeline is track 0, slot ``s`` is track ``s + 1``
#: (``slot_track``). Chrome export names them via thread_name metadata.
ENGINE_TRACK = 0


def slot_track(slot: int) -> int:
    return slot + 1


def _gc_hook(ref: weakref.ref):
    """A ``gc.callbacks`` entry holding its tracer weakly, so a tracer that
    is dropped while still watching stops recording once it is gone."""
    def hook(phase: str, info: dict) -> None:
        tracer = ref()
        if tracer is not None:
            tracer._on_gc(phase, info)
    return hook


def _unhook(hook) -> None:
    if hook in gc.callbacks:
        gc.callbacks.remove(hook)


class TraceEvent(NamedTuple):
    """One trace event. ``ph`` follows the Chrome ``trace_event`` phases the
    exporter emits: ``"X"`` complete span (``ts``..``ts + dur``), ``"i"``
    instant, ``"C"`` counter. Timestamps/durations are seconds on the
    ``time.perf_counter`` clock; the exporter rebases onto the tracer's
    ``t0`` and converts to microseconds. Immutable; a named tuple rather
    than a frozen dataclass because it is built on the engine's hot path,
    where a frozen dataclass costs several times as much to construct."""

    name: str
    cat: str
    ph: str
    ts: float
    dur: float = 0.0
    track: int = ENGINE_TRACK
    args: Optional[dict] = None

    @property
    def end(self) -> float:
        return self.ts + self.dur


class Scope:
    """A span being recorded, opened by :meth:`Tracer.scope`.

    Its profiler annotation opens with it if a profiler session is running;
    ``close(**args)`` (or leaving a ``with`` block) ends both and records
    the ring event. Args known only at the end join the ring event and the
    annotation there.
    """

    __slots__ = ("tracer", "name", "cat", "track", "args", "t0_ns", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, track: int,
                 args: dict):
        self.tracer, self.name, self.cat, self.track = tracer, name, cat, track
        self.args = args
        self.t0_ns = time.perf_counter_ns()
        self._ann = None
        if TraceAnnotation.is_enabled():  # a profiler session is running
            self._ann = TraceAnnotation(name, **args, pc_ns=self.t0_ns)
            self._ann.__enter__()

    def close(self, **args) -> None:
        if args:
            self.args.update(args)
        if self._ann is not None:
            if args:
                self._ann.set_metadata(**args)
            self._ann.__exit__(None, None, None)
        dur = time.perf_counter_ns() - self.t0_ns
        # Tracer.emit inlined: a scope closes several times per engine step
        tracer = self.tracer
        tracer._ring.append(TraceEvent(self.name, self.cat, "X",
                                       self.t0_ns * 1e-9, dur * 1e-9,
                                       self.track, self.args or None))
        tracer.emitted += 1

    def __enter__(self) -> "Scope":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class Tracer:
    """Bounded ring-buffer event store + the span/instant emission API.

    ``capacity`` bounds memory forever: the ring keeps the NEWEST events
    (a deque with ``maxlen`` drops from the head), ``emitted`` counts every
    event ever offered, and ``dropped`` is the difference — surfaced in
    :meth:`gauges` so a truncated trace is visible, never silent. Span-
    completeness checks (:meth:`check_request_spans`) therefore need a
    capacity sized to the run; the default holds ~64k events (a few
    thousand requests' chains).
    """

    def __init__(self, capacity: int = 1 << 16):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._ring: collections.deque[TraceEvent] = collections.deque(
            maxlen=self.capacity)
        self.emitted = 0
        #: export epoch: event timestamps are reported relative to this
        self.t0 = time.perf_counter()
        #: seconds spent in garbage collections (every generation) while
        #: watched (:meth:`watch_gc`)
        self.gc_pause_s = 0.0
        self._gc_t0_ns: Optional[int] = None
        self._gc_scope: Optional[Scope] = None
        self._gc_cb = _gc_hook(weakref.ref(self))
        weakref.finalize(self, _unhook, self._gc_cb)

    # --- emission -----------------------------------------------------------

    def emit(self, event: TraceEvent) -> None:
        self._ring.append(event)
        self.emitted += 1

    def instant(self, name: str, *, cat: str, track: int = ENGINE_TRACK,
                ts: Optional[float] = None, **args) -> None:
        self.emit(TraceEvent(name, cat, "i",
                             time.perf_counter() if ts is None else ts,
                             track=track, args=args or None))

    def span(self, name: str, *, cat: str, t0: float, t1: float,
             track: int = ENGINE_TRACK, **args) -> None:
        """A complete span ``t0..t1`` (Chrome phase ``X``). Negative
        durations are clamped to zero — clock reads are monotonic but
        callers may stamp boundaries in either order on a zero-work span."""
        self.emit(TraceEvent(name, cat, "X", t0, max(0.0, t1 - t0),
                             track=track, args=args or None))

    def scope(self, name: str, *, cat: str, track: int = ENGINE_TRACK,
              **args) -> Scope:
        """Open a live span now: a :class:`Scope` whose end is stamped when
        it closes, mirrored into a running profiler session."""
        return Scope(self, name, cat, track, args)

    def counter(self, name: str, value: float, *,
                track: int = ENGINE_TRACK,
                ts: Optional[float] = None) -> None:
        self.emit(TraceEvent(name, "engine", "C",
                             time.perf_counter() if ts is None else ts,
                             track=track, args={"value": value}))

    # --- garbage collection -------------------------------------------------

    def watch_gc(self) -> None:
        """Time every collection from now on (``gc.callbacks``), and record a
        ``host.gc`` span for each of generation >= 1. Idempotent."""
        if self._gc_cb not in gc.callbacks:
            gc.callbacks.append(self._gc_cb)

    def unwatch_gc(self) -> None:
        _unhook(self._gc_cb)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0_ns = time.perf_counter_ns()
            if info["generation"] >= 1:
                self._gc_scope = self.scope("host.gc", cat="host",
                                            gen=info["generation"])
            return
        if self._gc_t0_ns is None:  # watching began mid-collection
            return
        self.gc_pause_s += (time.perf_counter_ns() - self._gc_t0_ns) * 1e-9
        self._gc_t0_ns = None
        if self._gc_scope is not None:
            self._gc_scope.close(collected=info["collected"])
            self._gc_scope = None

    # --- access -------------------------------------------------------------

    def events(self) -> list[TraceEvent]:
        """The retained events, oldest first."""
        return list(self._ring)

    @property
    def dropped(self) -> int:
        return self.emitted - len(self._ring)

    def gauges(self) -> dict:
        """The ``trace/``-namespaced fragment ``metrics()`` mounts when a
        tracer is attached (and the scrape endpoint therefore exports)."""
        return {
            "trace/events_emitted": self.emitted,
            "trace/events_retained": len(self._ring),
            "trace/events_dropped": self.dropped,
            "trace/capacity": self.capacity,
            "trace/gc_pause_s": self.gc_pause_s,
        }

    # --- span bookkeeping (offline analysis + tests) ------------------------

    def request_events(self) -> dict[int, list[TraceEvent]]:
        """Retained ``cat="request"`` events grouped by ``rid``, in emission
        order (which is release order for the span events — spans are
        emitted when their end is known)."""
        by_rid: dict[int, list[TraceEvent]] = {}
        for ev in self._ring:
            if ev.cat == "request" and ev.args and "rid" in ev.args:
                by_rid.setdefault(int(ev.args["rid"]), []).append(ev)
        return by_rid

    def check_request_spans(self,
                            rids: Optional[Iterable[int]] = None) -> int:
        """Validate span completeness + nesting for every traced request
        (or just ``rids``). Raises ``ValueError`` naming the first broken
        invariant; returns the number of requests checked.

        Checked per request: a terminal ``release`` exists; a request that
        was ADMITTED (has a ``queued`` span) carries the full chain
        (``queued`` -> ``first_token`` -> ``decode`` -> ``request``) with
        children inside the ``request`` span, in order, non-overlapping
        (``queued.end <= prefill.start``, ``prefill.end <= first_token <=
        decode.start``, chunk spans sequential inside ``prefill``). A
        request released before its first token (cancelled mid-prefill)
        must still carry ``queued`` + ``request`` + ``release``."""
        groups = self.request_events()
        if rids is not None:
            missing = [r for r in rids if r not in groups]
            if missing:
                raise ValueError(f"no trace events for rids {missing}")
            groups = {r: groups[r] for r in rids}
        for rid, evs in sorted(groups.items()):
            def one(name, ph, evs=evs, rid=rid, required=True):
                hits = [e for e in evs if e.name == name and e.ph == ph]
                if len(hits) > 1:
                    raise ValueError(f"rid {rid}: {len(hits)} {name!r} events")
                if not hits:
                    if required:
                        raise ValueError(f"rid {rid}: missing {name!r} event")
                    return None
                return hits[0]

            release = one("release", "i")
            if release.args.get("status") not in ("done", "stopped",
                                                 "cancelled"):
                raise ValueError(
                    f"rid {rid}: release status {release.args.get('status')!r}"
                    f" is not terminal")
            request = one("request", "X")
            queued = one("queued", "X", required=False)
            if queued is None:
                continue  # cancelled while queued: never admitted
            prefill = one("prefill", "X", required=False)
            first = one("first_token", "i", required=False)
            decode = one("decode", "X", required=False)
            if first is None:
                continue  # released before any token (cancelled mid-prefill)
            if decode is None:
                raise ValueError(f"rid {rid}: first_token without decode span")
            eps = 1e-9  # float add/compare slack on the perf_counter scale
            chain = [("queued", queued.ts, queued.end)]
            if prefill is not None:
                chain.append(("prefill", prefill.ts, prefill.end))
            chain += [("first_token", first.ts, first.ts),
                      ("decode", decode.ts, decode.end)]
            for (na, _, ea), (nb, sb, _) in zip(chain, chain[1:]):
                if ea > sb + eps:
                    raise ValueError(
                        f"rid {rid}: {na} (ends {ea:.6f}) overlaps {nb} "
                        f"(starts {sb:.6f})")
            for name, s, e in chain:
                if s < request.ts - eps or e > request.end + eps:
                    raise ValueError(
                        f"rid {rid}: {name} [{s:.6f}, {e:.6f}] escapes the "
                        f"request span [{request.ts:.6f}, {request.end:.6f}]")
            chunks = sorted((e for e in evs
                             if e.name.startswith("prefill_chunk[")),
                            key=lambda e: e.ts)
            for a, b in zip(chunks, chunks[1:]):
                if a.end > b.ts + eps:
                    raise ValueError(
                        f"rid {rid}: {a.name} overlaps {b.name}")
        return len(groups)

    # --- exporters ----------------------------------------------------------

    def to_chrome(self) -> dict:
        """The Chrome/Perfetto ``trace_event`` document (JSON-ready dict):
        one process, one named thread per track (engine pipeline first,
        then the slots), microsecond timestamps rebased to ``t0``."""
        events: list[dict] = [{
            "name": "process_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": "repro.serve"},
        }]
        tracks = sorted({ev.track for ev in self._ring} | {ENGINE_TRACK})
        for t in tracks:
            label = ("engine pipeline" if t == ENGINE_TRACK
                     else f"slot {t - 1}")
            events.append({"name": "thread_name", "ph": "M", "pid": 0,
                           "tid": t, "args": {"name": label}})
            events.append({"name": "thread_sort_index", "ph": "M", "pid": 0,
                           "tid": t, "args": {"sort_index": t}})
        for ev in self._ring:
            rec = {
                "name": ev.name,
                "cat": ev.cat,
                "ph": ev.ph,
                "ts": (ev.ts - self.t0) * 1e6,
                "pid": 0,
                "tid": ev.track,
            }
            if ev.ph == "X":
                rec["dur"] = ev.dur * 1e6
            if ev.ph == "i":
                rec["s"] = "t"  # thread-scoped instant
            if ev.args:
                rec["args"] = ev.args
            events.append(rec)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome(self, path) -> str:
        """Write the Chrome ``trace_event`` JSON to ``path`` (open it at
        https://ui.perfetto.dev or chrome://tracing)."""
        doc = self.to_chrome()
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        return str(path)

    def export_jsonl(self, path) -> str:
        """Write one JSON object per retained event — the structured log
        for offline analysis (pandas/jq; no Chrome schema ceremony)."""
        with open(path, "w") as f:
            for ev in self._ring:
                f.write(json.dumps({
                    "name": ev.name, "cat": ev.cat, "ph": ev.ph,
                    "ts": ev.ts - self.t0, "dur": ev.dur,
                    "track": ev.track, "args": ev.args or {},
                }, sort_keys=True) + "\n")
        return str(path)

"""Observability-layer tests (fast tier): the Tracer's span chains must be
complete, nested, and non-overlapping for every release path (normal
completion, max_new=1, stop sequences, mid-decode and queued cancels); the
Chrome export must be valid ``trace_event`` JSON with per-slot + engine
tracks; tracing on must leave token streams BIT-IDENTICAL to tracing off
on all three cache backends (serialized and continuous); the ring buffer
must stay bounded; live spans must reach a JAX profiler session on the
tracer's own clock (and an untraced engine must write none); the
Prometheus exposition must round-trip every ``metrics()`` key through a
real HTTP scrape; and LatencyHistogram mean/merge hold their contracts.
"""

import gc
import glob
import json
import urllib.request

import jax
import numpy as np
import pytest

from repro import configs
from repro.core.policy import get_policy
from repro.models import model as M
from repro.serve import (
    LatencyHistogram,
    MetricsServer,
    Request,
    SamplingParams,
    ServeEngine,
    Tracer,
)
from repro.serve import promexport
from repro.serve.trace import ENGINE_TRACK, TraceEvent, slot_track

jax.config.update("jax_platform_name", "cpu")

TINY = configs.reduced(configs.get_arch("internlm2-1.8b"))
POLICY = get_policy("w4a8")

BACKENDS = {
    "slot": {},
    "paged": dict(page_size=8, n_pages=40),
    "prefix": dict(page_size=8, n_pages=40),
}


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.key(3), TINY, POLICY, mode="serve")


def _engine(params, *, backend="slot", mixed=False, **kw):
    return ServeEngine(params, TINY, POLICY, n_slots=2, s_max=48, impl="jnp",
                       cache=backend, mixed=mixed,
                       **{**BACKENDS[backend], **kw})


def _requests(lengths=(3, 9, 21, 2), seed=0, max_new=None):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(1, TINY.vocab, size=n).astype(np.int32),
                    max_new=max_new if max_new else 4 + (i % 3))
            for i, n in enumerate(lengths)]


# ------------------------------------------------ satellite: histogram


def test_histogram_summary_reports_mean():
    h = LatencyHistogram()
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    s = h.summary("x")
    assert s["x_mean_s"] == pytest.approx(0.2)
    assert s["x_count"] == 3
    assert LatencyHistogram().summary("x")["x_mean_s"] == 0.0


def test_histogram_merge_is_binwise_exact():
    a, b = LatencyHistogram(), LatencyHistogram()
    both = LatencyHistogram()
    rng = np.random.RandomState(7)
    for i, v in enumerate(rng.lognormal(-3.0, 1.5, size=200)):
        (a if i % 2 else b).observe(float(v))
        both.observe(float(v))
    a.merge(b)
    assert a.n == both.n
    assert a.counts == both.counts
    assert a.total == pytest.approx(both.total)
    assert a.vmin == both.vmin and a.vmax == both.vmax
    for q in (50, 95, 99):
        assert a.percentile(q) == both.percentile(q)


def test_histogram_merge_rejects_shape_mismatch():
    with pytest.raises(ValueError, match="bin layouts"):
        LatencyHistogram().merge(LatencyHistogram(bins=32))


# ------------------------------------------------ tracer unit contracts


def test_ring_buffer_bounded_and_drop_counted():
    tr = Tracer(capacity=8)
    for i in range(100):
        tr.instant(f"e{i}", cat="engine")
    assert len(tr.events()) == 8
    assert tr.emitted == 100
    assert tr.dropped == 92
    assert tr.gauges()["trace/events_dropped"] == 92
    # the ring keeps the NEWEST events
    assert [e.name for e in tr.events()] == [f"e{i}" for i in range(92, 100)]


def test_span_clamps_negative_duration():
    tr = Tracer()
    tr.span("s", cat="engine", t0=2.0, t1=1.0)
    assert tr.events()[0].dur == 0.0


def test_jsonl_export_round_trips(tmp_path):
    tr = Tracer()
    tr.span("work", cat="request", t0=tr.t0, t1=tr.t0 + 0.5, track=1, rid=3)
    tr.instant("mark", cat="engine")
    path = tr.export_jsonl(tmp_path / "t.jsonl")
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 2
    assert lines[0] == {"name": "work", "cat": "request", "ph": "X",
                        "ts": 0.0, "dur": 0.5, "track": 1,
                        "args": {"rid": 3}}


def test_check_request_spans_catches_missing_and_overlap():
    tr = Tracer()
    t = tr.t0
    # missing release
    tr.span("request", cat="request", t0=t, t1=t + 1, track=1, rid=0)
    with pytest.raises(ValueError, match="missing 'release'"):
        tr.check_request_spans()
    tr.instant("release", cat="request", track=1, ts=t + 1, rid=0,
               status="done")
    tr.check_request_spans()
    # overlap: queued ends after first_token
    tr2 = Tracer()
    tr2.span("queued", cat="request", t0=t, t1=t + 2, track=1, rid=1)
    tr2.instant("first_token", cat="request", track=1, ts=t + 1, rid=1)
    tr2.span("decode", cat="request", t0=t + 1, t1=t + 3, track=1, rid=1)
    tr2.span("request", cat="request", t0=t, t1=t + 3, track=1, rid=1)
    tr2.instant("release", cat="request", track=1, ts=t + 3, rid=1,
                status="done")
    with pytest.raises(ValueError, match="overlaps"):
        tr2.check_request_spans()
    # unknown rid
    with pytest.raises(ValueError, match="no trace events"):
        tr.check_request_spans([99])


# ------------------------------------------------ engine span emission


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("mixed", [False, True])
def test_span_chain_complete_and_nested(params, backend, mixed):
    tr = Tracer()
    eng = _engine(params, backend=backend, mixed=mixed, trace=tr,
                  prefill_chunk=4, **(dict(mixed_budget=4) if mixed else {}))
    reqs = _requests()
    eng.run(reqs)
    assert tr.check_request_spans([r.rid for r in reqs]) == len(reqs)
    # request spans end at the stamped release time
    for rid, evs in tr.request_events().items():
        req = next(e for e in evs if e.name == "request" and e.ph == "X")
        rel = next(e for e in evs if e.name == "release")
        assert rel.args["status"] == "done"
        assert req.end == pytest.approx(rel.ts)


def test_span_chain_max_new_1(params):
    """A max_new=1 request's only token IS its first token: the chain must
    still be complete (first_token from the prefill logits, zero-length
    decode window)."""
    tr = Tracer()
    eng = _engine(params, trace=tr)
    eng.run(_requests(lengths=(3, 5), max_new=1))
    assert tr.check_request_spans([0, 1]) == 2


def test_span_chain_stop_sequence(params):
    # find the real first tokens to build a stop sequence that hits
    ref = _engine(params)
    rh = ref.submit(np.arange(1, 8, dtype=np.int32),
                    SamplingParams(max_new=16))
    ref.drain()
    stop = [rh.result()[:2]]
    tr = Tracer()
    eng = _engine(params, trace=tr)
    h2 = eng.submit(np.arange(1, 8, dtype=np.int32),
                    SamplingParams(max_new=16, stop=stop))
    eng.drain()
    assert h2.status == "stopped"
    evs = tr.request_events()[h2.rid]
    rel = next(e for e in evs if e.name == "release")
    assert rel.args["status"] == "stopped"
    tr.check_request_spans([h2.rid])


def test_span_chain_cancelled_exits(params):
    """Cancellation through every path keeps the trace complete: queued
    cancel (never admitted — terminal events on the engine track), and
    mid-decode cancel (full chain, release status cancelled)."""
    tr = Tracer()
    eng = _engine(params, trace=tr)
    # fill both slots, third stays queued
    hs = [eng.submit(np.arange(1, 5, dtype=np.int32),
                     SamplingParams(max_new=8)) for _ in range(3)]
    eng.step()
    assert hs[2].status == "queued"
    hs[2].cancel()
    evs = tr.request_events()[hs[2].rid]
    assert all(e.track == ENGINE_TRACK for e in evs)
    assert next(e for e in evs if e.name == "release").args["status"] == \
        "cancelled"
    # mid-decode cancel
    for tok in hs[0].tokens():
        if len(hs[0].request.out) >= 2:
            hs[0].cancel()
    eng.drain()
    rel = next(e for e in tr.request_events()[hs[0].rid]
               if e.name == "release")
    assert rel.args["status"] == "cancelled"
    tr.check_request_spans([h.rid for h in hs])


def test_first_token_instant_on_slot_track(params):
    tr = Tracer()
    eng = _engine(params, trace=tr)
    reqs = _requests(lengths=(3, 5))
    eng.run(reqs)
    for rid, evs in tr.request_events().items():
        first = next(e for e in evs if e.name == "first_token")
        queued = next(e for e in evs if e.name == "queued")
        assert first.track == queued.track != ENGINE_TRACK


def test_engine_step_events_emitted(params):
    tr = Tracer()
    eng = _engine(params, mixed=True, mixed_budget=4, prefill_chunk=4,
                  backend="paged", trace=tr)
    eng.run(_requests())
    names = {e.name for e in tr.events() if e.cat == "engine"}
    assert "mixed_step" in names and "retire" in names
    # dispatch spans carry the budget split
    ms = next(e for e in tr.events() if e.name == "mixed_step")
    for key in ("step", "decode_lanes", "prefill_lanes", "prefill_tokens",
                "budget", "inflight"):
        assert key in ms.args, key
    # the paged backend's page draws are attributed to steps
    drawn = sum(e.args.get("pages_drawn", 0) for e in tr.events()
                if e.cat == "engine" and e.ph == "X")
    assert drawn == eng.metrics()["cache/pages_drawn"]
    # counter samples for the Perfetto counter track
    assert any(e.ph == "C" and e.name == "inflight" for e in tr.events())


def test_mixed_step_records_rows_and_deferred_chunks(params):
    """A packed mixed step records the rows it computed (budget + n_slots)
    and how many cursors with work the chunk cap (two per step) held back:
    four 3-token prompts admitted together fit one 16-token budget, so the
    first mixed step serves two and defers two, the second serves those."""
    tr = Tracer()
    eng = ServeEngine(params, TINY, POLICY, n_slots=4, s_max=48, impl="jnp",
                      cache="paged", page_size=8, n_pages=40, mixed=True,
                      mixed_budget=16, trace=tr)
    eng.run(_requests(lengths=(3, 3, 3, 3)))
    steps = [e for e in tr.events() if e.name == "mixed_step"]
    assert [e.args["rows"] for e in steps] == [16 + 4] * 2
    assert [e.args["deferred_chunks"] for e in steps] == [2, 0]
    assert [e.args["prefill_tokens"] for e in steps] == [6, 6]
    assert [e.args["decode_lanes"] for e in steps] == [0, 2]
    assert all("rows" not in e.args for e in tr.events()
               if e.name == "decode_step")


def test_prefill_chunk_spans(params):
    """A 3-chunk prompt produces sequential chunk spans inside the prefill
    span — serialized (emitted by ChunkedPrefill) and continuous (emitted
    per mixed-step allotment)."""
    for mixed in (False, True):
        tr = Tracer()
        eng = _engine(params, trace=tr, prefill_chunk=4, mixed=mixed,
                      **(dict(mixed_budget=4) if mixed else {}))
        eng.run(_requests(lengths=(11,)))
        evs = tr.request_events()[0]
        chunks = sorted((e for e in evs
                         if e.name.startswith("prefill_chunk[")),
                        key=lambda e: e.ts)
        assert [e.name for e in chunks] == [f"prefill_chunk[{i}]"
                                            for i in range(3)]
        assert sum(e.args["tokens"] for e in chunks) == 11
        prefill = next(e for e in evs if e.name == "prefill" and e.ph == "X")
        eps = 1e-9
        for c in chunks:
            assert c.ts >= prefill.ts - eps and c.end <= prefill.end + eps


# ------------------------------------------------ bit-exactness on/off


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("mixed", [False, True])
def test_tokens_bit_identical_tracing_on_vs_off(params, backend, mixed):
    kw = dict(backend=backend, mixed=mixed, prefill_chunk=4,
              **(dict(mixed_budget=4) if mixed else {}))
    out_off = _engine(params, **kw).run(_requests())
    out_on = _engine(params, trace=Tracer(), **kw).run(_requests())
    assert out_on == out_off


# ------------------------------------------------ Chrome export


def _chrome_doc(params, backend):
    tr = Tracer()
    eng = _engine(params, backend=backend, mixed=True, mixed_budget=4,
                  prefill_chunk=4, trace=tr)
    eng.run(_requests())
    return tr.to_chrome(), eng


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_chrome_export_schema(params, backend, tmp_path):
    doc, eng = _chrome_doc(params, backend)
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    tids = set()
    for ev in doc["traceEvents"]:
        # trace_event required fields per phase
        assert ev["ph"] in ("X", "i", "C", "M")
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["pid"] == 0 and isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0.0
        tids.add(ev["tid"])
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    # one engine-pipeline track + a track per slot that served a request
    assert ENGINE_TRACK in tids
    assert {slot_track(s) for s in range(eng.n_slots)} <= tids
    # thread names label every used track
    named = {ev["tid"]: ev["args"]["name"] for ev in doc["traceEvents"]
             if ev.get("ph") == "M" and ev["name"] == "thread_name"}
    assert named[ENGINE_TRACK] == "engine pipeline"
    assert named[slot_track(0)] == "slot 0"
    assert tids <= set(named)
    # the file form is valid JSON
    tr2 = Tracer()
    tr2.instant("x", cat="engine")
    path = tr2.export_chrome(tmp_path / "trace.json")
    assert json.load(open(path))["traceEvents"]


def test_chrome_timestamps_are_microseconds_from_t0(params):
    tr = Tracer()
    ev = TraceEvent("s", "engine", "X", tr.t0 + 0.001, 0.002)
    tr.emit(ev)
    rec = [e for e in tr.to_chrome()["traceEvents"] if e["ph"] == "X"][0]
    assert rec["ts"] == pytest.approx(1000.0)
    assert rec["dur"] == pytest.approx(2000.0)


# ------------------------------------------------ kernel call counts


def test_kernel_op_stats_in_metrics_without_tracer(params):
    """Kernel rows count calls, with a tracer or without; no row reads as
    a time."""
    for trace in (None, Tracer()):
        eng = _engine(params, trace=trace)
        eng.run(_requests(lengths=(3,)))
        m = eng.metrics()
        assert m["kernels/mpmm_calls"] > 0
        assert not [k for k in m if k.startswith("kernels/")
                    and not k.endswith("_calls")]
        assert ("trace/events_emitted" in m) is (trace is not None)
        eng.close()


# ------------------------------------------------ live spans in a profile

MIRRORED = ("serve.step", "serve.admit", "mixed_step", "decode_step",
            "retire", "serve.emit")


def _profile(tmp_path, fn):
    """Run ``fn`` under a JAX profiler session; the host events of its
    trace as (name, start_ns, stats) plus every host event name."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    events = [(e.name, e.start_ns, dict(e.stats)) for plane in pd.planes
              if plane.name.startswith("/host") for line in plane.lines
              for e in line.events]
    return events


def test_spans_mirror_into_the_profiler_on_one_clock(params, tmp_path):
    """Every live span opens a profiler annotation with the ring event's
    args, stamped ``pc_ns`` = the ring event's start; the engine's programs
    run under their own names."""
    tr = Tracer()
    eng = _engine(params, backend="paged", mixed=True, mixed_budget=4,
                  prefill_chunk=4, trace=tr)
    events = _profile(tmp_path, lambda: eng.run(_requests()))
    eng.close()
    ring = [e for e in tr.events() if e.name in MIRRORED]
    anns = [e for e in events if e[0] in MIRRORED]
    assert {e[0] for e in anns} == set(MIRRORED)
    assert len(anns) == len(ring)
    offsets = []
    for name, start_ns, stats in anns:
        ev = min(ring, key=lambda e: abs(e.ts * 1e9 - stats["pc_ns"]))
        assert ev.name == name
        assert abs(ev.ts * 1e9 - stats["pc_ns"]) < 1e3  # within 1 us
        if "step" in ev.args:
            assert stats["step"] == ev.args["step"]
        offsets.append(start_ns - stats["pc_ns"])
    # one offset ties the two clocks; it holds across the run
    assert max(offsets) - min(offsets) < 10e6
    names = {e[0] for e in events}
    assert "PjitFunction(serve_mixed_step)" in names
    assert "PjitFunction(serve_decode_step)" in names


def test_untraced_engine_annotates_nothing_and_leaves_gc_alone(params,
                                                               tmp_path):
    gc.collect()  # tracers of earlier tests' engines stop watching
    callbacks = list(gc.callbacks)
    eng = _engine(params, backend="paged", mixed=True, mixed_budget=4,
                  prefill_chunk=4)
    assert gc.callbacks == callbacks
    events = _profile(tmp_path, lambda: eng.run(_requests()))
    assert not [e for e in events if e[0] in MIRRORED + ("host.gc",)]
    assert "PjitFunction(serve_mixed_step)" in {e[0] for e in events}


def test_gc_collection_recorded_as_host_gc_span(params):
    tr = Tracer()
    eng = _engine(params, trace=tr)
    assert tr._gc_cb in gc.callbacks
    eng.submit(np.arange(1, 6, dtype=np.int32), SamplingParams(max_new=3),
               on_token=lambda rid, tok: gc.collect(1))
    eng.drain()
    spans = [e for e in tr.events() if e.name == "host.gc"]
    assert spans and all(e.cat == "host" and e.ph == "X" for e in spans)
    assert {e.args["gen"] for e in spans} >= {1}
    assert all(e.args["collected"] >= 0 for e in spans)
    assert eng.metrics()["trace/gc_pause_s"] >= sum(e.dur for e in spans) > 0
    eng.close()
    assert tr._gc_cb not in gc.callbacks
    n = len(tr.events())
    gc.collect(1)
    assert len(tr.events()) == n


def test_step_and_chunk_counts_follow_request_lengths(params):
    """``decode_ctx_tokens`` / ``decode_pages`` on the dispatch spans and
    each chunk's ``offset`` are what the requests' lengths imply: a
    request of prompt L and max_new N decodes N - 1 steps at cached
    contexts L .. L + N - 2; its chunks tile its prompt."""
    ps = BACKENDS["paged"]["page_size"]
    reqs = _requests(lengths=(3, 9, 21, 2), max_new=5)
    for mixed in (False, True):
        tr = Tracer()
        eng = _engine(params, backend="paged", mixed=mixed, trace=tr,
                      prefill_chunk=4, **(dict(mixed_budget=4) if mixed else {}))
        eng.run(_requests(lengths=(3, 9, 21, 2), max_new=5))
        eng.close()
        for rid, evs in tr.request_events().items():
            chunks = sorted((e for e in evs if e.name.startswith("prefill_chunk[")),
                            key=lambda e: int(e.name[14:-1]))
            offs = [e.args["offset"] for e in chunks]
            lens = [e.args["tokens"] for e in chunks]
            assert offs == [sum(lens[:i]) for i in range(len(lens))]
            assert offs[-1] + lens[-1] == len(reqs[rid].prompt)
        if not mixed:
            continue
        steps = [e for e in tr.events() if e.name in ("mixed_step", "decode_step")]
        # a mixed step's chunks share its start: that pairs them
        starts = {e.ts for e in steps if e.name == "mixed_step"}
        assert {e.ts for e in tr.events()
                if e.name.startswith("prefill_chunk[")} <= starts
        ctx = [len(r.prompt) + k for r in reqs for k in range(r.max_new - 1)]
        assert sum(e.args["decode_ctx_tokens"] for e in steps) == sum(ctx)
        assert sum(e.args["decode_pages"] for e in steps) == \
            sum(-(-(n + 1) // ps) for n in ctx)


# ------------------------------------------------ Prometheus exposition


def test_prom_round_trips_every_metrics_key(params):
    tr = Tracer()
    eng = _engine(params, backend="prefix", mixed=True, mixed_budget=4,
                  prefill_chunk=4, trace=tr)
    eng.run(_requests())
    m = eng.metrics()
    back = promexport.parse(promexport.render(m))
    assert set(back) == set(m)
    for k, v in m.items():
        if isinstance(v, str):
            assert back[k] == v
        else:
            assert back[k] == float(v)


def test_prom_escapes_label_values():
    m = {'weird/key with "quotes"': 'a\\b\n"c"', "n": 1}
    back = promexport.parse(promexport.render(m))
    assert back == {'weird/key with "quotes"': 'a\\b\n"c"', "n": 1.0}


def test_prom_render_shape():
    text = promexport.render({"slo/ttft_p50_s": 0.25, "mode": "continuous"})
    assert '# TYPE repro_slo_ttft_p50_s gauge' in text
    assert 'repro_slo_ttft_p50_s{key="slo/ttft_p50_s"} 0.25' in text
    assert 'repro_info{key="mode",value="continuous"} 1' in text


def test_metrics_server_scrape(params, tmp_path):
    eng = _engine(params)
    eng.run(_requests(lengths=(3,)))
    srv = MetricsServer(eng.metrics, port=0)
    try:
        body = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        back = promexport.parse(body)
        assert back["requests_completed"] == 1.0
        assert back["mode"] == "serialized"
        with pytest.raises(Exception):
            urllib.request.urlopen(srv.url.replace("/metrics", "/nope"),
                                   timeout=10)
    finally:
        srv.close()
    # the no-socket file dump renders the same exposition
    path = promexport.write_exposition(tmp_path / "m.prom", eng.metrics())
    assert promexport.parse(open(path).read())["mode"] == "serialized"

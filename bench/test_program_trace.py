"""CPU tests of what the benchmark reads from the program's own spans and
named programs (``bench/program_trace.py`` and the metrics built on it):

    JAX_PLATFORMS=cpu python -m pytest -q bench

A traced run of the cell at a CPU size, a program that records none of
these spans (an older one), and a small trace recorded on a TPU v5e chip
(``bench/testdata/chat_steps.*``).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from bench import layers, program_trace, run, test_bench, trace_run  # noqa: E402

NEW = ("step.mixed_device_ms.chat", "engine.host_ms_per_step.chat",
       "host.gc_pause_ms.chat", "step.mixed_attn_util.chat", "paged_attn.kv_pages.chat")
V5E = types.SimpleNamespace(device_kind="TPU v5 lite")


@pytest.fixture(scope="module")
def traced_run():
    cell = test_bench.tiny_cell()
    rec = run.serve(cell, test_bench.SEED, 3.0, True)
    yield cell, rec
    shutil.rmtree(rec["prof"].dir, ignore_errors=True)


def test_a_traced_run_reads_host_metrics_and_one_clock(traced_run):
    cell, rec = traced_run
    ctx = layers.Context(cell, rec, {}, V5E)
    got = {m: run.load_metric(m)(ctx) for m in NEW}
    assert got["step.mixed_device_ms.chat"] is None  # no device plane on a CPU
    assert 0 < got["engine.host_ms_per_step.chat"] < 1e3 * (rec["t1"] - rec["t0"])
    assert got["host.gc_pause_ms.chat"] >= 0
    assert 0 < got["step.mixed_attn_util.chat"] <= 100
    eng = cell["mix"]["engine"]
    if ctx.traced_steps(("decode_step",)):
        blocks = -(-eng["s_max"] // eng["page_size"])
        assert 1 <= got["paged_attn.kv_pages.chat"] <= eng["n_slots"] * blocks
    # the older readers still pair each mixed step with its prefill chunks
    mixed = ctx.window_steps(("mixed_step",))
    assert mixed and sum(n for e in mixed for _, n in ctx.prefill_chunks(e)) == \
        sum(e.args["prefill_tokens"] for e in mixed)
    red = program_trace.of(ctx)
    assert program_trace.of(ctx) is red  # reduced once per run
    assert red["clock"]["n"] > 10 and red["clock"]["spread_ns"] < 10e6
    # the session's clock starts inside start_trace: between the harness's
    # t_start (its assumption) and t_ready
    prof = rec["prof"]
    err = -prof.t_start - red["clock"]["median_ns"] * 1e-9
    assert -1e-3 < err < prof.t_ready - prof.t_start + 1e-3


def test_a_tracer_only_run_records_spans_and_no_profile():
    cell = test_bench.tiny_cell()
    rec = run.serve(cell, test_bench.SEED, 2.0, False, fault=trace_run.Traced())
    from bench import program as PG

    assert PG.engine.__name__ == "engine"  # the hook removed itself
    assert rec["prof"].dir is None and rec["spans"]
    top = trace_run.stalls(rec["spans"], rec["t0"], rec["t1"], 3)
    assert len(top) == 3 and top[0]["own_s"] >= top[-1]["own_s"]
    for s in top:
        assert s["dur_s"] >= s["own_s"] > 0 and s["uncovered_s"] >= 0


def test_each_host_step_less_its_retires():
    E = lambda name, ts, dur: types.SimpleNamespace(name=name, ts=ts, dur=dur,  # noqa: E731
                                                     end=ts + dur)
    spans = [E("serve.step", 1.0, 0.5), E("retire", 1.1, 0.2), E("serve.emit", 1.3, 0.1),
             E("serve.step", 2.0, 0.3), E("retire", 2.0, 0.1), E("retire", 2.2, 0.05),
             E("serve.step", 9.0, 0.3), E("retire", 9.1, 0.1)]
    own = program_trace.host_self_s(spans, 0.5, 5.0)
    assert own == pytest.approx([0.3, 0.15])


def test_attention_scores_and_pages_from_the_span_counts():
    """A mixed step of 2 slots x 4 rows over 8-key tables carries a decode
    lane with 5 cached tokens (6 scores) and a 3-token chunk at offset 2
    (3 x 2 + 6 scores): 18 of 64. Decode steps need 7 and 9 pages."""
    cell = test_bench.tiny_cell()
    cell["mix"]["engine"].update(n_slots=2, s_max=7, page_size=4)
    S = lambda name, ts, **args: types.SimpleNamespace(  # noqa: E731
        name=name, ts=ts, dur=0.01, end=ts + 0.01, args=args)
    prof = types.SimpleNamespace(dir=None, t_ready=0.0, t_stop=1.0, t_start=0.0)
    rec = {"spans": [S("mixed_step", 0.5, decode_ctx_tokens=5, decode_lanes=1, budget=4),
                     S("prefill_chunk[1]", 0.5, rid=3, tokens=3, offset=2),
                     S("prefill_chunk[0]", 0.4, rid=3, tokens=2, offset=0),
                     S("decode_step", 0.6, decode_pages=7), S("decode_step", 0.7, decode_pages=9)],
           "t0": 0.45, "t1": 1.0, "prof": prof}
    ctx = layers.Context(cell, rec, {}, V5E)
    assert run.load_metric("step.mixed_attn_util.chat")(ctx) == pytest.approx(100 * 18 / 64)
    assert run.load_metric("paged_attn.kv_pages.chat")(ctx) == 8


def test_a_program_without_these_spans_reads_nothing(tmp_path):
    """The benchmark's newer files over an older program: no error, no
    number."""
    cell = test_bench.tiny_cell()
    prof = types.SimpleNamespace(dir=None, t_ready=0.0, t_stop=1.0, t_start=0.0)
    S = lambda name, ts, **args: types.SimpleNamespace(  # noqa: E731
        name=name, ts=ts, dur=0.1, end=ts + 0.1, args=args)
    rec = {"spans": [S("mixed_step", 0.5, decode_lanes=1, budget=32),
                     S("prefill_chunk[0]", 0.5, rid=0, tokens=8),
                     S("decode_step", 0.7, decode_lanes=1)],
           "t0": 0.0, "t1": 1.0, "prof": prof}
    ctx = layers.Context(cell, rec, {}, V5E)
    assert all(run.load_metric(m)(ctx) is None for m in NEW)


def test_program_trace_on_a_recorded_trace():
    """chat_steps.json holds the same numbers computed by a second method
    (the raw protobuf, in integer ns)."""
    path = os.path.join(HERE, "testdata", "chat_steps.xplane.pb")
    with open(os.path.join(HERE, "testdata", "chat_steps.json")) as f:
        want = json.load(f)
    red = program_trace.reduce_file(path, want["w0"], want["w1"])
    # the two whole mixed steps; the runs cut by the profile's start and
    # stop do not count
    assert list(red["modules"]) == ["jit_serve_mixed_step"]
    assert red["modules"]["jit_serve_mixed_step"] == \
        pytest.approx([1e-3 * t for t in want["mixed_step_ms"]], abs=1e-9)
    clock = red["clock"]
    assert (clock["median_ns"], clock["spread_ns"], clock["n"]) == \
        (want["clock_median_ns"], want["clock_spread_ns"], want["clock_n"])
    assert clock["spread_ns"] <= 100e3  # the clocks agree within 100 us

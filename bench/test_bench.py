"""CPU tests of the benchmark itself, at a size a test run holds:

    JAX_PLATFORMS=cpu python -m pytest -q bench

They skip the harness's look for a chip and drive the rest of a run: the
traffic generator, the players, the check against the plain reference with
faults planted in the timed path, the control, and the trace reduction on a
small trace recorded on a TPU v5e chip (``bench/testdata``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from bench import check, control, loadgen, run, trace_reduce  # noqa: E402
from bench.faults import Fault  # noqa: E402

SEED = 2**31 + 4321  # past 32 signed bits: seeds may be that large


def tiny_cell() -> dict:
    """The cell's configuration and mix at a CPU size: every width cut,
    the precision and the engine's path as stated."""
    cell = run.load_cell("internlm2.chat_open")
    cell["config"].update(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
                          num_key_value_heads=2, head_dim=32, intermediate_size=256,
                          vocab_size=512)
    mix = cell["mix"]
    mix.update(rate_per_s=4.0, prompt=dict(mix["prompt"], median=40, min=8, max=120),
               output=dict(mix["output"], median=8, min=6, max=24))
    mix["engine"] = {"n_slots": 4, "mixed_budget": 32, "s_max": 192, "page_size": 16,
                     "inflight": 2}
    mix["check"] = {"min_tokens": 30, "max_requests": 8}
    # the cell's limits are set for its own size; at this size on the CPU
    # sound runs read widest gaps of 0.006 or less and mean gaps under
    # 0.001, the planted faults widest gaps of 4 or more, the 4-bit KV
    # controls widest gaps of 0.44-0.97 and mean gaps of 0.038-0.152
    cell["limits"] = {"widest_logit_gap": {"limit": 0.5}, "mean_logit_gap": {"limit": 0.02}}
    return cell


def test_every_seed_gets_the_same_schedule_and_its_own_tokens():
    mix = run.load_cell("internlm2.chat_open")["mix"]
    a = loadgen.make_open(mix, 1, 51.0, 1000)
    b = loadgen.make_open(mix, SEED, 51.0, 1000)
    assert [(len(x.prompt), x.max_new, x.due) for x in a] == \
        [(len(x.prompt), x.max_new, x.due) for x in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert max(x.due for x in b) < 51.0
    assert len({len(x.prompt) for x in a}) > 1


def test_without_a_tpu_a_run_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "internlm2.chat_open",
                        "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.mark.parametrize("fault", [None, "token", "stale_cache"])
def test_correct_holds_for_a_sound_run_and_fails_for_each_fault(fault):
    cell = tiny_cell()
    rec = run.serve(cell, SEED, 3.0, False, fault=None if fault is None else Fault(fault))
    v = check.check(cell, rec, SEED)
    assert v["correct"] is (fault is None), v["numbers"]


def test_every_control_comes_out_as_not_correct():
    cell = tiny_cell()
    r = control.readings(cell, SEED, 3.0)
    assert r["program"]["correct"], r
    for name in ("act4", "kv4_reference", "kv4_program"):
        assert r[name]["correct"] is False, (name, r)


def test_trace_reduce_on_a_recorded_trace():
    """decode_steps.json holds the same numbers computed by a second method
    (a sweep over interval endpoints in integer ns)."""
    path = os.path.join(HERE, "testdata", "decode_steps.xplane.pb")
    with open(os.path.join(HERE, "testdata", "decode_steps.json")) as f:
        want = json.load(f)
    red = trace_reduce.reduce_file(path, want["w0"], want["w1"])
    assert abs(red["window_s"] - want["window_s"]) < 1e-12
    assert abs(red["busy_s"] - want["busy_s"]) < 1e-6
    assert {k: v["count"] for k, v in red["kernels"].items()} == want["kernel_counts"]
    for k, t in want["kernel_time_s"].items():
        assert abs(red["kernels"][k]["time_s"] - t) < 1e-6
    mpmm = [c for c in red["calls"] if c[0].startswith("mpmm")]
    assert mpmm and all(c[2][0][1][0] == 32 for c in mpmm)  # M = the 32 decode lanes
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0][0] == "paged_attn_kv8"
    assert len(bd["idle_gaps"]) == 10 and all(g[0] == "engine.step" for g in bd["idle_gaps"][:3])
    assert 0 < red["busy_s"] < red["window_s"]


def test_benchmark_file_names_its_files_and_metrics_consistently():
    import re

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    cells = {w["name"]: w for w in b["workloads"]}
    confs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for w in b["workloads"]:
        assert name.match(w["name"]) and w["config"] in confs and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(HERE, "limits", w["name"] + ".json"))
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    reported = {w: {m for m, v in e2e.items() if w in v.get("workloads", cells)} for w in cells}
    for m in b["per_layer"]:
        assert name.match(m["name"]) and m["moves"] in e2e
        assert os.path.exists(os.path.join(HERE, "metrics", m["name"] + ".py"))
        for w in m["workloads"]:
            assert m["moves"] in reported[w], (m["name"], w)
    for w in cells:
        assert len(reported[w]) >= 2 and any(w in m["workloads"] for m in b["per_layer"])

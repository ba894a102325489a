"""CPU tests of the benchmark itself, at a size a test run holds:

    JAX_PLATFORMS=cpu python -m pytest -q bench

They skip the harness's look for a chip and drive the rest of a run: the
traffic generator, the players, the check against the plain reference with
faults planted in the timed path, the control, and the trace reduction on a
small trace recorded on a TPU v5e chip (``bench/testdata``). Others hold the
model families (``bench/families``) to their contract and the dense GQA
family to numbers recorded before it moved there.
"""

from __future__ import annotations

import ast
import glob
import hashlib
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from bench import check, control, families, loadgen, run, trace_reduce  # noqa: E402
from bench import weights as Wt  # noqa: E402
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


def golden() -> dict:
    with open(os.path.join(HERE, "testdata", "dense_gqa_golden.json")) as f:
        return json.load(f)


def golden_config(g: dict) -> dict:
    c = run.load_cell("internlm2.chat_open")["config"]
    c.update(g["tiny"])
    return c


def leaf_hashes(tree) -> dict:
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        out[jax.tree_util.keystr(path)] = hashlib.sha256(
            f"{a.dtype}{a.shape}".encode() + a.tobytes()).hexdigest()
    return out


def test_the_dense_family_builds_the_recorded_parameter_leaves():
    from bench import program as PG

    g = golden()
    c = golden_config(g)
    params = PG.make_params(c, SEED, families.get(c).program.arch(c), PG.policy(c))
    assert g["seed"] == SEED and leaf_hashes(params) == g["leaves"]


def test_the_dense_family_reference_gives_the_recorded_scores():
    g = golden()
    c = golden_config(g)
    mx, at, am = check.reference_scores(SEED, c, c["precision"], np.array(g["seq"], np.int32),
                                        np.array(g["targets"], np.int32))
    assert [float(x) for x in mx] == g["scores"]["max"]
    assert [float(x) for x in at] == g["scores"]["at_target"]
    assert [int(x) for x in am] == g["scores"]["argmax"]


def test_the_dense_family_counts_give_the_recorded_numbers():
    g = golden()
    c = run.load_cell("internlm2.chat_open")["config"]
    F = families.get(c).counts
    got = {"linear_ops": F.linear_ops(c), "head_ops": F.head_ops(c),
           "kv_bytes_per_token": F.kv_bytes_per_token(c)}
    for n in g["contexts"]:
        got[f"attn_ops@{n}"] = F.attn_ops(c, n)
        got[f"attn_ops_range@1-{n}"] = F.attn_ops_range(c, 1, n)
        got[f"attn_ops_range@{n // 2 + 1}-{n}"] = F.attn_ops_range(c, n // 2 + 1, n)
        got[f"token_flops@{n}"] = F.token_flops(c, n, head=True)
        got[f"token_flops_nohead@{n}"] = F.token_flops(c, n, head=False)
        got[f"paged_attn_bytes@{n}"] = F.paged_attn_bytes(c, n, g["page_size"])
    assert got == g["counts"]


def imports_of(path: str) -> set:
    """Every module an ``import`` statement of ``path`` names, relative
    ones with their leading dots."""
    with open(path) as f:
        tree = ast.parse(f.read())
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mod = "." * node.level + (node.module or "")
            out.add(mod)
            out |= {(mod + "." if node.module else mod) + a.name for a in node.names}
    return out


def test_every_configuration_names_a_family_that_offers_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for conf in b["configs"]:
        with open(os.path.join(ROOT, conf["file"])) as f:
            c = json.load(f)
        fam = families.get(c)
        for part, names in families.CONTRACT.items():
            mod = getattr(fam, part)
            assert [n for n in names if not hasattr(mod, n)] == [], (fam.name, part)
        for part in ("weights", "reference", "counts"):
            mods = imports_of(os.path.join(families.HERE, fam.name, part + ".py"))
            bad = {m for m in mods if m.split(".")[0] == "repro" or m == "bench.program"
                   or m.startswith("bench.program.") or m.lstrip(".") == "program"}
            assert not bad, (fam.name, part, bad)
    for c in ({"name": "x"}, {"name": "x", "harness": "no_such_family"}):
        with pytest.raises(LookupError, match="dense_gqa"):
            families.get(c)


def test_no_module_outside_the_families_names_a_dense_only_key_or_leaf():
    words = ("num_key_value_heads", "intermediate_size", "head_dim",
             '"wq"', "'wq'", '"gate"', "'gate'")
    found = {}
    for path in glob.glob(os.path.join(HERE, "**", "*.py"), recursive=True):
        rel = os.path.relpath(path, HERE)
        if rel.startswith("families" + os.sep) or os.path.basename(rel).startswith("test_"):
            continue
        with open(path) as f:
            text = f.read()
        if any(w in text for w in words):
            found[rel] = [w for w in words if w in text]
    assert found == {}


@pytest.mark.parametrize("bits,lo,hi,std", [(2, -1, 1, math.sqrt(0.5)),
                                            (4, -6, 6, math.sqrt(5.0)),
                                            (8, -126, 126, 36.95)])
def test_weight_codes_lie_in_range_with_the_stated_std(bits, lo, hi, std):
    assert abs(Wt.code_std(bits) - std) < 5e-3
    q = np.asarray(Wt.codes(Wt.seed_key(SEED), (512, 1024), bits))
    assert q.dtype == np.int8 and lo <= q.min() and q.max() <= hi
    assert abs(q.std() - std) < 0.01 * std and abs(q.mean()) < 0.01 * std
    if bits == 2:
        assert set(np.unique(q)) == {-1, 0, 1}

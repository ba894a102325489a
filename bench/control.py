"""Readings that set a cell's correctness limits: the program on many
seeds, and the controls that must come out as not correct.

    python bench/control.py --workload <cell> --seeds 11 12 13 --seconds 51

For each seed, one process serves the cell's traffic at its own load (as a
run does) and judges, by the cell's own comparison (``bench/check.py``):

  * ``program``: the served tokens of the sampled finished requests (the
    lower readings);
  * ``act4``: the reference itself put in the program's place, with the
    linears' activations at 4-bit where the configuration states 8-bit: at
    every position of the same prompts and served tokens, the token the
    4-bit reference puts first is scored by the configuration's reference;
  * ``kv4_reference``: the same with the KV cache at 4-bit (8-bit stated);
  * ``kv4_program``: the program's own 4-bit KV cache path switched on
    (its policy with ``kv_cache_bits`` 4), served again at the same load and
    checked as a run is.

Each seed prints one JSON line with every number, ``correct`` of each, and
more statistics of the gaps beside them. The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

ACT4 = {"act_bits": 4}
KV4 = {"kv_bits": 4}


class ProgramKV4:
    """Switches the program's own 4-bit KV cache on: the policy the harness
    hands the engine gets ``kv_cache_bits`` 4; the check still holds the run
    to the configuration's 8-bit reference."""

    def install(self) -> None:
        from bench import program as PG

        self._orig = orig = PG.policy
        PG.policy = lambda c: dataclasses.replace(orig(c), kv_cache_bits=4)

    def remove(self) -> None:
        from bench import program as PG

        PG.policy = self._orig


def control_gaps(seed: int, c: dict, r, lower: dict):
    """Per position of request ``r``'s served tokens: the reference gap of
    the token a lower-precision reference puts first there."""
    import numpy as np

    from bench import check

    seq = np.concatenate([np.asarray(r.prompt, np.int32), np.asarray(r.out[:-1], np.int32)])
    P = len(r.prompt)
    p = c["precision"]
    _, _, top = check.reference_scores(seed, c, p, seq, np.zeros(len(seq), np.int32), **lower)
    mx, at, _ = check.reference_scores(seed, c, p, seq, top)
    return (mx - at)[P - 1:]


def stats(gs: list) -> dict:
    """More views of the gaps than the check compares, to choose from."""
    import numpy as np

    g = np.concatenate(gs)
    return {"p50": float(np.percentile(g, 50)), "p90": float(np.percentile(g, 90)),
            "p99": float(np.percentile(g, 99)),
            "off_best_share": float((g > 0).mean()),
            **{f"share_over_{t}": float((g > t).mean()) for t in (0.05, 0.1, 0.25, 0.5)}}


def verdict(v: dict, gs: list) -> dict:
    return {"correct": v["correct"],
            **{k: n["value"] for k, n in v["numbers"].items()}, **stats(gs)}


def readings(cell: dict, seed: int, seconds: float) -> dict:
    from bench import check, run

    c, chk = cell["config"], cell["mix"]["check"]
    rec = run.serve(cell, seed, seconds, False)
    picked = check.sample(rec["requests"], seed, chk["min_tokens"], chk["max_requests"])
    short = sum(r.status == "done" and len(r.out) != r.max_new
                for r in rec["requests"].values())
    out = {"seed": seed, "requests": len(picked)}
    gs = [check.gaps(seed, c, c["precision"], r.prompt, r.out) for r in picked]
    out["program"] = verdict(check.judge(cell, gs, short), gs)
    for name, lower in (("act4", ACT4), ("kv4_reference", KV4)):
        gs = [control_gaps(seed, c, r, lower) for r in picked]
        out[name] = verdict(check.judge(cell, gs, 0), gs)
    del rec, picked
    try:
        rec = run.serve(cell, seed, seconds, False, fault=ProgramKV4())
        picked = check.sample(rec["requests"], seed, chk["min_tokens"], chk["max_requests"])
        gs = [check.gaps(seed, c, c["precision"], r.prompt, r.out) for r in picked]
        out["kv4_program"] = verdict(check.check(cell, rec, seed), gs)
    except Exception as e:  # a control that crashes has failed
        out["kv4_program"] = {"correct": False, "error": repr(e)[:500]}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()
    from bench import run

    cell = run.load_cell(args.workload)
    run.enable_cache()
    run.find_chip(cell["chips"])
    for s in args.seeds:
        r = readings(cell, s, args.seconds)
        print(json.dumps({"workload": args.workload, **r}), flush=True)


if __name__ == "__main__":
    main()

"""One run of a cell with the program's tracing on, and what its spans say.

    python bench/trace_run.py --workload <cell> --seed <n> --seconds <s> [--profile]

A ``Tracer`` is attached to the engine, as in a ``bench/run.py --trace 1``
run. Without ``--profile`` no profiler runs: the end-to-end metrics then
read what tracing alone costs, against a ``--trace 0`` run of the same
seed. With ``--profile`` the window's last seconds are profiled as in
``bench/run.py --trace 1``, and the run also prints the cell's per-layer
metrics, the programs' device times and the fitted clock offset against
the one the harness assumes. Either way it prints the engine iterations
with the most host time of their own, with the time each phase took inside
them and the time no engine phase covers. The last line of standard output
is one JSON object. There is no correctness check (``bench/run.py`` makes
it).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import program_trace as PT  # noqa: E402
from bench import run  # noqa: E402  (its import starts the set-up clock)

PHASES = ("serve.admit", "mixed_step", "decode_step", "retire", "serve.emit", "host.gc")


class Traced:
    """Makes ``run.serve`` attach a ``Tracer`` whether or not it profiles,
    through its ``fault`` hook, which installs and removes a change to the
    program it sets up (as ``bench/control.py`` does)."""

    def install(self) -> None:
        from bench import program as PG

        self._orig = orig = PG.engine
        PG.engine = lambda *a, traced, **kw: orig(*a, traced=True, **kw)

    def remove(self) -> None:
        from bench import program as PG

        PG.engine = self._orig


def quantiles(xs: list) -> dict:
    if not xs:
        return {}
    xs = sorted(xs)
    return {"p50": xs[len(xs) // 2], "p90": xs[int(0.9 * (len(xs) - 1))],
            "p99": xs[int(0.99 * (len(xs) - 1))], "max": xs[-1]}


def stalls(spans, t0: float, t1: float, top: int) -> list:
    """The ``top`` engine iterations in [t0, t1) with the most host time of
    their own (``serve.step`` less its ``retire``), each with the time its
    phases took inside it and the time no engine phase covers, in seconds
    (a ``host.gc`` span lies inside whatever it interrupted)."""
    steps = sorted((e for e in spans if e.name == "serve.step" and t0 <= e.ts < t1),
                   key=lambda e: e.ts)
    own = PT.host_self_s(spans, t0, t1)
    out = []
    for i in sorted(range(len(steps)), key=lambda i: -own[i])[:top]:
        s = steps[i]
        parts: dict = {}
        for e in spans:
            if e.name in PHASES and s.ts <= e.ts < s.end:
                parts[e.name] = parts.get(e.name, 0.0) + e.dur
        out.append({"at_s": s.ts - t0, "dur_s": s.dur, "own_s": own[i], **parts,
                    "uncovered_s": s.dur - sum(v for k, v in parts.items() if k != "host.gc")})
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", action="store_true")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.enable_cache()
    devs = run.find_chip(cell["chips"])
    from bench import layers

    rec = run.serve(cell, args.seed, args.seconds, args.profile, fault=Traced())
    t0, t1, spans = rec["t0"], rec["t1"], rec["spans"]
    gcs = [e.dur for e in spans if e.name == "host.gc" and t0 <= e.ts < t1]
    out = {"seed": args.seed, "profile": args.profile,
           "metrics": run.end_to_end(cell, rec),
           "compiles_in_window": rec["compiles_in_window"],
           "host_self_ms": {k: 1e3 * v for k, v in
                            quantiles(PT.host_self_s(spans, t0, t1)).items()},
           "gc": {"count_gen1+": len(gcs), "ms": 1e3 * sum(gcs),
                  "max_ms": 1e3 * max(gcs, default=0.0)},
           "stalls": stalls(spans, t0, t1, 5)}
    prof = rec["prof"]
    if prof.dir:
        per_layer, devinfo, bd = run.per_layer(cell, rec, devs[0])
        red = PT.of(layers.Context(cell, rec, {}, devs[0]))
        out.update(per_layer=per_layer, device=devinfo, breakdown=bd,
                   modules={k: {"n": len(v), "median_ms": 1e3 * statistics.median(v),
                                "min_ms": 1e3 * min(v), "max_ms": 1e3 * max(v)}
                            for k, v in red["modules"].items()},
                   clock=red["clock"])
        if red["clock"]:
            # the harness maps host time h to session time h - t_start;
            # the annotations say h + median offset
            out["harness_window_error_s"] = -prof.t_start - red["clock"]["median_ns"] * 1e-9
        shutil.rmtree(prof.dir, ignore_errors=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

"""Benchmark harness — one module per paper table/figure, plus the
beyond-paper LM-serving table and the dry-run roofline summary.

Prints ``name,us_per_call,derived`` CSV (one row per measurement).
Run: PYTHONPATH=src python -m benchmarks.run [--only fig4,tab1,...]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--json-dir", default="",
                    help="where BENCH_*.json artifacts land "
                         "(default benchmarks/out; also via BENCH_OUT_DIR)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.json_dir:
        os.environ["BENCH_OUT_DIR"] = args.json_dir
    from repro import compile_cache

    compile_cache.enable()

    from benchmarks import (fig4_matmul, fig5_speedup, fig6_energy, load_gen,
                            lm_serving, tab1_qntpack)

    suites = {
        "fig4": fig4_matmul.run,     # MACs/cycle by weight/ifmap precision
        "tab1": tab1_qntpack.run,    # QntPack overhead per output pixel
        "fig5": fig5_speedup.run,    # speedup vs fp32 baseline
        "fig6": fig6_energy.run,     # energy model per inference
        "lm": lm_serving.run,        # beyond-paper: LM decode bytes/token
        "load_slo": load_gen.run,    # arrival traces: TTFT/TPOT tails + goodput
        "trace_overhead": load_gen.run_trace_overhead,  # tracing <= 5%/step
    }
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if only and name not in only:
            continue
        fn()

    # roofline summary (reads dry-run artifacts if present)
    art = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")
    if os.path.isdir(art) and (not only or "roofline" in only):
        from repro.roofline import cell_terms, load_all

        for rec in load_all(art):
            if (rec.get("status") != "ok" or rec.get("mesh") != "16x16"
                    or rec.get("tag")):
                continue
            t = cell_terms(rec)
            print(f"roofline_{rec['arch']}_{rec['shape']},0.0,"
                  f"bound={t['dominant']};frac={t['roofline_fraction']:.2f};"
                  f"useful={t['usefulness']:.2f}")


if __name__ == "__main__":
    main()

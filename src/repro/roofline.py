"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md Sec. Roofline).

Hardware constants: the TPU v5e row of ``repro.devices.PEAKS`` (197
TFLOP/s bf16 per chip — the dry-run programs are bf16; HBM 819 GB/s; ICI
50 GB/s per link).

The compiled SPMD module is the PER-DEVICE program, so cost_analysis flops /
bytes and the HLO-parsed collective bytes are per-device quantities:
  T_comp = flops_dev / peak          (== HLO_FLOPs / (chips * peak))
  T_mem  = bytes_dev / hbm_bw
  T_coll = coll_bytes_dev / link_bw
Dominant term = the bottleneck; roofline fraction = T_comp / max(terms)
(the share of step time the MXU is the limiter — 1.0 = compute-bound).
usefulness = MODEL_FLOPS / (flops_dev * chips) — catches remat/dispatch waste.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Optional

from repro.devices import V5E_KIND, peaks

_V5E = peaks(V5E_KIND)
PEAK_FLOPS = _V5E["bf16_flops"]  # per chip
HBM_BW = _V5E["hbm_bw"]  # B/s per chip
ICI_BW = _V5E["ici_link_bw"]  # B/s per link
V5E_HBM_BYTES = _V5E["hbm_bytes"]


def cell_terms(rec: dict) -> Optional[dict]:
    if rec.get("status") != "ok":
        return None
    cost = rec.get("cost") or rec.get("cost_rolled") or {}
    flops_dev = cost.get("flops", 0.0)
    bytes_dev = cost.get("bytes accessed", 0.0)
    colls = rec.get("collectives") or rec.get("collectives_rolled") or {}
    coll_bytes = sum(v["bytes"] for v in colls.values())
    t_comp = flops_dev / PEAK_FLOPS
    t_mem = bytes_dev / HBM_BW
    t_coll = coll_bytes / ICI_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values()) or 1e-30
    mem = rec.get("memory", {})
    hbm_per_dev = sum(mem.get(k, 0) for k in
                      ("argument_size_in_bytes", "temp_size_in_bytes",
                       "output_size_in_bytes")) - mem.get("alias_size_in_bytes", 0)
    global_flops = flops_dev * rec["chips"]
    return {
        **{f"t_{k}": v for k, v in terms.items()},
        "dominant": dominant,
        "roofline_fraction": t_comp / bound,
        "model_flops": rec.get("model_flops", 0.0),
        "hlo_flops_global": global_flops,
        "usefulness": (rec.get("model_flops", 0.0) / global_flops
                       if global_flops else 0.0),
        "hbm_per_dev_gib": hbm_per_dev / 1024**3,
        "fits_v5e": hbm_per_dev <= V5E_HBM_BYTES,
        "coll_bytes_dev": coll_bytes,
        "coll_breakdown": {k: v["bytes"] for k, v in colls.items()},
    }


def improvement_hint(rec: dict, t: dict) -> str:
    """One sentence on what would move the dominant term down."""
    dom = t["dominant"]
    if dom == "compute":
        if t["usefulness"] < 0.5:
            return ("compute-bound but <50% useful FLOPs: relax remat policy / "
                    "trim MoE dispatch overcompute")
        return "compute-bound near peak: gains need lower-precision MXU (int8/int4) math"
    if dom == "memory":
        if rec.get("kind") == "decode":
            return ("HBM-bound decode: shrink bytes/param further (w4->w2), "
                    "quantize KV cache harder, or widen batch per chip")
        return "HBM-bound: increase arithmetic intensity (fusion, larger microbatch)"
    big = max(t["coll_breakdown"], key=t["coll_breakdown"].get) if t["coll_breakdown"] else "?"
    return (f"collective-bound (mostly {big}): reshard to cut {big} volume, "
            "overlap with compute, or compress payloads (int8 collectives)")


def load_all(art_dir: str) -> list[dict]:
    recs = []
    for p in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(p) as f:
            recs.append(json.load(f))
    return recs


def table(art_dir: str, *, mesh: str = "16x16") -> str:
    """Markdown roofline table over all ok cells of one mesh."""
    rows = [
        "| arch | shape | kind | T_comp (s) | T_mem (s) | T_coll (s) | bound | "
        "roofline frac | useful | HBM/dev (GiB) | next lever |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for rec in load_all(art_dir):
        if rec.get("mesh") != mesh or rec.get("tag"):
            continue
        if rec.get("status") == "skip":
            rows.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | — | "
                        f"skip | — | — | — | {rec.get('reason', '')[:60]} |")
            continue
        t = cell_terms(rec)
        if t is None:
            rows.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | — | "
                        f"ERROR | — | — | — | {rec.get('error', '')[:60]} |")
            continue
        rows.append(
            f"| {rec['arch']} | {rec['shape']} | {rec['kind']} "
            f"| {t['t_compute']:.4g} | {t['t_memory']:.4g} | {t['t_collective']:.4g} "
            f"| {t['dominant']} | {t['roofline_fraction']:.2f} "
            f"| {t['usefulness']:.2f} | {t['hbm_per_dev_gib']:.2f}"
            f"{'' if t['fits_v5e'] else ' (!)'} | {improvement_hint(rec, t)} |")
    return "\n".join(rows)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--art", default=os.path.join(
        os.path.dirname(__file__), "..", "..", "experiments", "dryrun"))
    ap.add_argument("--mesh", default="16x16")
    args = ap.parse_args()
    print(table(os.path.normpath(args.art), mesh=args.mesh))


if __name__ == "__main__":
    main()

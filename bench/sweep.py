"""Find the knee of an open-loop cell once: the highest arrival rate whose
backlog does not grow over the window.

    python bench/sweep.py --workload internlm2.chat_open --rates 0.2 0.3 0.4 --seconds 100

For each rate, one window of the cell's traffic at that rate (the rest of
the mix as it is), in one process. Prints one JSON line per rate: requests
due; every ``--every`` seconds of the window, the backlog (due, no first
token yet) and the requests in the system (due, not finished); the TTFT
median and p90 of the first and second half of the arrivals (a backlog that
grows shows as a second half slower than the first); output tokens per
second. The benchmark's own runs never run this; the rate of a cell is
fixed in its traffic file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def one_rate(cell: dict, rate: float, seed: int, seconds: float, every: float) -> dict:
    import numpy as np

    from bench import run

    cell = dict(cell, mix=dict(cell["mix"], rate_per_s=rate))
    rec = run.serve(cell, seed, seconds, False)
    t0, t1, due = rec["t0"], rec["t1"], rec["due"]
    reqs = sorted(rec["requests"].values(), key=lambda r: due[r.rid])
    ttft = [(r.t_first or rec["t_grace_end"]) - due[r.rid] for r in reqs]
    half = len(ttft) // 2

    def pct(v, p):
        return float(np.percentile(v, p)) if v else None

    ticks = np.arange(every, seconds + 1e-9, every)
    return {"rate": rate, "due": len(reqs),
            "at_s": [float(x) for x in ticks],
            "backlog": [int(sum(due[r.rid] <= t0 + x and not 0 < r.t_first <= t0 + x
                                for r in reqs)) for x in ticks],
            "in_system": [int(sum(due[r.rid] <= t0 + x and not 0 < r.t_done <= t0 + x
                                  for r in reqs)) for x in ticks],
            "ttft_p50_first_half": pct(ttft[:half], 50),
            "ttft_p50_second_half": pct(ttft[half:], 50),
            "ttft_p90_first_half": pct(ttft[:half], 90),
            "ttft_p90_second_half": pct(ttft[half:], 90),
            "out_tokens_per_s": float(sum(t0 <= t < t1 for _, t in rec["emits"]) / (t1 - t0))}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=100.0)
    ap.add_argument("--every", type=float, default=10.0)
    args = ap.parse_args()
    from bench import run

    cell = run.load_cell(args.workload)
    run.GRACE_S = 10.0  # past capacity the backlog never drains; do not wait for it
    run.enable_cache()
    run.find_chip(cell["chips"])
    for r in args.rates:
        print(json.dumps({"workload": args.workload,
                          **one_rate(cell, r, args.seed, args.seconds, args.every)}),
              flush=True)


if __name__ == "__main__":
    main()

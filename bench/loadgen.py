"""Traffic: one general generator for every mix.

A traffic mix is a data file, ``bench/traffic/<mix>.json``, of parameters:
the loop (``open``: arrivals on a schedule, the one kind there is yet), the
rate and burstiness, the distributions of prompt and output lengths, and the
engine settings the mix is served with. This module reads any such file; a
new mix is a new file.

Steadiness: the sizes and arrival gaps are fixed by the mix: stratified
quantiles of its distributions, in an order drawn once from the mix's own
``schedule_seed``. ``--seed`` draws the token ids (and the weights). So
every seed does the same work on the same schedule, and runs differ by the
system, not by the draw: with a few tens of requests in a window, the order
in which bursts meet long prompts would otherwise move a TTFT percentile by
more than most changes a PR makes.

The player and the percentile arithmetic are those of the program's
``benchmarks/load_gen.py`` (``play``, ``_latencies``, ``_percentiles``),
with each request timed from when it was DUE (not from when the player got
round to submitting it), and the player's lateness reported.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np
from scipy import stats


@dataclasses.dataclass
class Arrival:
    rid: int
    due: float  # seconds after the window opens (open loop); 0 = on demand
    prompt: np.ndarray
    max_new: int


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """n stratified quantiles (i + 0.5) / n of a length distribution,
    rounded and clipped to its [min, max]."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        x = stats.lognorm.ppf(u, s=dist["sigma"], scale=dist["median"])
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def _gaps(rate: float, cv: float, n: int) -> np.ndarray:
    """n stratified inter-arrival gaps of a Gamma renewal process with mean
    1 / rate and coefficient of variation ``cv`` (cv 1: Poisson)."""
    k = 1.0 / (cv * cv)
    u = (np.arange(n) + 0.5) / n
    g = stats.gamma.ppf(u, a=k, scale=1.0 / (rate * k))
    return g * (n / rate) / g.sum()  # the n gaps span exactly n / rate


def make_open(mix: dict, seed: int, seconds: float, vocab: int) -> list[Arrival]:
    """Every request due in a window of ``seconds``: rate x seconds of them,
    at Gamma gaps, with the mix's prompt and output lengths."""
    n = max(1, int(mix["rate_per_s"] * seconds))
    order = np.random.default_rng(mix["schedule_seed"])
    gaps = order.permutation(_gaps(mix["rate_per_s"], mix["arrival_cv"], n))
    plen = order.permutation(_quantiles(mix["prompt"], n))
    olen = order.permutation(_quantiles(mix["output"], n))
    rng = np.random.default_rng(seed)
    due = np.cumsum(gaps) - gaps[0] * 0.5  # first arrival inside the window
    return [Arrival(i, float(due[i]), rng.integers(0, vocab, plen[i]).astype(np.int32),
                    int(olen[i])) for i in range(n)]


def percentile(vals, p: float) -> float:
    """Exact host-side percentile (numpy's linear interpolation), as
    ``benchmarks/load_gen.py:_percentiles`` computes it."""
    if len(vals) == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(np.asarray(vals, np.float64), p))


def sleep_until(t: float) -> None:
    dt = t - time.perf_counter()
    if dt > 0:
        time.sleep(dt)

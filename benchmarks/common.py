"""Shared benchmark utilities: timing, the paper's Reference Layer setup,
and the v5e analytical-projection model used where CPU wall time is not the
relevant metric (this container has no TPU — stated in EXPERIMENTS.md)."""

from __future__ import annotations

import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pack as P
from repro.core import quant as Q

from repro.devices import V5E_KIND, peaks

# v5e projection constants (the table roofline.py reads too)
_V5E = peaks(V5E_KIND)
PEAK_FLOPS = _V5E["bf16_flops"]
INT8_OPS = _V5E["int8_ops"]
HBM_BW = _V5E["hbm_bw"]
# energy proxy constants (order-of-magnitude DRAM/MAC energies, documented)
PJ_PER_HBM_BYTE = 15.0
PJ_PER_MAC_INT8 = 0.2
PJ_PER_MAC_BF16 = 0.8


def timeit(fn, *args, iters: int = 20, warmup: int = 3) -> float:
    """Median wall time (us) of a jitted call on this CPU."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def ref_layer_tensors(x_bits: int, w_bits: int, seed: int = 0):
    """The paper's Reference Layer: 32x16x16 ifmap, 64 filters 3x3 (im2col
    288), packed at the requested precisions."""
    rng = np.random.RandomState(seed)
    H = W = 16
    C, Cout = 32, 64
    xq = rng.randint(0, 2**x_bits, size=(H, W, C)).astype(np.uint8)
    wspec = Q.WGT_SPECS[w_bits]
    wq = rng.randint(wspec.qmin, wspec.qmax + 1, size=(Cout, 9 * C)).astype(np.int8)
    return jnp.asarray(P.pack_np(xq, x_bits)), jnp.asarray(P.pack_np(wq, w_bits))


def ref_layer_macs() -> int:
    return 16 * 16 * 64 * 288  # ofmap pixels x im2col size


def ref_layer_bytes(x_bits: int, w_bits: int, y_bits: int) -> dict:
    """HBM traffic of one Reference-Layer inference at given precisions."""
    H = W = 16
    C, Cout = 32, 64
    return {
        "ifmap": H * W * C * x_bits / 8,
        "weights": Cout * 9 * C * w_bits / 8,
        "ofmap": H * W * Cout * y_bits / 8,
    }


def csv_row(name: str, us: float, derived: str):
    print(f"{name},{us:.2f},{derived}")


# ------------------------------------------------- machine-readable emission


def bench_out_dir() -> pathlib.Path:
    env = os.environ.get("BENCH_OUT_DIR")
    if env:
        return pathlib.Path(env)
    return pathlib.Path(__file__).resolve().parent / "out"


def emit_json(bench: str, rows: list[dict]) -> pathlib.Path:
    """Write one benchmark's rows as ``BENCH_<bench>.json`` (the artifact the
    CI bench-smoke job diffs against the ``benchmarks/tuned/`` baselines)."""
    out = bench_out_dir()
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"BENCH_{bench}.json"
    doc = {
        "format": "repro-bench-v1",
        "bench": bench,
        "backend": jax.default_backend(),
        "rows": rows,
    }
    path.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    return path

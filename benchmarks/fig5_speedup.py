"""Paper Fig. 5: speed-up of the mixed-precision library over scalar
baselines on the Reference Layer.

The paper compares GAP-8 (8 cores + SIMD + bext) against STM32H7/L4 (scalar
MCUs). The TPU analogue compares the packed integer path against the naive
dequantize-to-fp32 path (the 'no quantized kernels' baseline a framework
would otherwise run), both as measured CPU time and as v5e roofline
projection (memory-bound layer: bytes ratio governs)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import (
    HBM_BW, INT8_OPS, PEAK_FLOPS, csv_row, ref_layer_bytes, ref_layer_macs,
    ref_layer_tensors, timeit,
)
from repro.core import pack as P
from repro.core import quant as Q
from repro.kernels import ops


def run():
    macs = ref_layer_macs()
    for x_bits, w_bits, y_bits in [(8, 8, 8), (8, 4, 4), (4, 4, 4), (8, 2, 2), (2, 2, 2)]:
        x_p, w_p = ref_layer_tensors(x_bits, w_bits)
        rq = Q.make_requant_params(y_bits=y_bits, eps_phi=2**-12, eps_y=1.0)
        q_fn = jax.jit(lambda xp, wp, xb=x_bits, wb=w_bits, yb=y_bits, r=rq:
                       ops.conv2d(xp, wp, r, x_bits=xb, w_bits=wb, y_bits=yb,
                                  impl="jnp"))

        # fp32 baseline: dequantized dense conv (what runs without the library)
        xf = P.unpack(x_p, x_bits, signed=False).astype(jnp.float32)
        wf = P.unpack(w_p, w_bits, signed=True).astype(jnp.float32)

        def fp_fn(x, w):
            xp4 = jnp.pad(x, ((1, 1), (1, 1), (0, 0)))
            cols = jnp.stack(
                [jnp.stack([xp4[dy : dy + 16, dx : dx + 16, :] for dx in range(3)], 2)
                 for dy in range(3)], 2).reshape(256, -1)
            return cols @ w.T

        fp_jit = jax.jit(fp_fn)
        us_q = timeit(q_fn, x_p, w_p)
        us_fp = timeit(fp_jit, xf, wf)

        b_q = sum(ref_layer_bytes(x_bits, w_bits, y_bits).values())
        b_fp = sum(ref_layer_bytes(32, 32, 32).values())
        # v5e: this layer is tiny -> memory-bound; projected speedup = bytes ratio
        t_q = max(b_q / HBM_BW, 2 * macs / INT8_OPS)  # int8 MXU
        t_fp = max(b_fp / HBM_BW, 2 * macs / (PEAK_FLOPS / 2))  # fp32: half MXU rate
        csv_row(
            f"fig5_speedup_u{x_bits}_i{w_bits}_u{y_bits}", us_q,
            f"cpu_speedup_vs_fp32={us_fp / us_q:.2f};"
            f"v5e_projected_speedup={t_fp / t_q:.2f};bytes={b_q:.0f}_vs_{b_fp:.0f}")


if __name__ == "__main__":
    run()

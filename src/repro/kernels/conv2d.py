"""Fused im2col + MatMul + QntPack conv — the paper's Reference Layer
(3x3, stride 1, pad 1, HWC) as one Pallas kernel.

GAP-8 keeps the whole ifmap in its 64 KiB TCDM; the v5e analogue keeps the
whole *packed* ifmap resident in VMEM (constant index map -> single DMA) and
walks output rows on the grid, dynamic-slicing the 3-row window — im2col never
round-trips to HBM, exactly the paper's execution flow. The ops.py wrapper
pre-pads the ifmap by 1 pixel (quantized zero == real 0.0, alpha = 0), so the
kernel body is branch-free. Reference Layer footprint: 18x18x32 packed ifmap
<= 10 KiB + weights 64x288 <= 18 KiB — VMEM-trivial, like TCDM on GAP-8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import pack as P
from repro.kernels.mpmm import _requant_block, _unpack_x


def _conv2d_kernel(
    x_ref,  # (H+2, W+2, C/rx) packed, whole padded ifmap (VMEM-resident)
    w_ref,  # (Cout, 9*C/rw) packed, (dy, dx, c) order
    rqv_ref,  # SMEM requant vector
    o_ref,  # (bh, W, Cout/ry) packed output row block
    *,
    x_bits: int,
    w_bits: int,
    y_bits: int,
    W: int,
    bh: int,
):
    h = pl.program_id(0)
    rows_p = x_ref[pl.ds(h * bh, bh + 2), :, :]  # (bh+2, W+2, C/rx) packed
    xs, x_off = _unpack_x(rows_p, x_bits)  # (bh+2, W+2, C) s8
    C = xs.shape[-1]
    # im2col for bh output rows: (bh*W, 9C) in (dy, dx, c) order — a taller
    # MXU call per grid step (the autotuned row-block trade-off: fewer grid
    # iterations and dot calls vs a larger live im2col block).
    cols = jnp.concatenate(
        [
            jnp.stack(
                [
                    jnp.stack([xs[r + dy, dx : dx + W, :] for dx in range(3)], axis=1)
                    for dy in range(3)
                ],
                axis=1,
            ).reshape(W, 9 * C)
            for r in range(bh)
        ],
        axis=0,
    )  # (bh*W, 9C)
    w = P.unpack(w_ref[...], w_bits, signed=True)  # (Cout, 9C) s8
    phi = jax.lax.dot_general(
        cols, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )  # (bh*W, Cout)
    if x_off:
        wsum = jnp.sum(w.astype(jnp.int32), axis=1)  # (Cout,)
        phi = phi + x_off * wsum[None, :]
    y = _requant_block(phi, rqv_ref, y_bits)  # (bh*W, Cout) int32
    o_ref[...] = P.pack(y, y_bits).reshape(bh, W, -1)


def conv2d_pallas(
    x_pad_p: jax.Array,  # (H+2, W+2, C/rx) packed pre-padded ifmap
    w_p: jax.Array,  # (Cout, 9*C/rw) packed weights
    rqv: jax.Array,
    *,
    x_bits: int,
    w_bits: int,
    y_bits: int,
    bh: int = 1,
    interpret: bool,
) -> jax.Array:
    Hp, Wp, Cp = x_pad_p.shape
    H, W = Hp - 2, Wp - 2
    Cout = w_p.shape[0]
    ry = P.pack_ratio(y_bits)
    assert Cout % ry == 0
    if H % bh:
        raise ValueError(f"bh={bh} must divide H={H} (ops.conv2d clamps)")
    return pl.pallas_call(
        functools.partial(
            _conv2d_kernel, x_bits=x_bits, w_bits=w_bits, y_bits=y_bits, W=W,
            bh=bh,
        ),
        grid=(H // bh,),
        in_specs=[
            pl.BlockSpec((Hp, Wp, Cp), lambda h: (0, 0, 0)),  # resident ifmap
            pl.BlockSpec(w_p.shape, lambda h: (0, 0)),  # resident weights
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bh, W, Cout // ry), lambda h: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W, Cout // ry), jnp.int8),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=f"conv3x3_u{x_bits}_i{w_bits}_u{y_bits}",
    )(x_pad_p, w_p, rqv)

"""Mixed-precision packed matmul — the paper's MatMul phase as a Pallas TPU
kernel, parameterized over all 27 (x_bits, w_bits, y_bits) permutations.

TPU-native adaptation of PULP-NN's inner loop (DESIGN.md Sec. 2):
  * packed operand blocks are DMA'd HBM -> VMEM (BlockSpec; the paper's
    L2 -> register-file loads of packed words),
  * unpack = vectorized shift/mask on the VPU (the paper's 1-cycle ``bext``),
  * the MAC is an int8 x int8 -> int32 MXU ``dot_general`` (the paper's
    4-way SIMD ``sumdotp``),
  * accumulation in an int32 VMEM scratch tile (the paper's 32-bit
    accumulator registers),
  * on the last K step: fused requantization (threshold ladder for sub-byte,
    shift-and-clamp for 8-bit — paper Sec. 3) + bit-insert packing, then a
    single packed write-back.

Offset-binary fold: 8-bit unsigned ifmaps (0..255) do not fit the MXU's s8
operands, so the kernel computes with x' = x - 128 (s8) and adds the exact
per-block compensation 128 * sum_k w[n, k] back into the accumulator. This is
the standard zero-point fold; phi is bit-identical to the oracle's u8 x s8
accumulation.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary"); M, N dims parallel.
VMEM working set per step (defaults bm=bn=256, bk=512):
  packed x (bm x bk/rx) + packed w (bn x bk/rw) <= 256*512*2 B = 256 KiB
  + unpacked staging 2 * 256*512 B = 256 KiB + int32 accum 256*256*4 = 256 KiB
  ~= 0.8 MiB << 16 MiB VMEM; MXU dims are multiples of (8, 128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import pack as P
from repro.core import quant as Q
from repro.kernels.tuning import IllegalTile


def _unpack_x(block: jax.Array, x_bits: int, x_signed: bool = False) -> jax.Array:
    """Unpack ifmaps to MXU-ready s8. Returns (values_s8, comp_offset).

    Unsigned (paper-faithful CNN path): true value = stored u; only 8-bit
    needs the offset-binary fold (x - 128 fits s8), compensated by adding
    128 * sum_k w[n, k] back into the accumulator (comp_offset = 128).
    Signed (LM hidden-state extension, DESIGN.md Sec. 5): values are stored
    offset-binary (q + 2^(b-1)); true value = u - 2^(b-1), which is exactly
    what the subtraction yields -> no compensation (comp_offset = 0).
    """
    u = P.fields(block, x_bits, signed=False)  # raw unsigned field values
    off = (1 << (x_bits - 1)) if (x_signed or x_bits == 8) else 0
    if off:
        return (u - off).astype(jnp.int8), (0 if x_signed else off)
    return u.astype(jnp.int8), 0  # 0..15 / 0..3 fit s8 directly


def _requant_block(acc: jax.Array, rqv_ref, y_bits: int) -> jax.Array:
    """Fused QntPack on an int32 accumulator block: int32 values in
    [0, 2^y_bits). rqv layout: [0]=shift, [1]=bias, [2:2+2^y-1]=thresholds."""
    if y_bits == 8:
        shift = rqv_ref[0]
        bias = rqv_ref[1]
        y = jnp.right_shift(acc + bias, shift)
        y = jnp.clip(y, 0, 255)
    else:
        n_thresh = (1 << y_bits) - 1
        y = jnp.zeros(acc.shape, jnp.int32)
        for i in range(n_thresh):  # 3 (2-bit) or 15 (4-bit) VPU compares
            y = y + (acc >= rqv_ref[2 + i]).astype(jnp.int32)
    return y


def _mpmm_kernel(
    x_ref,  # (bm, bk/rx) packed int8
    w_ref,  # (bn, bk/rw) packed int8
    rqv_ref,  # SMEM int32 requant vector
    scale_ref,  # SMEM f32 [1] out scale (f32 mode)
    o_ref,  # (bm, bn/ry) packed int8 | (bm, bn) f32
    acc_ref,  # VMEM (bm, bn) int32 scratch
    *,
    x_bits: int,
    w_bits: int,
    y_bits: int,
    x_signed: bool,
    out_kind: str,
    k_steps: int,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xs, x_off = _unpack_x(x_ref[...], x_bits, x_signed)  # (bm, bk) s8
    w = P.unpack(w_ref[...], w_bits, signed=True)  # (bn, bk) s8
    phi = jax.lax.dot_general(
        xs, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )  # (bm, bn) — MXU
    if x_off:
        # exact zero-point compensation for this K block: x_off * sum_k w[n,k]
        wsum = jnp.sum(w.astype(jnp.int32), axis=1)  # (bn,)
        phi = phi + x_off * wsum[None, :]
    acc_ref[...] += phi

    @pl.when(k == k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        if out_kind == "f32":
            o_ref[...] = acc.astype(jnp.float32) * scale_ref[0]
        elif out_kind == "int32":
            o_ref[...] = acc
        else:
            y = _requant_block(acc, rqv_ref, y_bits)  # (bm, bn) int32
            o_ref[...] = P.pack(y, y_bits)  # (bm, bn/ry) int8


def mpmm_pallas(
    x_p: jax.Array,  # (M, K/rx) packed (int8 bit patterns)
    w_p: jax.Array,  # (N, K/rw) packed
    rqv: jax.Array,  # int32 [2 + 2^y_bits - 1] requant vector
    out_scale: jax.Array,  # f32 [1]
    *,
    x_bits: int,
    w_bits: int,
    y_bits: int,
    x_signed: bool = False,
    out_kind: str = "packed",
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool,
) -> jax.Array:
    """Blocked mixed-precision matmul. ``bn``/``bk`` snap to whole layout
    groups (``pack.snap_block``); shapes must then divide the blocks (ops.py
    pads). Returns packed (M, N/ry) int8, or (M, N) f32/int32."""
    rx, rw, ry = P.pack_ratio(x_bits), P.pack_ratio(w_bits), P.pack_ratio(y_bits)
    M, Kx = x_p.shape
    N, Kw = w_p.shape
    K = Kx * rx
    if Kw * rw != K:
        raise ValueError(f"K mismatch: x gives {K}, w gives {Kw * rw}")
    bm = min(bm, M)
    bn = P.snap_block(bn, N, ry if out_kind == "packed" else 1)
    bk = P.snap_block(bk, K, max(rx, rw))
    if M % bm or N % bn or K % bk:
        raise IllegalTile(f"blocks {(bm, bn, bk)} do not divide {(M, N, K)}")
    k_steps = K // bk

    if out_kind == "packed":
        out_shape = jax.ShapeDtypeStruct((M, N // ry), jnp.int8)
        out_spec = pl.BlockSpec((bm, bn // ry), lambda i, j, k: (i, j))
    elif out_kind == "f32":
        out_shape = jax.ShapeDtypeStruct((M, N), jnp.float32)
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    elif out_kind == "int32":
        out_shape = jax.ShapeDtypeStruct((M, N), jnp.int32)
        out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    else:
        raise ValueError(out_kind)

    kernel = functools.partial(
        _mpmm_kernel,
        x_bits=x_bits,
        w_bits=w_bits,
        y_bits=y_bits,
        x_signed=x_signed,
        out_kind=out_kind,
        k_steps=k_steps,
    )
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk // rx), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk // rw), lambda i, j, k: (j, k)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"mpmm_u{x_bits}_i{w_bits}_u{y_bits}",
    )(x_p, w_p, rqv, out_scale)


def requant_vector(rq: Q.RequantParams) -> jax.Array:
    """Fold RequantParams into the kernel's SMEM vector:
    [shift, bias, thresholds...] (int32)."""
    import numpy as np

    return jnp.asarray(
        np.concatenate([[rq.shift, rq.bias], rq.thresholds.astype(np.int64)]).astype(
            np.int32
        )
    )

"""Weight-only dequant matmul — the decode-GEMV workhorse of the integer
serving path when activations stay bf16 (weight-only quantization policies).

y[m, n] = sum_k x[m, k] * (eps_w * unpack(w_p)[n, k])

Packed sub-byte weights stream HBM -> VMEM (the memory-roofline win decode
lives on: bytes/param drop 4x at w4 vs bf16); the VPU unpacks + dequantizes
a (bn, bk) tile; the MXU runs the bf16 dot. Same blocking discipline as
mpmm.py, f32 accumulator scratch across the K grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import pack as P
from repro.kernels.tuning import IllegalTile


def _wdqmm_kernel(x_ref, w_ref, eps_ref, o_ref, acc_ref, *,
                  w_bits: int, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = P.unpack(w_ref[...], w_bits, signed=True)  # (bn, bk) s8
    wf = w.astype(jnp.bfloat16) * eps_ref[0].astype(jnp.bfloat16)
    x = x_ref[...].astype(jnp.bfloat16)  # (bm, bk)
    acc_ref[...] += jax.lax.dot_general(
        x, wf, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def wdqmm_pallas(
    x: jax.Array,  # (M, K) bf16/f32
    w_p: jax.Array,  # (N, K/r) packed signed weights
    eps_w: jax.Array,  # f32 [1]
    *,
    w_bits: int,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool,
) -> jax.Array:
    rw = P.pack_ratio(w_bits)
    M, K = x.shape
    N = w_p.shape[0]
    if w_p.shape[1] * rw != K:
        raise ValueError(f"K mismatch: x gives {K}, w gives {w_p.shape[1] * rw}")
    bm, bn, bk = min(bm, M), P.snap_block(bn, N, 1), P.snap_block(bk, K, rw)
    if M % bm or N % bn or K % bk:
        raise IllegalTile(f"blocks {(bm, bn, bk)} do not divide {(M, N, K)}")
    k_steps = K // bk
    return pl.pallas_call(
        functools.partial(_wdqmm_kernel, w_bits=w_bits, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk // rw), lambda i, j, k: (j, k)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"wdqmm_i{w_bits}",
    )(x, w_p, eps_w.reshape(1))


def wdqmm_ref(x: jax.Array, w_p: jax.Array, eps_w: jax.Array, *, w_bits: int):
    w = P.unpack(w_p, w_bits, signed=True).astype(jnp.float32) * eps_w
    return jax.lax.dot_general(
        x.astype(jnp.float32), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

"""Standalone QntPack Pallas kernel — the paper's third phase, for int32
accumulators produced away from a matmul (residual adds, pooled stats).

Branch-free threshold ladder (sub-byte) / shift-and-clamp (8-bit) + bit-insert
packing, 1-D grid over row blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import pack as P
from repro.kernels.mpmm import _requant_block
from repro.kernels.tuning import IllegalTile


def _qntpack_kernel(phi_ref, rqv_ref, o_ref, *, y_bits: int):
    y = _requant_block(phi_ref[...], rqv_ref, y_bits)
    o_ref[...] = P.pack(y, y_bits)


def qntpack_pallas(
    phi: jax.Array,  # (M, N) int32
    rqv: jax.Array,  # int32 [2 + 2^y_bits - 1]
    *,
    y_bits: int,
    bm: int = 256,
    interpret: bool,
) -> jax.Array:
    M, N = phi.shape
    ry = P.pack_ratio(y_bits)
    bm = min(bm, M)
    if M % bm:
        raise IllegalTile(f"bm={bm} does not divide M={M}")
    return pl.pallas_call(
        functools.partial(_qntpack_kernel, y_bits=y_bits),
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, N), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, N // ry), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N // ry), jnp.int8),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name=f"qntpack_u{y_bits}",
    )(phi, rqv)

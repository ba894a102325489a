"""Decode attention over the QUANTIZED KV cache — the paper's
unpack-adjacent-to-compute discipline fused into the serving hot loop.

One new query token attends over an int8 (or packed int4) cache: cache
blocks stream HBM -> VMEM at quantized width (the decode memory-roofline
lever measured in EXPERIMENTS.md Iteration C2), are dequantized on the VPU
inside the kernel, and reduced with a running softmax — the cache is never
materialized in bf16.

Grid (B, H, ns) over sequence blocks; scratch (m, l, acc) persists across
the ns steps; blocks beyond ``pos`` are masked (and could be grid-predicated
given a scalar-prefetched position — noted for real-TPU tuning).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import pack as P

BIG_NEG = -2.0e9


def _qkv_decode_kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, pos_ref,
                       o_ref, m_ref, l_ref, acc_ref, *,
                       bits: int, bs: int, ns: int, scale: float):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, BIG_NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (1, d)
    kq = kq_ref[0, :, 0]  # (bs, d/r) int8
    vq = vq_ref[0, :, 0]
    if bits < 8:
        kq = P.unpack(kq, bits, signed=True)
        vq = P.unpack(vq, bits, signed=True)
    k = kq.astype(jnp.float32) * ks_ref[0, :, 0][:, None]  # fused dequant
    v = vq.astype(jnp.float32) * vs_ref[0, :, 0][:, None]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (1, bs)
    kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    s = jnp.where(kpos <= pos_ref[0], s, BIG_NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == ns - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def qkv_decode_pallas(
    q: jax.Array,  # (B, Hq, d) one new token per sequence
    k_q: jax.Array,  # (B, S, Hkv, d/r) int8 storage
    k_s: jax.Array,  # (B, S, Hkv) f32 per-(token, head) scales
    v_q: jax.Array,
    v_s: jax.Array,
    pos: jax.Array,  # () int32: attend to cache[0..pos]
    *,
    bits: int = 8,
    bs: int = 512,
    interpret: bool,
) -> jax.Array:
    """Returns (B, Hq, d)."""
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_q.shape
    groups = Hq // Hkv
    r = P.pack_ratio(bits)
    bs_ = min(bs, S)
    assert S % bs_ == 0, (S, bs_)
    ns = S // bs_
    scale = 1.0 / (D**0.5)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(
        _qkv_decode_kernel, bits=bits, bs=bs_, ns=ns, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, ns),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda b, h, j: (b, h, 0)),
            pl.BlockSpec((1, bs_, 1, D // r),
                         lambda b, h, j, g=groups: (b, j, h // g, 0)),
            pl.BlockSpec((1, bs_, 1), lambda b, h, j, g=groups: (b, j, h // g)),
            pl.BlockSpec((1, bs_, 1, D // r),
                         lambda b, h, j, g=groups: (b, j, h // g, 0)),
            pl.BlockSpec((1, bs_, 1), lambda b, h, j, g=groups: (b, j, h // g)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, h, j: (b, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"qkv_decode_i{bits}",
    )(q, k_q, k_s, v_q, v_s, pos_arr)
    return out


def qkv_decode_ref(q, k_q, k_s, v_q, v_s, pos, *, bits: int = 8):
    """Oracle: dequantize the whole cache, run masked softmax attention."""
    from repro.models.attention import kv_dequantize

    B, Hq, D = q.shape
    k = kv_dequantize(k_q, k_s, bits).astype(jnp.float32)  # (B, S, Hkv, D)
    v = kv_dequantize(v_q, v_s, bits).astype(jnp.float32)
    groups = Hq // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k) / (D**0.5)
    mask = jnp.arange(k.shape[1])[None, None, :] <= pos
    s = jnp.where(mask, s, BIG_NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p, v)

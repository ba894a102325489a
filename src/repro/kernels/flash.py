"""Pallas flash attention (causal / windowed) — the attention hot-spot as a
TPU kernel, realizing the block schedule the dry-run accounting models
(EXPERIMENTS.md Iteration A2): fully-future kv blocks are predicated off via
``pl.when`` on the grid, so causal attention does ~half the MXU work.

Grid (B, H, nq, nk), nk innermost; running-softmax state (m, l, acc) lives
in VMEM scratch across the nk steps (same persistence discipline as
mpmm.py's int32 accumulator). Operands stream HBM -> VMEM per (bq, d) /
(bk, d) block; out written once per q block at the last visited kv step.

GQA is handled by the wrapper (kv heads repeated into the head grid dim —
index maps only, no materialized copy).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG_NEG = -2.0e9


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, window, bq: int, bk: int, nk: int,
                  seq_k: int, scale: float):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, BIG_NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * bq
    k_start = j * bk
    visible = True
    if causal:  # any kv position in this block <= some q position?
        visible = k_start <= q_start + bq - 1
    if window is not None:  # any kv position within the window?
        visible = jnp.logical_and(visible, k_start + bk - 1 >= q_start - (window - 1))

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_k  # tail padding
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, BIG_NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_mha_pallas(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: int | None = None,
    bq: int = 512,
    bk: int = 512,
    interpret: bool,
) -> jax.Array:
    """Returns (B, Hq, Sq, D). Sq/Sk padded to block multiples internally."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    groups = Hq // Hkv
    scale = 1.0 / (D**0.5)
    bq_, bk_ = min(bq, Sq), min(bk, Sk)
    pq, pk = -Sq % bq_, -Sk % bk_
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq, nk = (Sq + pq) // bq_, (Sk + pk) // bk_

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, bq=bq_, bk=bk_, nk=nk,
        seq_k=Sk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq_, D), lambda b, h, i, j: (b, h, i, 0)),
            # GQA: kv head index = q head // groups (index map only)
            pl.BlockSpec((1, 1, bk_, D),
                         lambda b, h, i, j, g=groups: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk_, D),
                         lambda b, h, i, j, g=groups: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq + pq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),  # running max
            pltpu.VMEM((bq_,), jnp.float32),  # running denom
            pltpu.VMEM((bq_, D), jnp.float32),  # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=f"flash_{'causal' if causal else 'full'}"
             + (f"_w{window}" if window else ""),
    )(q, k, v)
    return out[:, :, :Sq]

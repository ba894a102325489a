"""Fused paged-attention decode — block-table walk + in-kernel dequant.

The decode hot path used to be gather-then-dense: ``ops.paged_gather`` copied
every slot's quantized pages into contiguous logical rows, ``kv_dequantize``
materialized them at bf16, and plain attention math ran on the dense copy —
one full materialized cache pass per decoded token. These kernels fuse the
three steps in the paper's unpack-adjacent-to-compute discipline (PULP-NN's
no-intermediate-tensor rule, arXiv:2007.07759 Sec. III): each grid step DMAs
ONE page at stored (packed int8 / int4-pair) width straight into VMEM via a
scalar-prefetched block table (the ``paged_gather`` indexing pattern),
dequantizes it on the VPU, and folds it into a running softmax (the
``qkv_decode`` reduction pattern). The dense logical-row copy never exists.

Two variants cover the model zoo's decode shapes:

  * ``paged_attn``      — GQA decode: one query token per slot attends over
    K/V pages ``(n_pages, page_size, Hkv, D/r)`` + per-(token, head) scale
    pages. Grid ``(B, n_blocks)``; each step DMAs one whole page (every kv
    head: a block whose last two dims are the pool's own, as Mosaic requires)
    and scores each kv head's ``groups`` query heads against that head's
    rows, so a page is read once per slot. Sliding-window masking (SWA
    archs) is fused.
  * ``paged_mla_attn``  — MLA absorbed decode: latent-KV pages stay
    COMPRESSED in the pool (kv_lora-wide ``c`` rows + shared rope key ``r``
    rows; SNIPPETS.md Snippet 3's matrix absorption). The kernel scores
    ``q_lat = q_nope . W_uk`` against dequantized ``c`` plus the shared rope
    score, and accumulates the context IN LATENT SPACE — ``W_uv`` is applied
    by the caller after the kernel, so per-head K/V are never materialized.

Numerics: dequantization rounds through bf16 (``(int * scale) -> bf16 ->
f32``) to reproduce ``models.attention.kv_dequantize`` exactly — the fused
path reads the same values the gather-then-dense path reads, and the only
difference from it is the page-blocked softmax reduction order (~1e-6 rel).
Fully-masked pages (a sliding window that has slid past a page, or recycled
pool pages past a slot's write frontier) contribute EXACTLY zero: probability
terms are forced to 0.0 under the mask rather than relying on exp(-inf).

Layout contract: the dense slot cache is the same kernel with an identity
block table — ops.paged_attn reshapes ``(B, S_max, ...)`` stripes into a
``(B * S_max/bs, bs, ...)`` pool view (free, contiguous) so slot, paged, and
prefix backends all share this code path; with equal block/page sizes their
outputs are bit-identical (gather and dequantize commute elementwise).

Both variants ship the usual pair: the Pallas kernel (interpreted off-TPU)
and a jnp twin mirroring the page-blocked reduction step for step (same dots,
same masks, same flush — agreement is ulp-level, bounded only by XLA's
reassociation freedom; the integer-matmul twins elsewhere in this package are
bit-exact because their accumulation is integral, which float softmax is
not). Both impls register in kernels/dispatch.py under kv-bits cells
{None, 8, 4}; the dense-view block size ``bs`` resolves through
kernels/tuning.py (op ``paged_attn``).
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


def _dequant_block(qv: jax.Array, scale: Optional[jax.Array],
                   bits: Optional[int]) -> jax.Array:
    """(ps, D/r) stored block -> (ps, D) f32, matching kv_dequantize bit-for-
    bit: int8/int4 rows scale (``scale`` is the (ps, 1) column) then round
    through bf16; bf16 rows just widen."""
    if bits is None:
        return qv.astype(jnp.float32)
    if bits < 8:
        qv = P.unpack(qv, bits, signed=True)
    x = qv.astype(jnp.float32) * scale
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _dot2(a: jax.Array, b: jax.Array, *, trans: bool) -> jax.Array:
    dims = (((1,), (1,) if trans else (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _bdot(a: jax.Array, b: jax.Array, *, trans: bool = False) -> jax.Array:
    """The kernel's exact 2-D dot, vmapped over leading batch axes — the jnp
    twins use this instead of einsum so they stay bit-identical with the
    kernel's per-grid-step ``dot_general`` calls."""
    fn = functools.partial(_dot2, trans=trans)
    for _ in range(a.ndim - 2):
        fn = jax.vmap(fn)
    return fn(a, b)


def _page_spec(leaf: Optional[jax.Array]) -> pl.BlockSpec:
    """One whole pool page per grid step, routed through the prefetched block
    table. A page block keeps the pool's trailing dims whole, which is what
    Mosaic requires of the last two block dims; a dummy (1,) spec stands in
    for the scales of a bf16 cache."""
    if leaf is None:
        return pl.BlockSpec((1,), lambda b, j, bt, pos: (0,))
    tail = leaf.shape[1:]
    return pl.BlockSpec((1, *tail),
                        lambda b, j, bt, pos: (bt[b, j],) + (0,) * len(tail))


def _softmax_update(m_ref, l_ref, acc_ref, rows, s, valid, v):
    """One page's running-softmax update of query rows ``rows``: scores
    ``s`` (G, ps), mask ``valid`` (1, ps), values ``v`` (ps, Dv)."""
    s = jnp.where(valid, s, BIG_NEG)
    m_prev = m_ref[rows]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # p is forced to exactly 0.0 under the mask: a fully-masked page (window
    # slid past it, or a recycled page beyond the write frontier) leaves
    # m == BIG_NEG, where exp(s - m) would be exp(0) = 1, not 0
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[rows] = l_ref[rows] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[rows] = m_new


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, BIG_NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _flush(o_ref, l_ref, acc_ref):
    o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


# ------------------------------------------------------------- GQA decode


def _paged_attn_kernel(bt_ref, pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                       o_ref, m_ref, l_ref, acc_ref, *,
                       bits: Optional[int], ps: int, nb: int, groups: int,
                       scale: float, window: Optional[int]):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    valid = kpos <= pos_ref[b]
    if window is not None:
        valid &= (pos_ref[b] - kpos) < window
    ks = vs = None
    if bits is not None:
        ks, vs = ks_ref[0], vs_ref[0]  # (ps, Hkv)
    for h in range(kq_ref.shape[2]):
        rows = pl.ds(h * groups, groups)  # q head kv*G + g (jnp.repeat order)
        col = slice(h, h + 1)
        k = _dequant_block(kq_ref[0, :, h, :],
                           None if ks is None else ks[:, col], bits)
        v = _dequant_block(vq_ref[0, :, h, :],
                           None if vs is None else vs[:, col], bits)
        q = q_ref[0, rows, :].astype(jnp.float32)  # (G, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        _softmax_update(m_ref, l_ref, acc_ref, rows, s, valid, v)

    @pl.when(j == nb - 1)
    def _done():
        _flush(o_ref, l_ref, acc_ref)


def paged_attn_pallas(
    q: jax.Array,  # (B, Hq, D) one new query token per slot
    k: jax.Array,  # (P, ps, Hkv, D/r) page pool: int8 storage, bf16 if bits None
    k_s: Optional[jax.Array],  # (P, ps, Hkv) f32 scales (None when bits None)
    v: jax.Array,
    v_s: Optional[jax.Array],
    pos: jax.Array,  # (B,) int32: slot b attends cache[0..pos[b]]
    block_table: jax.Array,  # (B, NB) int32 physical page ids
    *,
    bits: Optional[int],
    window: Optional[int] = None,
    interpret: bool,
) -> jax.Array:
    """Returns (B, Hq, D) f32. Scalar-prefetched block table + per-slot pos;
    one grid step = one (slot, page) running-softmax update over every kv
    head — each head's ``groups`` query rows score the page's rows of that
    head, so a page is read once per slot."""
    B, Hq, D = q.shape
    Hkv = k.shape[2]
    ps = k.shape[1]
    NB = block_table.shape[1]
    pos = jnp.asarray(pos, jnp.int32).reshape(B)
    quant = bits is not None
    kernel = functools.partial(
        _paged_attn_kernel, bits=bits, ps=ps, nb=NB, groups=Hq // Hkv,
        scale=1.0 / (D**0.5), window=window)
    zero = jnp.zeros((1,), jnp.float32)  # dummy scale operand when bf16
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NB),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, j, bt, pos: (b, 0, 0)),
            _page_spec(k), _page_spec(k_s if quant else None),
            _page_spec(v), _page_spec(v_s if quant else None),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, j, bt, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=f"paged_attn_{'bf16' if bits is None else f'kv{bits}'}",
    )(block_table, pos, q, k, k_s if quant else zero, v,
      v_s if quant else zero)


def paged_attn_ref(q, k, k_s, v, v_s, pos, block_table, *,
                   bits: Optional[int], window: Optional[int] = None):
    """jnp twin: the same page-blocked running softmax, vectorized over
    (slot, kv head) — bit-exact with the interpret-mode kernel."""
    B, Hq, D = q.shape
    _, ps, Hkv, _ = k.shape
    G = Hq // Hkv
    NB = block_table.shape[1]
    scale = 1.0 / (D**0.5)
    q4 = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    pos = jnp.asarray(pos, jnp.int32).reshape(B)

    def dequant(qv, sc):
        if bits is None:
            return qv.astype(jnp.float32)
        if bits < 8:
            qv = P.unpack(qv, bits, signed=True)
        x = qv.astype(jnp.float32) * sc[..., None]
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def step(carry, j):
        m, l, acc = carry
        pages = block_table[:, j]  # (B,)
        kf = dequant(k[pages], None if bits is None else k_s[pages])
        vf = dequant(v[pages], None if bits is None else v_s[pages])
        # the kernel's exact 2-D dots, vmapped over (slot, kv head) — einsum
        # reassociates the contraction and drifts a ulp from the kernel
        s = _bdot(q4, kf.transpose(0, 2, 1, 3), trans=True) * scale
        kpos = j * ps + jnp.arange(ps, dtype=jnp.int32)
        valid = kpos[None] <= pos[:, None]  # (B, ps)
        if window is not None:
            valid &= (pos[:, None] - kpos[None]) < window
        vmask = valid[:, None, None, :]
        s = jnp.where(vmask, s, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(vmask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + _bdot(p, vf.transpose(0, 2, 1, 3))
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G), BIG_NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  jnp.arange(NB, dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Hq, D)


# ---------------------------------------------------- MLA absorbed decode


def _paged_mla_kernel(bt_ref, pos_ref, ql_ref, qr_ref, cq_ref, cs_ref, r_ref,
                      o_ref, m_ref, l_ref, acc_ref, *,
                      bits: Optional[int], ps: int, nb: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    ql = ql_ref[0].astype(jnp.float32)  # (H, C)
    qr = qr_ref[0].astype(jnp.float32)  # (H, dr)
    c = _dequant_block(cq_ref[0, :, 0],
                       None if bits is None else cs_ref[0], bits)
    r = r_ref[0, :, 0].astype(jnp.float32)  # (ps, dr) shared rope key

    s_lat = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    s_rope = jax.lax.dot_general(qr, r, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    s = (s_lat + s_rope) * scale  # (H, ps)
    kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    # context accumulates in LATENT space: value rows ARE the c latents
    _softmax_update(m_ref, l_ref, acc_ref, slice(None), s, kpos <= pos_ref[b], c)

    @pl.when(j == nb - 1)
    def _done():
        _flush(o_ref, l_ref, acc_ref)


def paged_mla_attn_pallas(
    q_lat: jax.Array,  # (B, H, C) absorbed query (q_nope . W_uk), f32
    q_rope: jax.Array,  # (B, H, dr) rotary query
    c: jax.Array,  # (P, ps, 1, C/r) latent pages, compressed in the pool
    c_s: Optional[jax.Array],  # (P, ps, 1) f32 (None when bits None)
    r: jax.Array,  # (P, ps, 1, dr) bf16 shared rope-key pages
    pos: jax.Array,  # (B,) int32
    block_table: jax.Array,  # (B, NB) int32
    *,
    bits: Optional[int],
    scale: float,
    interpret: bool,
) -> jax.Array:
    """Returns the latent context (B, H, C) f32 — the caller applies W_uv.
    One grid step = one (slot, page); every head shares the page read."""
    B, H, C = q_lat.shape
    ps = c.shape[1]
    NB = block_table.shape[1]
    pos = jnp.asarray(pos, jnp.int32).reshape(B)

    kernel = functools.partial(_paged_mla_kernel, bits=bits, ps=ps, nb=NB,
                               scale=scale)
    quant = bits is not None
    zero = jnp.zeros((1,), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NB),
        in_specs=[
            pl.BlockSpec((1, H, C), lambda b, j, bt, pos: (b, 0, 0)),
            pl.BlockSpec((1, H, q_rope.shape[-1]),
                         lambda b, j, bt, pos: (b, 0, 0)),
            _page_spec(c), _page_spec(c_s if quant else None), _page_spec(r),
        ],
        out_specs=pl.BlockSpec((1, H, C), lambda b, j, bt, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, C), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=f"paged_mla_attn_{'bf16' if bits is None else f'kv{bits}'}",
    )(block_table, pos, q_lat, q_rope, c, c_s if quant else zero, r)


def paged_mla_attn_ref(q_lat, q_rope, c, c_s, r, pos, block_table, *,
                       bits: Optional[int], scale: float):
    """jnp twin of the absorbed-MLA kernel: same page-blocked reduction."""
    B, H, C = q_lat.shape
    ps = c.shape[1]
    NB = block_table.shape[1]
    ql = q_lat.astype(jnp.float32)
    qr = q_rope.astype(jnp.float32)
    pos = jnp.asarray(pos, jnp.int32).reshape(B)

    def dequant(qv, sc):
        if bits is None:
            return qv.astype(jnp.float32)
        if bits < 8:
            qv = P.unpack(qv, bits, signed=True)
        x = qv.astype(jnp.float32) * sc[..., None]
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def step(carry, j):
        m, l, acc = carry
        pages = block_table[:, j]
        cf = dequant(c[pages][:, :, 0], None if bits is None else c_s[pages][:, :, 0])
        rf = r[pages][:, :, 0].astype(jnp.float32)  # (B, ps, dr)
        s = (_bdot(ql, cf, trans=True) + _bdot(qr, rf, trans=True)) * scale
        kpos = j * ps + jnp.arange(ps, dtype=jnp.int32)
        valid = kpos[None] <= pos[:, None]  # (B, ps)
        vmask = valid[:, None, :]
        s = jnp.where(vmask, s, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(vmask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + _bdot(p, cf)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H), BIG_NEG, jnp.float32)
    l0 = jnp.zeros((B, H), jnp.float32)
    a0 = jnp.zeros((B, H, C), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  jnp.arange(NB, dtype=jnp.int32))
    return acc / jnp.maximum(l, 1e-30)[..., None]

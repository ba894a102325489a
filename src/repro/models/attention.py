"""Attention stack: RoPE / M-RoPE, chunked (flash-style) attention, GQA with
optional sliding window and quantized KV cache, and DeepSeek-style MLA with
the absorbed decode path.

Layouts: activations (B, S, D); per-head tensors (B, S, H, hd).
KV caches (B, S_max, Hkv, hd), int8-quantized per (token, head) when the
policy sets kv_cache_bits (the paper's quantization applied to the cache —
this is what makes 32k x 128 decode fit v5e HBM, EXPERIMENTS.md).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import pack as P
from repro.core.linear import linear_apply, linear_init
from repro.core.policy import BF16, PrecisionPolicy
from repro.kernels import ops

BIG_NEG = -2.0e9

#: write position of a packed step's pad rows: past every cache, so a dense
#: write drops and a paged one lands in the scratch page
PAD_POS = 2**30


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    window: Optional[int] = None  # SWA (h2o-danube)
    rope_theta: float = 10_000.0
    mrope_sections: Optional[tuple[int, ...]] = None  # qwen2-vl (t, h, w)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


# ------------------------------------------------------------------- RoPE


def rope_cos_sin(pos: jax.Array, head_dim: int, theta: float,
                 sections: Optional[tuple[int, ...]] = None):
    """pos (B, S) -> cos/sin (B, S, head_dim/2). With ``sections`` (M-RoPE),
    pos is (3, B, S) and freq groups are taken per section (Qwen2-VL)."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if sections is None:
        ang = pos.astype(jnp.float32)[..., None] * inv  # (B, S, half)
    else:
        assert sum(sections) == half, (sections, half)
        parts = []
        start = 0
        for i, sec in enumerate(sections):
            ang_i = pos[i].astype(jnp.float32)[..., None] * inv[start : start + sec]
            parts.append(ang_i)
            start += sec
        ang = jnp.concatenate(parts, axis=-1)  # (B, S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B, S, H, hd); half-rotation (llama-style)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ------------------------------------------- chunked (flash-style) attention


def _attn_chunk(q_blk, k, v, q_pos_blk, k_pos, *, causal, window, kv_chunk,
                groups, kv_limit: Optional[int] = None):
    """One q chunk vs all kv chunks with running softmax. Shapes:
    q_blk (B, qc, Hq, D); k/v (B, nk, kc, Hkv, D/Dv); returns (B, qc, Hq, Dv).
    ``kv_limit``: static number of kv blocks to visit (causal skip)."""
    B, qc, Hq, D = q_blk.shape
    Dv = v.shape[-1]
    scale = 1.0 / (D**0.5)

    def kv_step(carry, inp):
        m, l, acc = carry
        k_blk, v_blk, kp = inp  # (B, kc, Hkv, D), (B, kc, Hkv, Dv), (kc,)
        if groups > 1:
            k_blk = jnp.repeat(k_blk, groups, axis=2)
            v_blk = jnp.repeat(v_blk, groups, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk.astype(jnp.float32),
                       k_blk.astype(jnp.float32)) * scale
        mask = jnp.broadcast_to(kp[None, :] < 2**29, (qc, k_blk.shape[1]))
        if causal:
            mask &= kp[None, :] <= q_pos_blk[:, None]
        if window is not None:
            mask &= (q_pos_blk[:, None] - kp[None, :]) < window
        s = jnp.where(mask[None, None], s, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    from repro import runtime_flags as RF

    m0 = jnp.full((B, Hq, qc), BIG_NEG, jnp.float32)
    l0 = jnp.zeros((B, Hq, qc), jnp.float32)
    a0 = jnp.zeros((B, Hq, qc, Dv), jnp.float32)
    kp = k_pos.reshape(-1, kv_chunk)
    nk = kp.shape[0]
    if kv_limit is not None:  # static skip: (lo, hi) kv block range
        lo, hi = kv_limit
        k, v, kp = k[:, lo:hi], v[:, lo:hi], kp[lo:hi]
        nk = hi - lo
    (m, l, acc), _ = jax.lax.scan(
        kv_step, (m0, l0, a0),
        (k.swapaxes(0, 1), v.swapaxes(0, 1), kp), unroll=RF.unroll(nk))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.swapaxes(1, 2)  # (B, qc, Hq, Dv)


def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Sk, Hkv, D)
    v: jax.Array,  # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 512,
    kv_chunk: int = 512,
    q_offset: int = 0,
    impl: ops.Impl = "auto",
) -> jax.Array:
    """Memory-linear chunked attention. Differentiable; the per-q-chunk body
    is rematerialized so training never stores S x S scores.

    ``impl`` resolves like every kernel op (``auto``: Pallas on a TPU, jnp
    elsewhere). ``pallas`` runs the Pallas flash kernel (kernels/flash.py:
    grid-predicated causal/window schedule) on the shapes it takes; ``jnp``
    runs the pure-JAX path below, which is also the training one: a
    ``pallas_call`` has no transpose rule, and GSPMD cannot partition a
    Mosaic kernel across a sharded train step, so train-mode callers pass
    ``impl="jnp"``."""
    from repro import runtime_flags as RF
    from repro.kernels import dispatch

    if (dispatch.resolve_impl(impl) == "pallas" and not RF.unrolled()
            and q.shape[1] > 1 and q_offset == 0
            and v.shape[-1] == q.shape[-1]):
        out = ops.flash_mha(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, window=window,
            bq=q_chunk, bk=kv_chunk)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)
    return _flash_jnp(q, k, v, causal=causal, window=window, q_chunk=q_chunk,
                      kv_chunk=kv_chunk, q_offset=q_offset)


def _flash_jnp(q, k, v, *, causal, window, q_chunk, kv_chunk, q_offset=0):
    from repro import runtime_flags as RF

    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, Dv = v.shape[0], v.shape[1], v.shape[2], v.shape[3]
    groups = Hq // Hkv
    qc = min(RF.flash_chunk(q_chunk, Sq), Sq)
    kc = min(RF.flash_chunk(kv_chunk, Sk), Sk)
    pq, pk = -Sq % qc, -Sk % kc
    q_pos = q_offset + jnp.arange(Sq + pq)
    k_pos = jnp.where(jnp.arange(Sk + pk) < Sk, jnp.arange(Sk + pk), 2**30)
    if not causal:  # padded keys must still be masked
        k_pos = jnp.where(jnp.arange(Sk + pk) < Sk, 0, 2**30)
        q_pos = jnp.zeros((Sq + pq,), jnp.int32)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (Sq + pq) // qc, (Sk + pk) // kc
    kb = k.reshape(B, nk, kc, Hkv, D)
    vb = v.reshape(B, nk, kc, Hkv, Dv)

    chunk_fn = functools.partial(
        _attn_chunk, causal=causal, window=window, kv_chunk=kc, groups=groups
    )
    chunk_fn_ckpt = jax.checkpoint(
        lambda qb, qp, lim: chunk_fn(qb, kb, vb, qp, k_pos, kv_limit=lim),
        static_argnums=(2,))

    def per_chunk(i, kv_limit=None):
        qb = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, i * qc, qc)
        return chunk_fn_ckpt(qb, qp, kv_limit)

    if RF.unrolled():
        # dry-run accounting path: static q-chunk loop; with causal_skip a
        # chunk only visits kv blocks intersecting its (windowed) past — the
        # schedule a production flash kernel realizes via grid predication.
        nk_all = (Sk + pk) // kc
        lims = [None] * nq
        if causal and RF.FLAGS.get("causal_skip"):
            lims = []
            for i in range(nq):
                hi = min(nk_all, -(-((i + 1) * qc + q_offset) // kc))
                lo = 0
                if window is not None:
                    lo = max(0, (i * qc + q_offset - window) // kc)
                lims.append((lo, max(hi, lo + 1)))
        out_chunks = [per_chunk(i, lims[i]) for i in range(nq)]
        out = jnp.stack(out_chunks)
    else:
        out = jax.lax.map(per_chunk, jnp.arange(nq))  # (nq, B, qc, Hq, Dv)
    out = out.swapaxes(0, 1).reshape(B, nq * qc, Hq, Dv)[:, :Sq]
    return out.astype(q.dtype)


# ------------------------------------------------------- quantized KV cache


def kv_quantize(x: jax.Array, bits: Optional[int]):
    """x (B, S, H, D) -> (storage, scales) with per-(token, head) symmetric
    scales; bits None -> bf16 passthrough; 4 -> packed two-per-byte."""
    if bits is None:
        return x.astype(jnp.bfloat16), None
    half = 1 << (bits - 1)
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / (half - 1)
    q = jnp.clip(jnp.round(x / scale), -half, half - 1).astype(jnp.int8)
    if bits < 8:
        q = P.pack(q, bits)
    return q, scale.squeeze(-1)  # (B, S, H, D/r), (B, S, H)


def kv_dequantize(q: jax.Array, scale: Optional[jax.Array], bits: Optional[int]):
    if bits is None:
        return q
    if bits < 8:
        q = P.unpack(q, bits, signed=True)
    return (q.astype(jnp.float32) * scale[..., None]).astype(jnp.bfloat16)


def cache_init(batch: int, s_max: int, kv_heads: int, head_dim: int,
               bits: Optional[int]) -> dict:
    if bits is None:
        z = jnp.zeros((batch, s_max, kv_heads, head_dim), jnp.bfloat16)
        return {"k": z, "v": z}
    r = P.pack_ratio(bits)
    zq = jnp.zeros((batch, s_max, kv_heads, head_dim // r), jnp.int8)
    zs = jnp.zeros((batch, s_max, kv_heads), jnp.float32)
    return {"k": zq, "k_s": zs, "v": zq, "v_s": zs}


def seq_insert(buf: jax.Array, new: jax.Array, pos: jax.Array, *,
               block_table: Optional[jax.Array] = None,
               impl: ops.Impl = "auto") -> jax.Array:
    """Write ``new`` (B, S_new, ...) into ``buf`` at sequence position
    ``pos`` — scalar (all rows) or (B,) per-row (continuous batching: every
    slot has its own write offset).

    Dense layout: ``buf`` is (B, S_max, ...), axis 1 is the sequence. Paged
    layout (``block_table`` given): ``buf`` is a page pool (n_pages,
    page_size, ...) — the page size is the pool's axis 1 — and the write
    routes through the block table; rows on unallocated blocks (table entry
    0) land in the reserved scratch page."""
    new = new.astype(buf.dtype)
    if block_table is not None:
        pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (new.shape[0],))
        return ops.paged_scatter(buf, new, pos_b, block_table, impl=impl)
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice_in_dim(buf, new, pos, 1)
    B, S_new = new.shape[:2]
    idx = pos[:, None] + jnp.arange(S_new)[None]  # (B, S_new)
    # drop (never clamp) rows past s_max: a mixed step's right-padded tail
    # near capacity must not clamp-shift onto the slot's real last row —
    # the same semantics the paged twin gets from its scratch-page binning
    return buf.at[jnp.arange(B)[:, None], idx].set(new, mode="drop")


class PackedRows(NamedTuple):
    """Where the rows of a packed mixed step live (``model.mixed_step``).

    Rows ``[0, budget)`` carry the step's prefill chunks, each contiguous;
    rows ``[budget, T)`` are the decode rows, row ``budget + s`` slot
    ``s``'s. ``lane`` (T,) is each row's slot and ``pos`` (T,) its write
    position, ``PAD_POS`` for a pad. ``starts`` (P,) is the first row of
    each prefill chunk, ascending, ``budget`` where the step has fewer."""

    lane: jax.Array
    pos: jax.Array
    starts: jax.Array
    budget: int


def packed_insert(buf: jax.Array, new: jax.Array, rows: PackedRows, *,
                  block_table: Optional[jax.Array] = None,
                  impl: ops.Impl = "auto") -> jax.Array:
    """Write row t of ``new`` (T, 1, ...) at position ``rows.pos[t]`` of
    slot ``rows.lane[t]``. Dense ``buf`` (n_slots, S_max, ...): pads drop.
    Paged: each row is a one-token write through a one-entry block table
    ``[[page]]`` at its in-page offset, the page looked up here; a pad (or
    a row past its table) gets the scratch page 0, so no real page sees
    it."""
    if block_table is None:
        return buf.at[rows.lane, rows.pos].set(new[:, 0].astype(buf.dtype),
                                               mode="drop")
    ps, nb = buf.shape[1], block_table.shape[1]
    blk = rows.pos // ps
    page = jnp.where(blk < nb,
                     block_table[rows.lane, jnp.minimum(blk, nb - 1)], 0)
    return ops.paged_scatter(buf, new, rows.pos % ps, page[:, None],
                             impl=impl)


def cache_update(cache: dict, k: jax.Array, v: jax.Array, pos: jax.Array,
                 bits: Optional[int], *,
                 block_table: Optional[jax.Array] = None,
                 impl: ops.Impl = "auto",
                 rows: Optional[PackedRows] = None) -> dict:
    """Insert new k/v (B, S_new, H, D) at ``pos`` (scalar or (B,)); with
    ``rows``, the T rows (T, 1, H, D) of a packed step where ``rows`` says
    (``packed_insert``; ``pos`` unused)."""
    kq, ks = kv_quantize(k, bits)
    vq, vs = kv_quantize(v, bits)
    pg = dict(block_table=block_table, impl=impl)

    def put(buf, new):
        if rows is not None:
            return packed_insert(buf, new, rows, **pg)
        return seq_insert(buf, new, pos, **pg)

    out = dict(cache)
    out["k"] = put(cache["k"], kq)
    out["v"] = put(cache["v"], vq)
    if bits is not None:
        out["k_s"] = put(cache["k_s"], ks)
        out["v_s"] = put(cache["v_s"], vs)
    return out


def cache_read(cache: dict, bits: Optional[int], *,
               block_table: Optional[jax.Array] = None,
               impl: ops.Impl = "auto"):
    """Dequantized K/V. Dense: the (B, S_max, ...) buffers as stored. Paged:
    each pool leaf is gathered through the block table into contiguous
    (B, n_blocks * page_size, ...) logical rows FIRST (packed/int8 width —
    the gather moves quantized bytes, never bf16), then dequantized; gather
    and dequantize commute elementwise, so the result is bit-identical to
    reading a dense cache holding the same rows."""
    kq, ks = cache["k"], cache.get("k_s")
    vq, vs = cache["v"], cache.get("v_s")
    if block_table is not None:
        kq = ops.paged_gather(kq, block_table, impl=impl)
        vq = ops.paged_gather(vq, block_table, impl=impl)
        if ks is not None:
            ks = ops.paged_gather(ks, block_table, impl=impl)
            vs = ops.paged_gather(vs, block_table, impl=impl)
    k = kv_dequantize(kq, ks, bits)
    v = kv_dequantize(vq, vs, bits)
    return k, v


# ----------------------------------------------------------------- GQA block


def attn_init(key: jax.Array, cfg: AttnCfg, policy: PrecisionPolicy, *,
              mode: str = "train", dtype=jnp.float32) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    lp_qkv = policy.of("attn_qkv")
    lp_out = policy.of("attn_out")
    return {
        "wq": linear_init(kq, cfg.d_model, cfg.q_dim, lp_qkv, bias=cfg.qkv_bias, mode=mode, dtype=dtype),
        "wk": linear_init(kk, cfg.d_model, cfg.kv_dim, lp_qkv, bias=cfg.qkv_bias, mode=mode, dtype=dtype),
        "wv": linear_init(kv, cfg.d_model, cfg.kv_dim, lp_qkv, bias=cfg.qkv_bias, mode=mode, dtype=dtype),
        "wo": linear_init(ko, cfg.q_dim, cfg.d_model, lp_out, mode=mode, dtype=dtype),
    }


def attn_apply(
    params: dict,
    x: jax.Array,  # (B, S, d_model)
    pos: jax.Array,  # (B, S) int32 or (3, B, S) for M-RoPE
    cfg: AttnCfg,
    policy: PrecisionPolicy,
    *,
    mode: str = "train",
    impl: ops.Impl = "auto",
    causal: bool = True,
    cache: Optional[dict] = None,
    cache_pos: Optional[jax.Array] = None,
    kv_override: Optional[tuple[jax.Array, jax.Array]] = None,  # cross-attn
    attend_cached: bool = False,
    block_table: Optional[jax.Array] = None,
    fused: bool = False,
    packed: Optional[PackedRows] = None,
):
    """Returns (y, new_cache). Prefill/train: cache None -> flash path.
    Decode: cache given, S == new tokens (typically 1).

    ``attend_cached`` forces the cache-read path even when S > 1 (chunked
    prefill: queries must see tokens cached by *earlier* chunks, and must
    read the same dequantized values the decode path reads so chunked and
    token-by-token prefill are numerically identical).

    ``block_table`` (B, n_blocks) switches the cache to the PAGED layout:
    leaves are a (n_pages, page_size, ...) pool, writes scatter through the
    table, reads gather the slot's pages into the same contiguous logical
    rows the dense path stores — positions past a slot's write frontier are
    causally masked to exactly-zero softmax weight, so whatever a recycled
    page still holds can never reach the output and paged decode stays
    bit-identical to the dense-slot path.

    ``fused`` routes single-token causal decode through the fused
    paged-attention kernel (kernels/paged_attn.py): the block table is walked
    inside the kernel and quantized pages are dequantized in VMEM, so the
    gather-to-dense materialization below (``cache_read``) never runs. Other
    shapes (chunked prefill, cross-attn, non-causal) fall back unchanged.

    ``packed`` takes the rows of a packed mixed step, x (T, 1, d_model):
    each row is written where ``packed`` says and attends through
    :func:`packed_attend`."""
    B, S, _ = x.shape
    lp_qkv = policy.of("attn_qkv")
    lp_out = policy.of("attn_out")
    q = linear_apply(params["wq"], x, lp_qkv, mode=mode, impl=impl)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    if kv_override is None:
        k = linear_apply(params["wk"], x, lp_qkv, mode=mode, impl=impl)
        v = linear_apply(params["wv"], x, lp_qkv, mode=mode, impl=impl)
        k = k.reshape(B, S, cfg.kv_heads, cfg.head_dim)
        v = v.reshape(B, S, cfg.kv_heads, cfg.head_dim)
        if cfg.mrope_sections is None and pos.ndim == 3:
            pos = pos[0]
        cos, sin = rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    else:
        k, v = kv_override  # pre-computed encoder K/V (whisper cross-attn)

    bits = policy.kv_cache_bits
    if packed is not None:
        new_cache = cache_update(cache, k, v, None, bits,
                                 block_table=block_table, impl=impl,
                                 rows=packed)
        y = packed_attend(q[:, 0], new_cache, packed, cfg, bits,
                          block_table=block_table, impl=impl)
        out = linear_apply(params["wo"], y.reshape(B, S, cfg.q_dim), lp_out,
                           mode=mode, impl=impl)
        return out, new_cache
    new_cache = cache
    prefill = (cache is not None and S > 1 and kv_override is None
               and not attend_cached)
    if block_table is not None and prefill:
        raise NotImplementedError(
            "whole-sequence prefill over a paged cache is unsupported — "
            "prefill through model.prefill_into_pages (gather-row path) or "
            "decode token-by-token")
    fused_decode = (fused and cache is not None and kv_override is None
                    and S == 1 and causal and not prefill)
    if cache is not None and kv_override is None:
        new_cache = cache_update(cache, k, v, cache_pos, bits,
                                 block_table=block_table, impl=impl)
        if not prefill and not fused_decode:
            k, v = cache_read(new_cache, bits, block_table=block_table,
                              impl=impl)

    if fused_decode:
        # fused path: attend straight over the stored (quantized) cache —
        # the kernel walks the block table and dequantizes per page in VMEM
        pos_b = jnp.broadcast_to(jnp.atleast_1d(cache_pos), (B,))
        y = ops.paged_attn(
            q[:, 0].astype(jnp.float32),
            new_cache["k"], new_cache.get("k_s"),
            new_cache["v"], new_cache.get("v_s"),
            pos_b, bits=bits, block_table=block_table,
            window=cfg.window, impl=impl,
        )[:, None].astype(x.dtype)
    elif cache is None or prefill:
        # full-sequence: flash path. Prefill (cache_pos == 0) attends over the
        # freshly computed k/v while the quantized cache write happens above.
        y = flash_attention(q, k, v, causal=causal, window=cfg.window,
                            impl="jnp" if mode == "train" else impl)
    else:
        # decode / cross-attn: q is short, keys long -> single-pass softmax
        valid = None
        if cache is not None:
            pos_b = jnp.broadcast_to(jnp.atleast_1d(cache_pos), (B,))
            qpos = pos_b[:, None] + jnp.arange(S)[None]  # (B, S)
            if causal:
                valid = _cached_mask(qpos, k.shape[1], cfg.window)
            else:
                k_idx = jnp.arange(k.shape[1])
                valid = k_idx[None, None, :] <= (pos_b[:, None, None] + S - 1)
                if cfg.window is not None:
                    valid &= (qpos[:, :, None] - k_idx[None, None, :]) < cfg.window
        y = _single_pass(q, k, v, valid, cfg).astype(x.dtype)

    y = y.reshape(B, S, cfg.q_dim)
    out = linear_apply(params["wo"], y, lp_out, mode=mode, impl=impl)
    return out, new_cache


def _cached_mask(qpos: jax.Array, n_keys: int,
                 window: Optional[int]) -> jax.Array:
    """(B, S, n_keys) causal (and windowed) mask of queries at ``qpos``
    (B, S) over a table's key rows."""
    k_idx = jnp.arange(n_keys)
    valid = k_idx[None, None, :] <= qpos[:, :, None]
    if window is not None:
        valid &= (qpos[:, :, None] - k_idx[None, None, :]) < window
    return valid


def _single_pass(q, k, v, valid, cfg: AttnCfg) -> jax.Array:
    """Single-pass softmax attention of q (B, S, H, D) over whole key rows
    k/v (B, Sk, Hkv, D), masked by ``valid`` (B, S, Sk) unless None; f32
    out (B, S, H, D). Query head h reads KV head h // (H / Hkv): the query
    heads are grouped onto their KV head, never the K/V repeated per query
    head (on a decode batch that copy is most of the step)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, S, Hkv, H // Hkv, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    s = s / (cfg.head_dim**0.5)
    if valid is not None:
        s = jnp.where(valid[:, None, None], s, BIG_NEG)
    p = jax.nn.softmax(s, axis=-1)
    y = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return y.reshape(B, S, H, v.shape[-1])


def packed_attend(q: jax.Array, cache: dict, rows: PackedRows, cfg: AttnCfg,
                  bits: Optional[int], *,
                  block_table: Optional[jax.Array] = None,
                  impl: ops.Impl = "auto") -> jax.Array:
    """Attention of a packed step's queries q (T, H, D), its rows already
    written to ``cache``; returns (T, H, D) in q's dtype.

    Decode rows attend as :func:`attn_apply` decodes a batch (S = 1, each
    over its own slot's table, same mask). Prefill rows are gathered into
    one (P, budget) query block per chunk, each block attending over its
    chunk's slot's table only, and gathered back; block rows past their
    chunk are computed and dropped."""
    W = rows.budget
    T = q.shape[0]
    k, v = cache_read(cache, bits, block_table=block_table, impl=impl)
    y_dec = _single_pass(q[W:, None], k, v,
                         _cached_mask(rows.pos[W:, None], k.shape[1], cfg.window),
                         cfg)[:, 0]
    idx = jnp.minimum(rows.starts[:, None] + jnp.arange(W)[None], T - 1)
    lanes = rows.lane[idx[:, 0]]
    if block_table is not None:
        k, v = cache_read(cache, bits, block_table=block_table[lanes], impl=impl)
    else:
        k, v = cache_read(jax.tree.map(lambda a: a[lanes], cache), bits)
    y_blk = _single_pass(q[idx], k, v,
                         _cached_mask(rows.pos[idx], k.shape[1], cfg.window), cfg)
    t = jnp.arange(W)
    chunk = jnp.sum(t[:, None] >= rows.starts[None, 1:], axis=1)  # (W,)
    y_pre = y_blk[chunk, t - rows.starts[chunk]]
    return jnp.concatenate([y_pre, y_dec]).astype(q.dtype)


# ---------------------------------------------------------------- MLA block


@dataclasses.dataclass(frozen=True)
class MLACfg:
    d_model: int
    n_heads: int
    q_lora: int = 1536
    kv_lora: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128
    rope_theta: float = 10_000.0


def mla_init(key: jax.Array, cfg: MLACfg, policy: PrecisionPolicy, *,
             mode: str = "train", dtype=jnp.float32) -> dict:
    from repro.models.common import rms_norm_init

    ks = jax.random.split(key, 5)
    lp = policy.of("attn_qkv")
    lp_out = policy.of("attn_out")
    H = cfg.n_heads
    return {
        "wq_a": linear_init(ks[0], cfg.d_model, cfg.q_lora, lp, mode=mode, dtype=dtype),
        "q_norm": rms_norm_init(cfg.q_lora),
        "wq_b": linear_init(ks[1], cfg.q_lora, H * (cfg.d_nope + cfg.d_rope), lp, mode=mode, dtype=dtype),
        "wkv_a": linear_init(ks[2], cfg.d_model, cfg.kv_lora + cfg.d_rope, lp, mode=mode, dtype=dtype),
        "kv_norm": rms_norm_init(cfg.kv_lora),
        # kept unpacked-major so the absorbed decode path can reshape per head
        "wkv_b": linear_init(ks[3], cfg.kv_lora, H * (cfg.d_nope + cfg.d_v), lp, mode=mode, dtype=dtype),
        "wo": linear_init(ks[4], H * cfg.d_v, cfg.d_model, lp_out, mode=mode, dtype=dtype),
    }


def _mla_wkv_b_dense(params: dict, cfg: MLACfg, lp) -> jax.Array:
    """Materialize W_kv_b (H*(d_nope+d_v), kv_lora) for the absorbed path
    (weight-only dequant when serving packed)."""
    p = params["wkv_b"]
    if "w_packed" in p:
        w = P.unpack(p["w_packed"], lp.w_bits, signed=True).astype(jnp.float32) * p["eps_w"]
    else:
        w = p["w"].astype(jnp.float32)
    return w


def mla_apply(
    params: dict,
    x: jax.Array,
    pos: jax.Array,
    cfg: MLACfg,
    policy: PrecisionPolicy,
    *,
    mode: str = "train",
    impl: ops.Impl = "auto",
    cache: Optional[dict] = None,
    cache_pos: Optional[jax.Array] = None,
    attend_cached: bool = False,
    block_table: Optional[jax.Array] = None,
    fused: bool = False,
):
    """MLA. Train/prefill: unabsorbed full-head attention. Decode: absorbed
    path over the latent cache (c_kv, k_rope) — the MLA memory win.
    ``attend_cached`` forces the absorbed cache path even when S > 1
    (chunked prefill; see attn_apply). ``block_table`` selects the paged
    latent-cache layout (see attn_apply): c/r pool pages are gathered into
    logical rows before the absorbed score, scattered on write. ``fused``
    routes single-token decode through the fused kernel
    (kernels/paged_attn.py): latent pages stay compressed in the pool, the
    kernel scores and accumulates in latent space, and W_uv is applied to
    the kernel's latent context afterwards — no gather, no per-head K/V."""
    from repro.models.common import rms_norm

    B, S, _ = x.shape
    H = cfg.n_heads
    lp = policy.of("attn_qkv")
    lp_out = policy.of("attn_out")

    q = linear_apply(params["wq_b"], rms_norm(params["q_norm"],
        linear_apply(params["wq_a"], x, lp, mode=mode, impl=impl)), lp, mode=mode, impl=impl)
    q = q.reshape(B, S, H, cfg.d_nope + cfg.d_rope)
    q_nope, q_rope = q[..., : cfg.d_nope], q[..., cfg.d_nope :]

    kv_a = linear_apply(params["wkv_a"], x, lp, mode=mode, impl=impl)
    c_kv = rms_norm(params["kv_norm"], kv_a[..., : cfg.kv_lora])  # (B, S, kv_lora)
    k_rope = kv_a[..., cfg.kv_lora :].reshape(B, S, 1, cfg.d_rope)

    if pos.ndim == 3:
        pos = pos[0]
    cos, sin = rope_cos_sin(pos, cfg.d_rope, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)

    prefill = cache is not None and S > 1 and not attend_cached
    new_cache = cache
    if block_table is not None and prefill:
        raise NotImplementedError(
            "whole-sequence prefill over a paged cache is unsupported — "
            "prefill through model.prefill_into_pages (gather-row path) or "
            "decode token-by-token")
    if cache is not None:
        bits = policy.kv_cache_bits
        pg = dict(block_table=block_table, impl=impl)
        ckv_q, ckv_s = kv_quantize(c_kv[:, :, None, :], bits)
        new_cache = dict(cache)
        new_cache["c"] = seq_insert(cache["c"], ckv_q, cache_pos, **pg)
        if bits is not None:
            new_cache["c_s"] = seq_insert(cache["c_s"], ckv_s, cache_pos, **pg)
        new_cache["r"] = seq_insert(cache["r"], k_rope, cache_pos, **pg)

    if cache is None or prefill:
        # unabsorbed: materialize per-head k_nope, v from c_kv (train/prefill)
        kv = linear_apply(params["wkv_b"], c_kv, lp, mode=mode, impl=impl)
        kv = kv.reshape(B, S, H, cfg.d_nope + cfg.d_v)
        k_nope, v = kv[..., : cfg.d_nope], kv[..., cfg.d_nope :]
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, cfg.d_rope))], axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        y = flash_attention(qf, k, v, causal=True,
                            impl="jnp" if mode == "train" else impl)
    elif fused and S == 1:
        wkv_b = _mla_wkv_b_dense(params, cfg, lp).reshape(H, cfg.d_nope + cfg.d_v, cfg.kv_lora)
        w_uk, w_uv = wkv_b[:, : cfg.d_nope, :], wkv_b[:, cfg.d_nope :, :]
        q_lat = jnp.einsum("bhd,hdc->bhc", q_nope[:, 0].astype(jnp.float32), w_uk)
        pos_b = jnp.broadcast_to(jnp.atleast_1d(cache_pos), (B,))
        ctx = ops.paged_mla_attn(
            q_lat, q_rope[:, 0].astype(jnp.float32),
            new_cache["c"], new_cache.get("c_s"), new_cache["r"], pos_b,
            bits=bits, scale=1.0 / ((cfg.d_nope + cfg.d_rope) ** 0.5),
            block_table=block_table, impl=impl,
        )  # (B, H, kv_lora) latent context, compressed end to end
        y = jnp.einsum("bhc,hdc->bhd", ctx, w_uv)[:, None].astype(x.dtype)
    else:
        c_buf, c_s = new_cache["c"], new_cache.get("c_s")
        r_all = new_cache["r"]  # (B, S_max, 1, d_rope) bf16
        if block_table is not None:
            # gather latent pages at stored (packed) width, dequantize after
            c_buf = ops.paged_gather(c_buf, block_table, impl=impl)
            if c_s is not None:
                c_s = ops.paged_gather(c_s, block_table, impl=impl)
            r_all = ops.paged_gather(r_all, block_table, impl=impl)
        c_all = kv_dequantize(c_buf, c_s, bits)[:, :, 0]

        wkv_b = _mla_wkv_b_dense(params, cfg, lp).reshape(H, cfg.d_nope + cfg.d_v, cfg.kv_lora)
        w_uk, w_uv = wkv_b[:, : cfg.d_nope, :], wkv_b[:, cfg.d_nope :, :]
        # absorb: q_lat[b,s,h,c] = q_nope . W_uk
        q_lat = jnp.einsum("bshd,hdc->bshc", q_nope.astype(jnp.float32), w_uk)
        s_lat = jnp.einsum("bshc,btc->bhst", q_lat, c_all.astype(jnp.float32))
        # rope score: every head shares the single rope key
        s_rope = jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                            r_all.astype(jnp.float32)[:, :, 0])
        s = (s_lat + s_rope) / ((cfg.d_nope + cfg.d_rope) ** 0.5)
        t_idx = jnp.arange(c_all.shape[1])
        pos_b = jnp.broadcast_to(jnp.atleast_1d(cache_pos), (B,))
        qpos = pos_b[:, None] + jnp.arange(S)[None]  # (B, S)
        valid = t_idx[None, None, :] <= qpos[:, :, None]
        s = jnp.where(valid[:, None], s, BIG_NEG)
        p = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhst,btc->bshc", p, c_all.astype(jnp.float32))
        y = jnp.einsum("bshc,hdc->bshd", ctx, w_uv)  # (B, S, H, d_v)
        y = y.astype(x.dtype)

    y = y.reshape(B, S, H * cfg.d_v)
    out = linear_apply(params["wo"], y, lp_out, mode=mode, impl=impl)
    return out, new_cache


def mla_cache_init(batch: int, s_max: int, cfg: MLACfg, bits: Optional[int]) -> dict:
    if bits is None:
        return {
            "c": jnp.zeros((batch, s_max, 1, cfg.kv_lora), jnp.bfloat16),
            "r": jnp.zeros((batch, s_max, 1, cfg.d_rope), jnp.bfloat16),
        }
    r = P.pack_ratio(bits)
    return {
        "c": jnp.zeros((batch, s_max, 1, cfg.kv_lora // r), jnp.int8),
        "c_s": jnp.zeros((batch, s_max, 1), jnp.float32),
        "r": jnp.zeros((batch, s_max, 1, cfg.d_rope), jnp.bfloat16),
    }

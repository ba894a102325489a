"""Public, jit-friendly entry points for the mixed-precision kernels.

Every call routes through the dispatch registry (kernels/dispatch.py): the
permutation selects a registered ``KernelEntry`` — ``pallas`` (the Pallas TPU
kernel; interpret=True off-TPU) or ``jnp`` (the bit-exact plain-XLA twin used
for CPU training/tests/dry-run) — and tile shapes come from the autotuner's
cache (kernels/tuning.py) unless the caller pins them explicitly.

``impl="auto"`` picks ``pallas`` on TPU backends and ``jnp`` elsewhere, so the
same model code runs in every environment (DESIGN.md Sec. 6).
"""

from __future__ import annotations

from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.core import pack as P
from repro.core import quant as Q
from repro.kernels import dispatch, tuning
from repro.kernels.mpmm import requant_vector

Impl = Literal["auto", "pallas", "jnp"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_axis(a: jax.Array, axis: int, mult: int) -> jax.Array:
    size = a.shape[axis]
    pad = -size % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _ceil(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def mpmm(
    x_p: jax.Array,  # (M, K/rx) packed unsigned ifmaps
    w_p: jax.Array,  # (N, K/rw) packed signed weights
    rq: Q.RequantParams,
    *,
    x_bits: int,
    w_bits: int,
    y_bits: int,
    x_signed: bool = False,
    out_kind: str = "packed",
    out_scale: float | jax.Array = 1.0,
    impl: Impl = "auto",
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
) -> jax.Array:
    """The paper's MatMul + fused QntPack over any of the 27 permutations.

    bm/bn/bk default to the autotuned tiles for this (permutation, shape)
    cell — benchmarks/tuned/tiles_mpmm.json — falling back to the static
    defaults when untuned. Pass explicit values to pin a block shape.
    """
    if rq is None:
        rq = Q.make_requant_params(y_bits=y_bits, eps_phi=2**-8, eps_y=1.0)
    entry = dispatch.lookup("mpmm", x_bits=x_bits, w_bits=w_bits, y_bits=y_bits, impl=impl)
    if entry.key.impl == "jnp":
        return entry.fn(
            x_p, w_p, rq, x_signed=x_signed, out_kind=out_kind, out_scale=out_scale
        )
    rx, rw, ry = P.pack_ratio(x_bits), P.pack_ratio(w_bits), P.pack_ratio(y_bits)
    M, N, K = x_p.shape[0], w_p.shape[0], x_p.shape[1] * rx
    t = tuning.resolve_tiles(
        "mpmm",
        perm=tuning.perm_key(x_bits, w_bits, y_bits),
        shape=tuning.shape_key(M, N, K),
        overrides={"bm": bm, "bn": bn, "bk": bk},
    )
    bm_ = min(t["bm"], _ceil(M, 8))
    bn_ = P.snap_block(t["bn"], N, ry if out_kind == "packed" else 1)
    bk_ = P.snap_block(t["bk"], K, max(rx, rw))
    xp = _pad_axis(_pad_axis(x_p, 0, bm_), 1, bk_ // rx)
    wp = _pad_axis(_pad_axis(w_p, 0, bn_), 1, bk_ // rw)
    rqv = requant_vector(rq)
    scale = jnp.asarray(out_scale, jnp.float32).reshape(1)
    y = entry.fn(
        xp, wp, rqv, scale,
        x_signed=x_signed, out_kind=out_kind,
        bm=bm_, bn=bn_, bk=bk_, interpret=_interpret(),
    )
    if out_kind == "packed":
        return y[:M, : N // ry]
    return y[:M, :N]


def qntpack(
    phi: jax.Array,
    rq: Q.RequantParams,
    *,
    y_bits: int,
    impl: Impl = "auto",
    bm: Optional[int] = None,
) -> jax.Array:
    entry = dispatch.lookup("qntpack", y_bits=y_bits, impl=impl)
    if entry.key.impl == "jnp":
        return entry.fn(phi, rq)
    M, N = phi.shape
    t = tuning.resolve_tiles(
        "qntpack", perm=tuning.perm_key(y_bits=y_bits),
        shape=tuning.shape_key(M, N), overrides={"bm": bm},
    )
    # a block holds bm whole int32 rows, double-buffered in VMEM: keep the
    # pair within 4 MiB (a wide row, d_ff = 8192, takes bm <= 64)
    bm_ = min(t["bm"], _ceil(M, 8), max(8, (1 << 22) // (8 * N) // 8 * 8))
    ry = P.pack_ratio(y_bits)
    phip = _pad_axis(phi, 0, bm_)
    y = entry.fn(phip, requant_vector(rq), bm=bm_, interpret=_interpret())
    return y[:M, : N // ry]


def conv2d(
    x_p: jax.Array,  # (H, W, C/rx) packed HWC ifmap (un-padded)
    w_p: jax.Array,  # (Cout, 9*C/rw) packed weights
    rq: Q.RequantParams,
    *,
    x_bits: int,
    w_bits: int,
    y_bits: int,
    impl: Impl = "auto",
    bh: Optional[int] = None,
) -> jax.Array:
    """3x3/s1/p1 HWC conv (the paper's Reference Layer shape family).

    The output-row block ``bh`` resolves through the autotuner cache like
    every other dispatched op (benchmarks/tuned/tiles_conv2d.json; falls back
    to the static default when untuned); pass ``bh`` to pin it. The resolved
    value is snapped to the largest divisor of H so the grid tiles exactly.
    """
    entry = dispatch.lookup("conv2d", x_bits=x_bits, w_bits=w_bits, y_bits=y_bits, impl=impl)
    if entry.key.impl == "jnp":
        return entry.fn(x_p, w_p, rq)
    H, W = x_p.shape[0], x_p.shape[1]
    C = x_p.shape[2] * P.pack_ratio(x_bits)
    t = tuning.resolve_tiles(
        "conv2d",
        perm=tuning.perm_key(x_bits, w_bits, y_bits),
        shape=tuning.shape_key(H * W, w_p.shape[0], 9 * C),
        overrides={"bh": bh},
    )
    bh_ = max(d for d in range(1, min(t["bh"], H) + 1) if H % d == 0)
    x_pad = jnp.pad(x_p, ((1, 1), (1, 1), (0, 0)))  # quantized zero == 0.0
    return entry.fn(x_pad, w_p, requant_vector(rq), bh=bh_,
                    interpret=_interpret())


def wdqmm(
    x: jax.Array,  # (M, K) bf16/f32 activations
    w_p: jax.Array,  # (N, K/r) packed signed weights
    eps_w: jax.Array,
    *,
    w_bits: int,
    impl: Impl = "auto",
    bm: Optional[int] = None,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
) -> jax.Array:
    """Weight-only dequant matmul (decode GEMV path)."""
    entry = dispatch.lookup("wdqmm", w_bits=w_bits, impl=impl)
    if entry.key.impl == "jnp":
        return entry.fn(x, w_p, jnp.asarray(eps_w, jnp.float32))
    rw = P.pack_ratio(w_bits)
    M, K = x.shape
    N = w_p.shape[0]
    t = tuning.resolve_tiles(
        "wdqmm", perm=tuning.perm_key(w_bits=w_bits),
        shape=tuning.shape_key(M, N, K),
        overrides={"bm": bm, "bn": bn, "bk": bk},
    )
    bm_ = min(t["bm"], _ceil(M, 8))
    bn_ = P.snap_block(t["bn"], N, 1)
    bk_ = P.snap_block(t["bk"], K, rw)
    xp = _pad_axis(_pad_axis(x, 0, bm_), 1, bk_)
    wp = _pad_axis(_pad_axis(w_p, 0, bn_), 1, bk_ // rw)
    y = entry.fn(xp, wp, jnp.asarray(eps_w, jnp.float32).reshape(1),
                 bm=bm_, bn=bn_, bk=bk_, interpret=_interpret())
    return y[:M, :N]


def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
              window: Optional[int], bq: int, bk: int) -> jax.Array:
    """Pallas flash attention forward over (B, H, S, D) operands
    (kernels/flash.py); not a registry cell — models/attention.py calls it
    when its ``impl`` resolves to Pallas and runs its own jnp path
    otherwise."""
    from repro.kernels.flash import flash_mha_pallas

    return flash_mha_pallas(q, k, v, causal=causal, window=window, bq=bq,
                            bk=bk, interpret=_interpret())


def paged_gather(
    pool: jax.Array,  # (n_pages, page_size, ...) packed KV page pool
    block_table: jax.Array,  # (B, n_blocks) int32 physical page ids
    *,
    impl: Impl = "auto",
) -> jax.Array:
    """Gather a paged KV pool into contiguous logical rows
    (B, n_blocks * page_size, ...) — the paged decode read path."""
    entry = dispatch.lookup("paged_gather", impl=impl)
    if entry.key.impl == "jnp":
        return entry.fn(pool, block_table)
    return entry.fn(pool, block_table, interpret=_interpret())


def paged_scatter(
    pool: jax.Array,  # (n_pages, page_size, ...)
    new: jax.Array,  # (B, S_new, ...) rows to write
    pos: jax.Array,  # (B,) int32 logical write positions
    block_table: jax.Array,  # (B, n_blocks) int32
    *,
    impl: Impl = "auto",
) -> jax.Array:
    """Scatter new token rows into the page pool through the block table —
    the paged decode write path. Rows mapping outside the table (or onto
    unallocated blocks, entry 0) land in the reserved scratch page."""
    entry = dispatch.lookup("paged_scatter", impl=impl)
    if entry.key.impl == "jnp":
        return entry.fn(pool, new, pos, block_table)
    return entry.fn(pool, new, pos, block_table, interpret=_interpret())


def paged_copy(
    pool: jax.Array,  # (n_pages, page_size, ...)
    src: jax.Array,  # (K,) int32 source page ids
    dst: jax.Array,  # (K,) int32 destination page ids
    *,
    impl: Impl = "auto",
) -> jax.Array:
    """Clone whole pages inside the pool (``dst[i] = src[i]``) — the prefix
    cache's copy-on-write primitive (serve/prefix.py)."""
    entry = dispatch.lookup("paged_copy", impl=impl)
    if entry.key.impl == "jnp":
        return entry.fn(pool, src, dst)
    return entry.fn(pool, src, dst, interpret=_interpret())


def _dense_as_pool(bufs, B: int, S: int, bs: int):
    """View dense (B, S, ...) cache stripes as a (B*S/bs, bs, ...) page pool
    plus the identity block table — a free reshape (rows stay contiguous), so
    the slot backend shares the paged kernel rather than growing a twin."""
    nb = S // bs
    pooled = tuple(
        None if a is None else a.reshape(B * nb, bs, *a.shape[2:]) for a in bufs
    )
    bt = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    return pooled, bt


def _snap_divisor(bs: int, S: int) -> int:
    return max(d for d in range(1, min(bs, S) + 1) if S % d == 0)


def paged_attn(
    q: jax.Array,  # (B, Hq, D) one query token per slot
    k: jax.Array,  # pool (P, ps, Hkv, D/r) or dense (B, S, Hkv, D/r)
    k_s: Optional[jax.Array],  # matching (..., Hkv) scales; None when bf16
    v: jax.Array,
    v_s: Optional[jax.Array],
    pos: jax.Array,  # (B,) int32 last valid cache row per slot
    *,
    bits: Optional[int],
    block_table: Optional[jax.Array] = None,  # (B, NB) int32; None = dense
    window: Optional[int] = None,
    impl: Impl = "auto",
    bs: Optional[int] = None,
) -> jax.Array:
    """Fused GQA decode attention over quantized KV pages (in-kernel dequant).

    With ``block_table`` the cache is a page pool and the pool's page size is
    the block size. Without it the cache is a dense slot layout: the stripes
    are viewed as a pool with an identity block table, and the block size
    ``bs`` resolves through the autotuner cache
    (benchmarks/tuned/tiles_paged_attn.json; snapped to a divisor of S).
    Returns (B, Hq, D) f32 — bit-exact with the registered jnp twin.
    """
    entry = dispatch.lookup("paged_attn", w_bits=bits, impl=impl)
    if block_table is None:
        B, S = k.shape[0], k.shape[1]
        t = tuning.resolve_tiles(
            "paged_attn", perm=tuning.perm_key(w_bits=bits),
            shape=tuning.shape_key(S, q.shape[1], q.shape[2]),
            overrides={"bs": bs},
        )
        (k, k_s, v, v_s), block_table = _dense_as_pool(
            (k, k_s, v, v_s), B, S, _snap_divisor(t["bs"], S))
    if entry.key.impl == "jnp":
        return entry.fn(q, k, k_s, v, v_s, pos, block_table, window=window)
    return entry.fn(q, k, k_s, v, v_s, pos, block_table, window=window,
                    interpret=_interpret())


def paged_mla_attn(
    q_lat: jax.Array,  # (B, H, C) absorbed query (q_nope . W_uk)
    q_rope: jax.Array,  # (B, H, dr) rotary query
    c: jax.Array,  # latent pages, pool (P, ps, 1, C/r) or dense (B, S, 1, C/r)
    c_s: Optional[jax.Array],  # matching (..., 1) scales; None when bf16
    r: jax.Array,  # shared rope-key rows, same layout as c with dr tail
    pos: jax.Array,  # (B,) int32
    *,
    bits: Optional[int],
    scale: float,
    block_table: Optional[jax.Array] = None,
    impl: Impl = "auto",
    bs: Optional[int] = None,
) -> jax.Array:
    """Fused absorbed-MLA decode attention; latent pages stay compressed in
    the pool. Returns the latent context (B, H, C) f32 — the caller applies
    W_uv. Block-size resolution mirrors :func:`paged_attn` (same tuning op:
    the tunable axis is the dense-view block size either way)."""
    entry = dispatch.lookup("paged_mla_attn", w_bits=bits, impl=impl)
    if block_table is None:
        B, S = c.shape[0], c.shape[1]
        t = tuning.resolve_tiles(
            "paged_attn", perm=tuning.perm_key(w_bits=bits),
            shape=tuning.shape_key(S, q_lat.shape[1], q_lat.shape[2]),
            overrides={"bs": bs},
        )
        (c, c_s, r), block_table = _dense_as_pool(
            (c, c_s, r), B, S, _snap_divisor(t["bs"], S))
    if entry.key.impl == "jnp":
        return entry.fn(q_lat, q_rope, c, c_s, r, pos, block_table, scale=scale)
    return entry.fn(q_lat, q_rope, c, c_s, r, pos, block_table, scale=scale,
                    interpret=_interpret())


# ------------------------------------------------------- quantize-and-pack IO


def quantize_pack_act(x: jax.Array, beta, bits: int) -> tuple[jax.Array, jax.Array]:
    """float -> packed unsigned activations + eps scale."""
    q, eps = Q.quantize_act(x, beta, bits)
    return P.pack(q, bits), eps


def quantize_pack_weight(w: jax.Array, bits: int) -> tuple[jax.Array, jax.Array]:
    """float (N, K) -> packed signed weights + eps scale."""
    q, eps = Q.quantize_weight(w, bits)
    return P.pack(q, bits), eps


def make_rq(
    *, y_bits: int, eps_phi: float, eps_y: float, kappa: float = 1.0, lam: float = 0.0
) -> Q.RequantParams:
    return Q.make_requant_params(
        y_bits=y_bits, kappa=kappa, lam=lam, eps_phi=eps_phi, eps_y=eps_y
    )

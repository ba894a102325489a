"""Sub-byte packing along the last axis — the TPU analogue of XpulpV2
``bext`` (bit-extract, Fig. 2) and ``bins`` (bit-insert, Fig. 3).

Layout: planar within groups of ``GROUP`` (128) packed bytes. The packed
last axis of ``n / r`` bytes (``r = 8 / bits`` elements per byte) is cut into
groups of 128 bytes, the last group holding the remainder. A group of ``w``
bytes starting at packed byte ``s`` carries the ``r * w`` consecutive
elements starting at element ``r * s``, as ``r`` planes: bit field ``p``
(bits ``[p*bits, (p+1)*bits)``) of the group's byte ``j`` holds the group's
element ``p * w + j``.

  4-bit, n = 512: byte j of group 0 holds element j (low nibble) and
                  element 128 + j (high nibble); group 1 covers 256..511.
  2-bit, n = 64:  one group of 16 bytes; byte j holds elements j, 16 + j,
                  32 + j, 48 + j, low bits first.
  8-bit:          identity.

Why planar: a plane is a run of lanes, so unpacking a group is ``r`` shift
pairs and one lane concatenation, and packing is ``r`` shifts and an OR —
nothing moves an element across lanes. The old byte-interleaved layout
needed a ``(..., n/r, r)`` reshape, which the TPU's Mosaic compiler refuses.
Why 128-byte groups: a kernel block along the packed axis must be a whole
number of groups (or the whole row) to unpack to a contiguous element range,
and 128 bytes is one vreg's lanes, which Mosaic requires of a lane block
anyway (see :func:`snap_block`).

This mirrors the paper's HWC packing of adjacent channel pixels into one byte;
our feature axis is both the packing axis and the *next* layer's contraction
axis, so packed blocks stream contiguously HBM -> VMEM.

All ops are int32 shifts/masks (VPU work on TPU); sign extension for weights
uses the arithmetic-shift pair ``(v << (32 - hi)) >> (32 - bits)`` with
``hi = (p+1)*bits`` — the exact semantics of the paper's sign-extending
``bext``. The shifts run on int32 because Mosaic does not legalize an int8
arithmetic shift.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Packed bytes per layout group (one vreg's 128 lanes).
GROUP = 128


def pack_ratio(bits: int) -> int:
    """Elements per storage byte."""
    if bits not in (2, 4, 8):
        raise ValueError(f"unsupported bits: {bits}")
    return 8 // bits


def packed_width(n: int, bits: int) -> int:
    r = pack_ratio(bits)
    if n % r:
        raise ValueError(f"axis size {n} not divisible by pack ratio {r} ({bits}-bit)")
    return n // r


def snap_block(b: int, n: int, r: int) -> int:
    """The block along an axis of ``n`` elements that a kernel may use, given
    a requested block ``b`` and ``r`` elements per stored byte (1 when the
    axis is not packed).

    When ``n`` is a whole number of groups (``GROUP * r`` elements), the
    block is a whole number of groups, at most ``n``: the caller pads ``n``
    up to a multiple of it with whole zero groups, which keeps the layout.
    Otherwise the block is the whole axis, which the caller must not pad (a
    padded partial group would change which bytes hold which elements). Both
    cases also meet Mosaic's rule that a lane block is a multiple of 128 or
    the whole axis."""
    g = GROUP * r
    if n % g:
        return n
    return min(max(g, b - b % g), n)


def _groups(width: int) -> list[tuple[int, int]]:
    """(first packed byte, bytes) of each layout group of a packed row."""
    return [(s, min(GROUP, width - s)) for s in range(0, width, GROUP)]


def _as_u8(p: jax.Array) -> jax.Array:
    """Reinterpret a byte tensor as uint8 (exact bit pattern)."""
    if p.dtype == jnp.uint8:
        return p
    if p.dtype == jnp.int8:
        return jax.lax.bitcast_convert_type(p, jnp.uint8)
    raise TypeError(f"expected a byte tensor, got {p.dtype}")


def _cat(parts: list[jax.Array]) -> jax.Array:
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def pack(q: jax.Array, bits: int) -> jax.Array:
    """Pack byte-held {2,4,8}-bit values along the last axis. ``bins`` analogue.

    Works for signed or unsigned integer values — two's-complement low bits
    are kept. Packed bytes are returned as int8 bit patterns.
    """
    if bits == 8:
        if q.dtype == jnp.int8:
            return q
        if q.dtype == jnp.uint8:
            return jax.lax.bitcast_convert_type(q, jnp.int8)
        return ((q.astype(jnp.int32) << 24) >> 24).astype(jnp.int8)
    r = pack_ratio(bits)
    mask = (1 << bits) - 1
    n = q.shape[-1]
    if n % r:
        raise ValueError(f"last axis {n} not divisible by {r}")
    u = q.astype(jnp.int32) & mask  # keep low `bits` bits (two's complement)
    words = []
    for s, w in _groups(n // r):
        e = r * s
        word = u[..., e:e + w]
        for p in range(1, r):
            word = word | (u[..., e + p * w:e + (p + 1) * w] << (p * bits))
        words.append(word)
    # the low byte as an int8 bit pattern (in range, so the cast is exact)
    return ((_cat(words) << 24) >> 24).astype(jnp.int8)


def fields(p: jax.Array, bits: int, *, signed: bool) -> jax.Array:
    """Unpacked values as int32 — what a kernel feeds its integer math."""
    v = p.astype(jnp.int32)  # int8 sign-extends, uint8 zero-extends
    if bits == 8:
        return (v << 24) >> 24 if signed else v & 0xFF
    r = pack_ratio(bits)
    mask = (1 << bits) - 1
    out = []
    for s, w in _groups(p.shape[-1]):
        g = v[..., s:s + w]
        for k in range(r):
            if signed:  # arithmetic shift pair: field k, sign-extended
                out.append((g << (32 - (k + 1) * bits)) >> (32 - bits))
            else:
                out.append((g >> (k * bits)) & mask)
    return _cat(out)


def unpack(p: jax.Array, bits: int, *, signed: bool) -> jax.Array:
    """Unpack to int8 (signed) or uint8 values. ``bext`` analogue.

    One int8 load yields 2 (4-bit) or 4 (2-bit) ready operands — the paper's
    loads-per-operand amortization, applied to HBM->VMEM traffic.
    """
    if bits == 8:
        if signed:
            return p if p.dtype == jnp.int8 else jax.lax.bitcast_convert_type(p, jnp.int8)
        return _as_u8(p)
    return fields(p, bits, signed=signed).astype(jnp.int8 if signed else jnp.uint8)


# Numpy twins for host-side parameter preparation / tests -------------------
# Written from the element map rather than the group loop above, so the two
# are independent statements of one layout.


def _element_map(n: int, bits: int) -> np.ndarray:
    """(r, n/r) int: element index held by bit field k of packed byte j."""
    r = pack_ratio(bits)
    j = np.arange(n // r)
    s = (j // GROUP) * GROUP  # first byte of j's group
    w = np.minimum(GROUP, n // r - s)  # bytes in j's group
    return r * s + np.arange(r)[:, None] * w + (j - s)


def pack_np(q: np.ndarray, bits: int) -> np.ndarray:
    if bits == 8:
        return q.view(np.int8) if q.dtype in (np.int8, np.uint8) else q.astype(np.int8)
    mask = (1 << bits) - 1
    u = q.astype(np.int32) & mask
    idx = _element_map(q.shape[-1], bits)
    word = np.zeros(q.shape[:-1] + (idx.shape[1],), np.int32)
    for k in range(idx.shape[0]):
        word |= u[..., idx[k]] << (k * bits)
    return word.astype(np.uint8).view(np.int8)


def unpack_np(p: np.ndarray, bits: int, *, signed: bool) -> np.ndarray:
    if bits == 8:
        return p.view(np.int8) if signed else p.view(np.uint8)
    r = pack_ratio(bits)
    mask = (1 << bits) - 1
    u = p.view(np.uint8).astype(np.int32)
    idx = _element_map(p.shape[-1] * r, bits)
    out = np.zeros(p.shape[:-1] + (p.shape[-1] * r,), np.int32)
    for k in range(r):
        out[..., idx[k]] = (u >> (k * bits)) & mask
    if signed:
        out = np.where(out >= (1 << (bits - 1)), out - (1 << bits), out)
        return out.astype(np.int8)
    return out.astype(np.uint8)

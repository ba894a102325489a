"""Seeded integer weight codes and their scales, for every model family.

Everything here is the benchmark's own: it imports nothing of the program.
A family's ``weights.py`` (``bench/families/<family>/``) names its leaves
and their shapes and makes their codes here; the harness packs those codes
into the program's serving layout, and the family's plain reference reads
the same codes, so the two start from one set of numbers that only the
benchmark made.

Codes come from integer arithmetic on ``jax.random.bits`` alone (a sum of
uniform fields, centred: an Irwin-Hall stand-in for a Gaussian), so a code
is the same whichever program, fusion or device computes it.

  * 2-bit weights: two 1-bit fields, codes in [-1, 1], std sqrt(1/2);
  * 4-bit weights: four 2-bit fields, codes in [-6, 6], std sqrt(5);
  * 8-bit weights: four 6-bit fields, codes in [-126, 126], std 36.95.

A weight is ``code * eps_w`` with one f32 scale per tensor, chosen so that
the weight's std is ``1 / sqrt(d_in)`` (the usual initialisation).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: (number of fields, bits per field) whose centred sum is a code of that width
_FIELDS = {2: (2, 1), 4: (4, 2), 8: (4, 6)}
#: the clip range of every quantized linear's input (PACT beta; fixed here)
ACT_CLIP = 6.0


def code_std(bits: int) -> float:
    n, f = _FIELDS[bits]
    m = 1 << f
    return math.sqrt(n * (m * m - 1) / 12.0)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed up to 64 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_key(key: jax.Array, stream: int, layer: int | jax.Array = 0) -> jax.Array:
    """The key of one tensor: random stream ``stream`` (a family numbers its
    leaves) of layer ``layer``."""
    return jax.random.fold_in(jax.random.fold_in(key, stream), layer)


def codes(key: jax.Array, shape: tuple, bits: int) -> jax.Array:
    """int8 codes of ``shape``: the centred sum of ``_FIELDS[bits]``."""
    n, f = _FIELDS[bits]
    u = jax.random.bits(key, shape, jnp.uint32)
    mask = jnp.uint32((1 << f) - 1)
    s = sum(((u >> jnp.uint32(8 * i)) & mask).astype(jnp.int32) for i in range(n))
    return (s - n * ((1 << f) - 1) // 2).astype(jnp.int8)


def eps_w(d_in: int, bits: int) -> jax.Array:
    return jnp.float32(1.0 / (math.sqrt(d_in) * code_std(bits)))


def vocab_padded(vocab: int) -> int:
    return -(-vocab // 256) * 256

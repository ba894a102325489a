"""Seeded weights of a dense GQA transformer, as integer codes and scales.

Everything here is the benchmark's own: it imports nothing of the program.
The harness packs these codes into the program's serving layout, and the
plain reference (``bench/reference.py``) reads the same codes, so the two
start from one set of numbers that only this file made.

Codes come from integer arithmetic on ``jax.random.bits`` alone (a sum of
four uniform fields, centred: an Irwin-Hall stand-in for a Gaussian), so a
code is the same whichever program, fusion or device computes it.

  * 4-bit weights: four 2-bit fields, codes in [-6, 6], std sqrt(5);
  * 8-bit weights: four 6-bit fields, codes in [-126, 126], std 36.95.

A weight is ``code * eps_w`` with one f32 scale per tensor, chosen so that
the weight's std is ``1 / sqrt(d_in)`` (the usual initialisation). The token
embedding is an 8-bit code times 2^-11, exact in bf16 (std about 0.018).
Rows of the vocabulary padded up to a multiple of 256 are zero, as in a
converted checkpoint.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: (number of fields, bits per field) whose centred sum is a code of that width
_FIELDS = {4: (4, 2), 8: (4, 6)}
#: the clip range of every quantized linear's input (PACT beta; fixed here)
ACT_CLIP = 6.0
EMBED_STEP = 2.0 ** -11
#: leaf ids: one random stream per tensor kind
LEAVES = ("wq", "wk", "wv", "wo", "gate", "up", "down", "embed", "head")


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


def leaf_key(key: jax.Array, leaf: str, layer: int | jax.Array = 0) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(key, LEAVES.index(leaf)), layer)


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


def linear_shapes(c: dict) -> dict:
    """(d_out, d_in) of each per-layer linear of config ``c``."""
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv, ff = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd, c["intermediate_size"]
    return {"wq": (q, d), "wk": (kv, d), "wv": (kv, d), "wo": (d, q),
            "gate": (ff, d), "up": (ff, d), "down": (d, ff)}


def layer_codes(key: jax.Array, c: dict, layer, bits: dict) -> dict:
    """Codes of every linear of one layer; ``bits`` maps leaf -> weight bits."""
    return {name: codes(leaf_key(key, name, layer), shape, bits[name])
            for name, shape in linear_shapes(c).items()}


def embed_table(key: jax.Array, c: dict) -> jax.Array:
    vp, v, d = vocab_padded(c["vocab_size"]), c["vocab_size"], c["hidden_size"]
    t = codes(leaf_key(key, "embed"), (vp, d), 8).astype(jnp.float32) * EMBED_STEP
    return jnp.where(jnp.arange(vp)[:, None] < v, t, 0.0).astype(jnp.bfloat16)


def head_codes(key: jax.Array, c: dict, bits: int) -> jax.Array:
    vp, v, d = vocab_padded(c["vocab_size"]), c["vocab_size"], c["hidden_size"]
    q = codes(leaf_key(key, "head"), (vp, d), bits)
    return jnp.where(jnp.arange(vp)[:, None] < v, q, 0).astype(jnp.int8)

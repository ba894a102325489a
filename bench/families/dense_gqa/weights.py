"""Seeded weights of a dense GQA transformer: its leaves, their shapes and
their integer codes (``bench/weights.py`` makes the codes).

Everything here is the benchmark's own: it imports nothing of the program.
The family's ``program.py`` packs these codes into the program's serving
layout, and its ``reference.py`` reads the same codes.

The token embedding is an 8-bit code times 2^-11, exact in bf16 (std about
0.018). Rows of the vocabulary padded up to a multiple of 256 are zero, as
in a converted checkpoint.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as Wt

EMBED_STEP = 2.0 ** -11
#: leaf ids: one random stream per tensor kind, its index in this tuple
LEAVES = ("wq", "wk", "wv", "wo", "gate", "up", "down", "embed", "head")


def leaf_key(key: jax.Array, leaf: str, layer: int | jax.Array = 0) -> jax.Array:
    return Wt.leaf_key(key, LEAVES.index(leaf), layer)


def linear_shapes(c: dict) -> dict:
    """(d_out, d_in) of each per-layer linear of config ``c``."""
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv, ff = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd, c["intermediate_size"]
    return {"wq": (q, d), "wk": (kv, d), "wv": (kv, d), "wo": (d, q),
            "gate": (ff, d), "up": (ff, d), "down": (d, ff)}


def layer_codes(key: jax.Array, c: dict, layer, bits: dict) -> dict:
    """Codes of every linear of one layer; ``bits`` maps leaf -> weight bits."""
    return {name: Wt.codes(leaf_key(key, name, layer), shape, bits[name])
            for name, shape in linear_shapes(c).items()}


def embed_table(key: jax.Array, c: dict) -> jax.Array:
    vp, v, d = Wt.vocab_padded(c["vocab_size"]), c["vocab_size"], c["hidden_size"]
    t = Wt.codes(leaf_key(key, "embed"), (vp, d), 8).astype(jnp.float32) * EMBED_STEP
    return jnp.where(jnp.arange(vp)[:, None] < v, t, 0.0).astype(jnp.bfloat16)


def head_codes(key: jax.Array, c: dict, bits: int) -> jax.Array:
    vp, v, d = Wt.vocab_padded(c["vocab_size"]), c["vocab_size"], c["hidden_size"]
    q = Wt.codes(leaf_key(key, "head"), (vp, d), bits)
    return jnp.where(jnp.arange(vp)[:, None] < v, q, 0).astype(jnp.int8)

"""Operations and bytes of a dense GQA transformer, from shapes alone.

Part of the benchmark's yardstick: a change to the program cannot change
these. Counts follow the configuration file's sizes and precisions.

  * ``token_flops``: model operations of one token (two per multiply-add):
    every linear of every layer, the LM head where the token gets logits,
    and attention over its context (QK^T and PV; the window caps it).
  * ``paged_attn_bytes``: the KV pages one decode lane must read for its
    context: whole pages, K and V, codes plus f32 scales per (token, head).
"""

from __future__ import annotations

from bench import weights as Wt
from bench.families.dense_gqa import weights as FW


def linear_ops(c: dict) -> int:
    """Operations of every per-layer linear of all layers, one token."""
    per = sum(n * k for n, k in FW.linear_shapes(c).values())
    return 2 * per * c["num_hidden_layers"]


def head_ops(c: dict) -> int:
    return 2 * Wt.vocab_padded(c["vocab_size"]) * c["hidden_size"]


def attn_ops(c: dict, ctx: int) -> int:
    """QK^T and PV of one query token over ``ctx`` keys, all layers."""
    w = c.get("sliding_window")
    n = min(ctx, w) if w else ctx
    return 4 * c["num_hidden_layers"] * c["num_attention_heads"] * c["head_dim"] * n


def attn_ops_range(c: dict, lo: int, hi: int) -> int:
    """Attention operations of the query tokens at contexts lo..hi (one
    token each, inclusive): a prefill chunk, all layers."""
    w = c.get("sliding_window") or hi
    a, b = lo, min(hi, w)  # contexts below the window count in full
    full = (b - a + 1) * (a + b) // 2 if b >= a else 0
    capped = (hi - max(lo, w + 1) + 1) * w if hi > w else 0
    return 4 * c["num_hidden_layers"] * c["num_attention_heads"] * c["head_dim"] * (full + capped)


def token_flops(c: dict, ctx: int, head: bool) -> int:
    return linear_ops(c) + (head_ops(c) if head else 0) + attn_ops(c, ctx)


def kv_bytes_per_token(c: dict) -> int:
    """Stored K and V bytes of one token in every layer: codes + scales."""
    p = c["precision"]
    per_head = c["head_dim"] * p["kv_bits"] // 8 + 4
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * per_head


def paged_attn_bytes(c: dict, ctx: int, page_size: int) -> int:
    """KV bytes one decode lane reads in one step (all layers): the whole
    pages that hold the keys inside its window."""
    w = c.get("sliding_window")
    lo = max(0, ctx - w) if w else 0
    pages = -(-ctx // page_size) - lo // page_size
    return pages * page_size * kv_bytes_per_token(c)

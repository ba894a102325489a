"""Plain reference of a dense GQA transformer under a stated precision.

Straightforward ``jax.numpy``; it imports nothing of the program and reads
nothing the program made. Weights are regenerated from the seed by the
family's ``weights.py``, layer by layer inside a scan, so the reference
holds one layer's codes at a time.

What the configuration states, and this file computes (the ``precision``
block of ``bench/configs/<config>.json``):

  * the residual stream, every linear's output and every attention input
    are bf16 (``activation_dtype``);
  * a linear quantizes its input to signed ``act_bits`` codes against the
    fixed clip ``act_clip`` (step clip / 2^(bits-1), round half to even),
    multiplies by the weight's integer codes with exact integer
    accumulation, and scales the sum by ``step * eps_w`` in f32;
  * keys and values are stored as ``kv_bits`` codes with one f32 scale per
    (token, head) (absmax / (2^(bits-1) - 1)) and read back as bf16;
  * norms, RoPE angles, scores and softmax in f32; the RMS norm's eps and
    the RoPE base as the configuration states (``rms_norm_eps``, ``rope_theta``);
  * the LM head's logits are kept in f32 here.

``kv_bits`` and ``act_bits`` may be set lower than the configuration's: that
is the control (``bench/control.py``), the nearest precision below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights as Wt
from bench.families.dense_gqa import weights as FW

#: the keys of a configuration file the reference reads
MODEL_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "vocab_size", "sliding_window", "rope_theta", "rms_norm_eps")

Q_BLOCK = 512  # query rows per attention block
HEAD_BLOCK = 512  # positions per LM-head block


def rms_norm(x, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(jnp.bfloat16)


def qlinear(x, codes, eps_w, act_bits: int, act_clip: float):
    """x (..., d_in) bf16 -> (..., d_out) bf16 through integer codes."""
    half = 1 << (act_bits - 1)
    step = jnp.float32(act_clip) / half
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / step), -half, half - 1)
    acc = jax.lax.dot_general(q.astype(jnp.int8), codes,
                              (((q.ndim - 1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * (step * eps_w)).astype(jnp.bfloat16)


def rope(x, pos, theta: float):
    """x (S, H, D) bf16, half rotation; angles in f32, rotation in bf16."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv
    c = jnp.cos(ang)[:, None, :].astype(x.dtype)
    s = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def kv_roundtrip(x, bits: int):
    """Store (S, H, D) as ``bits`` codes + per-(token, head) scales; read
    back as bf16."""
    half = 1 << (bits - 1)
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True), 1e-6) / (half - 1)
    q = jnp.clip(jnp.round(xf / scale), -half, half - 1)
    return (q * scale).astype(jnp.bfloat16)


def attention(q, k, v, window):
    """Causal GQA over the whole sequence, in query blocks.
    q (S, Hq, D), k/v (S, Hkv, D) bf16 -> (S, Hq * D) bf16."""
    S, Hq, D = q.shape
    g = Hq // k.shape[1]
    kf = jnp.repeat(k, g, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, g, axis=1).astype(jnp.float32)
    nb = S // Q_BLOCK
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK).astype(jnp.float32)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, kf,
                       precision=jax.lax.Precision.HIGHEST) / (D ** 0.5)
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.where(ok[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, vf, precision=jax.lax.Precision.HIGHEST)
        return o.astype(jnp.bfloat16)

    out = jax.lax.map(block, jnp.arange(nb))  # (nb, Q_BLOCK, Hq, D)
    return out.reshape(S, Hq * D)


def _layer(x, key, layer, c: dict, prec: dict, kv_bits: int, act_bits: int):
    S = x.shape[0]
    H, Hkv, D = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    wb = prec["weight_bits"]
    cl = prec["act_clip"]
    w = FW.layer_codes(key, c, layer, wb)
    eps = {n: Wt.eps_w(s[1], wb[n]) for n, s in FW.linear_shapes(c).items()}
    lin = lambda h, n: qlinear(h, w[n], eps[n], act_bits, cl)  # noqa: E731
    pos = jnp.arange(S)
    h = rms_norm(x, c["rms_norm_eps"])
    q = rope(lin(h, "wq").reshape(S, H, D), pos, c["rope_theta"])
    k = rope(lin(h, "wk").reshape(S, Hkv, D), pos, c["rope_theta"])
    v = lin(h, "wv").reshape(S, Hkv, D)
    a = attention(q, kv_roundtrip(k, kv_bits), kv_roundtrip(v, kv_bits),
                  c.get("sliding_window"))
    x = x + lin(a, "wo")
    h = rms_norm(x, c["rms_norm_eps"])
    m = lin(jax.nn.silu(lin(h, "gate")) * lin(h, "up"), "down")
    return (x + m).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("c_items", "prec_items", "kv_bits", "act_bits"))
def _hidden(key, tokens, *, c_items, prec_items, kv_bits, act_bits):
    c, prec = _undict(c_items), _undict(prec_items)
    x = FW.embed_table(key, c)[tokens]

    def body(x, layer):
        return _layer(x, key, layer, c, prec, kv_bits, act_bits), None

    x, _ = jax.lax.scan(body, x, jnp.arange(c["num_hidden_layers"]))
    return rms_norm(x, c["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("c_items", "prec_items", "act_bits"))
def _head(key, h, targets, *, c_items, prec_items, act_bits):
    """Per position: the largest logit, the logit of ``targets``, the argmax."""
    c, prec = _undict(c_items), _undict(prec_items)
    wb = prec["weight_bits"]["head"]
    w = FW.head_codes(key, c, wb)
    eps = Wt.eps_w(c["hidden_size"], wb)
    nb = h.shape[0] // HEAD_BLOCK

    def block(i):
        hb = jax.lax.dynamic_slice_in_dim(h, i * HEAD_BLOCK, HEAD_BLOCK)
        tb = jax.lax.dynamic_slice_in_dim(targets, i * HEAD_BLOCK, HEAD_BLOCK)
        half = 1 << (act_bits - 1)
        step = jnp.float32(prec["act_clip"]) / half
        q = jnp.clip(jnp.round(hb.astype(jnp.float32) / step), -half, half - 1)
        acc = jax.lax.dot_general(q.astype(jnp.int8), w, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        lg = acc.astype(jnp.float32) * (step * eps)
        at = jnp.take_along_axis(lg, tb[:, None], axis=1)[:, 0]
        return jnp.max(lg, axis=1), at, jnp.argmax(lg, axis=1).astype(jnp.int32)

    mx, at, am = jax.lax.map(block, jnp.arange(nb))
    return mx.reshape(-1), at.reshape(-1), am.reshape(-1)


def model_view(c: dict) -> dict:
    return {k: c.get(k) for k in MODEL_KEYS}


def _items(d: dict) -> tuple:
    """A hashable, static view of a (nested) config dict."""
    return tuple(sorted((k, _items(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))


def _undict(items: tuple) -> dict:
    return {k: _undict(v) if isinstance(v, tuple) and v and isinstance(v[0], tuple)
            else v for k, v in items}


def padded_len(n: int) -> int:
    """Sequences are padded to whole blocks of 1024 (few compiled shapes)."""
    return -(-n // 1024) * 1024


def scores(seed: int, c: dict, prec: dict, seq, targets, *,
           kv_bits: int | None = None, act_bits: int | None = None):
    """Run the reference over ``seq`` (a token list); at every position p,
    return (largest logit, logit of targets[p], argmax), each as a numpy
    vector of len(seq). Padding past len(seq) is causally invisible."""
    import numpy as np

    kv_bits = prec["kv_bits"] if kv_bits is None else kv_bits
    act_bits = prec["act_bits"] if act_bits is None else act_bits
    n = len(seq)
    S = padded_len(n)
    tok = np.zeros(S, np.int32)
    tok[:n] = seq
    tg = np.zeros(S, np.int32)
    tg[:n] = targets
    key = Wt.seed_key(seed)
    ci, pi = _items(c), _items(prec)
    h = _hidden(key, jnp.asarray(tok), c_items=ci, prec_items=pi,
                kv_bits=kv_bits, act_bits=act_bits)
    mx, at, am = _head(key, h, jnp.asarray(tg), c_items=ci, prec_items=pi,
                       act_bits=act_bits)
    return (np.asarray(mx)[:n], np.asarray(at)[:n], np.asarray(am)[:n])

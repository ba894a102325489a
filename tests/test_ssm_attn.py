"""Correctness of the sequence mixers: chunked linear attention (Mamba2 SSD
/ RWKV6 GLA core) vs the exact per-token recurrence, flash attention vs
naive softmax attention, prefill-vs-decode cache consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models import ssm
from repro.models.attention import flash_attention

jax.config.update("jax_platform_name", "cpu")


def naive_linear_attn(r, k, v, log_w, mode, u=None):
    """Exact per-token recurrence (the definition)."""
    B, S, H, dk = r.shape
    dv = v.shape[-1]
    state = np.zeros((B, H, dk, dv), np.float64)
    out = np.zeros((B, S, H, dv), np.float64)
    r, k, v = np.float64(r), np.float64(k), np.float64(v)
    w = np.exp(np.clip(np.float64(log_w), ssm.LOGW_MIN, 0.0))
    if w.shape[-1] == 1:
        w = np.broadcast_to(w, r.shape)
    for t in range(S):
        kv = np.einsum("bhd,bhe->bhde", k[:, t], v[:, t])
        if mode == "ssd":
            state = state * w[:, t][..., None] + kv
            out[:, t] = np.einsum("bhd,bhde->bhe", r[:, t], state)
        else:
            bonus = np.einsum("bhd,hd,bhd->bh", r[:, t], np.float64(u), k[:, t])
            out[:, t] = (np.einsum("bhd,bhde->bhe", r[:, t], state)
                         + bonus[..., None] * v[:, t])
            state = state * w[:, t][..., None] + kv
    return out, state


@pytest.mark.parametrize("mode,scalar_decay", [("ssd", True), ("rwkv", False)])
@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_chunked_linear_attn_matches_recurrence(mode, scalar_decay, chunk):
    rng = np.random.RandomState(0)
    B, S, H, dk, dv = 2, 48, 3, 8, 8
    r = rng.randn(B, S, H, dk).astype(np.float32)
    k = rng.randn(B, S, H, dk).astype(np.float32)
    v = rng.randn(B, S, H, dv).astype(np.float32)
    wdim = 1 if scalar_decay else dk
    log_w = -np.abs(rng.randn(B, S, H, wdim)).astype(np.float32) * 0.5
    u = np.abs(rng.randn(H, dk)).astype(np.float32) if mode == "rwkv" else None
    want, want_state = naive_linear_attn(r, k, v, log_w, mode, u)
    got, got_state = ssm.chunked_linear_attn(
        jnp.asarray(r), jnp.asarray(k), jnp.asarray(v), jnp.asarray(log_w),
        mode=mode, u=None if u is None else jnp.asarray(u), chunk=chunk)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_state, np.float64), want_state,
                               rtol=2e-4, atol=2e-4)


def test_chunked_with_initial_state_equals_split_sequence():
    """prefill(x[:32]) then prefill(x[32:], state) == prefill(x) — the
    chunked core composes across calls (decode/prefill consistency)."""
    rng = np.random.RandomState(1)
    B, S, H, dk = 1, 32, 2, 8
    mk = lambda: rng.randn(B, S, H, dk).astype(np.float32)
    r, k, v = mk(), mk(), mk()
    log_w = -np.abs(rng.randn(B, S, H, 1)).astype(np.float32)
    full, state_full = ssm.chunked_linear_attn(
        jnp.asarray(r), jnp.asarray(k), jnp.asarray(v), jnp.asarray(log_w),
        mode="ssd", chunk=8)
    h = S // 2
    a, st = ssm.chunked_linear_attn(
        jnp.asarray(r[:, :h]), jnp.asarray(k[:, :h]), jnp.asarray(v[:, :h]),
        jnp.asarray(log_w[:, :h]), mode="ssd", chunk=8)
    b, st2 = ssm.chunked_linear_attn(
        jnp.asarray(r[:, h:]), jnp.asarray(k[:, h:]), jnp.asarray(v[:, h:]),
        jnp.asarray(log_w[:, h:]), mode="ssd", chunk=8, initial_state=st)
    np.testing.assert_allclose(np.concatenate([a, b], 1), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(state_full),
                               rtol=1e-4, atol=1e-4)


def test_decode_step_continues_chunked_prefill():
    """linear_attn_step after a chunked prefill == one longer chunked run."""
    rng = np.random.RandomState(2)
    B, S, H, dk = 1, 17, 2, 8
    r = rng.randn(B, S, H, dk).astype(np.float32)
    k = rng.randn(B, S, H, dk).astype(np.float32)
    v = rng.randn(B, S, H, dk).astype(np.float32)
    log_w = -np.abs(rng.randn(B, S, H, dk)).astype(np.float32)
    u = np.abs(rng.randn(H, dk)).astype(np.float32)
    full, _ = ssm.chunked_linear_attn(
        jnp.asarray(r), jnp.asarray(k), jnp.asarray(v), jnp.asarray(log_w),
        mode="rwkv", u=jnp.asarray(u), chunk=4)
    pre, st = ssm.chunked_linear_attn(
        jnp.asarray(r[:, :-1]), jnp.asarray(k[:, :-1]), jnp.asarray(v[:, :-1]),
        jnp.asarray(log_w[:, :-1]), mode="rwkv", u=jnp.asarray(u), chunk=4)
    o, _ = ssm.linear_attn_step(
        jnp.asarray(r[:, -1]), jnp.asarray(k[:, -1]), jnp.asarray(v[:, -1]),
        jnp.asarray(log_w[:, -1]), st, mode="rwkv", u=jnp.asarray(u))
    np.testing.assert_allclose(np.asarray(o), np.asarray(full[:, -1]),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- flash attention


def naive_attention(q, k, v, causal=True, window=None):
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    groups = Hq // Hkv
    kk = np.repeat(k, groups, axis=2) if groups > 1 else k
    vv = np.repeat(v, groups, axis=2) if groups > 1 else v
    s = np.einsum("bqhd,bkhd->bhqk", np.float64(q), np.float64(kk)) / np.sqrt(D)
    qi = np.arange(Sq)[:, None]
    ki = np.arange(k.shape[1])[None, :]
    mask = np.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= (qi - ki) < window
    s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, np.float64(vv))


@pytest.mark.parametrize("causal,window,gqa", [
    (True, None, 1), (True, None, 3), (False, None, 1), (True, 7, 1),
])
def test_flash_attention_matches_naive(causal, window, gqa):
    rng = np.random.RandomState(3)
    B, Sq, Hkv, D = 2, 37, 2, 16
    q = rng.randn(B, Sq, Hkv * gqa, D).astype(np.float32)
    k = rng.randn(B, Sq, Hkv, D).astype(np.float32)
    v = rng.randn(B, Sq, Hkv, D).astype(np.float32)
    want = naive_attention(q, k, v, causal=causal, window=window)
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, window=window, q_chunk=8, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=2e-3, atol=2e-3)


def test_flash_attention_different_kv_dim():
    """MLA: k head_dim != v head_dim."""
    rng = np.random.RandomState(4)
    q = rng.randn(1, 12, 2, 24).astype(np.float32)
    k = rng.randn(1, 12, 2, 24).astype(np.float32)
    v = rng.randn(1, 12, 2, 16).astype(np.float32)
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=True, q_chunk=4, kv_chunk=4)
    want = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal,window,gqa,bq,bk", [
    (True, None, 1, 8, 8),
    (True, None, 2, 8, 16),   # GQA via index maps
    (False, None, 1, 16, 8),
    (True, 10, 1, 8, 8),      # sliding window predication
])
def test_pallas_flash_kernel_matches_naive(causal, window, gqa, bq, bk):
    """The Pallas flash kernel (grid-predicated causal/window schedule) ==
    naive attention; (B, H, S, D) layout."""
    from repro.kernels.flash import flash_mha_pallas

    rng = np.random.RandomState(7)
    B, Sq, Hkv, D = 2, 35, 2, 16
    q = rng.randn(B, Sq, Hkv * gqa, D).astype(np.float32)
    k = rng.randn(B, Sq, Hkv, D).astype(np.float32)
    v = rng.randn(B, Sq, Hkv, D).astype(np.float32)
    want = naive_attention(q, k, v, causal=causal, window=window)
    got = flash_mha_pallas(
        jnp.asarray(q.transpose(0, 2, 1, 3)), jnp.asarray(k.transpose(0, 2, 1, 3)),
        jnp.asarray(v.transpose(0, 2, 1, 3)),
        causal=causal, window=window, bq=bq, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got).transpose(0, 2, 1, 3), want,
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("bits,gqa,pos", [(8, 1, 30), (8, 2, 12), (4, 1, 31)])
def test_pallas_quantized_kv_decode(bits, gqa, pos):
    """Decode attention over the int8/int4 cache with fused in-kernel
    dequant == dequantize-then-attend oracle."""
    from repro.kernels.qkv_decode import qkv_decode_pallas, qkv_decode_ref
    from repro.models.attention import kv_quantize

    rng = np.random.RandomState(11)
    B, S, Hkv, D = 2, 32, 2, 16
    k = jnp.asarray(rng.randn(B, S, Hkv, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, Hkv, D).astype(np.float32))
    k_q, k_s = kv_quantize(k, bits)
    v_q, v_s = kv_quantize(v, bits)
    q = jnp.asarray(rng.randn(B, Hkv * gqa, D).astype(np.float32))
    want = qkv_decode_ref(q, k_q, k_s, v_q, v_s, pos, bits=bits)
    got = qkv_decode_pallas(q, k_q, k_s, v_q, v_s, jnp.int32(pos),
                            bits=bits, bs=8, interpret=True)
    # oracle dequantizes via bf16 (the model path); kernel dequant is f32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=5e-3)


@given(st.integers(1, 4), st.integers(1, 50), st.booleans())
@settings(max_examples=10, deadline=None)
def test_flash_attention_property(b, s, causal):
    rng = np.random.RandomState(s)
    q = rng.randn(b, s, 2, 8).astype(np.float32)
    k = rng.randn(b, s, 2, 8).astype(np.float32)
    v = rng.randn(b, s, 2, 8).astype(np.float32)
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, q_chunk=16, kv_chunk=16)
    want = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=3e-3, atol=3e-3)

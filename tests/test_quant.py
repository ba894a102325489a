"""Property tests for the quantization core (paper Sec. 2.1 contract)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import pack as P
from repro.core import quant as Q

jax.config.update("jax_platform_name", "cpu")

BITS = [2, 4, 8]


# ---------------------------------------------------------------- pack/unpack


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("signed", [False, True])
def test_pack_unpack_roundtrip_exhaustive(bits, signed):
    """Every representable value survives pack -> unpack (bins ∘ bext = id)."""
    spec = Q.QuantSpec(bits, signed)
    dt = np.int8 if signed else np.uint8
    vals = np.arange(spec.qmin, spec.qmax + 1, dtype=dt)
    r = P.pack_ratio(bits)
    reps = -len(vals) % r
    q = np.concatenate([vals, vals[:reps]]).reshape(1, -1)
    packed = P.pack(jnp.asarray(q), bits)
    assert packed.shape[-1] == q.shape[-1] // r
    out = P.unpack(packed, bits, signed=signed)
    np.testing.assert_array_equal(np.asarray(out), q)


@given(
    bits=st.sampled_from([2, 4]),
    signed=st.booleans(),
    data=st.data(),
)
@settings(max_examples=50, deadline=None)
def test_pack_unpack_roundtrip_random(bits, signed, data):
    spec = Q.QuantSpec(bits, signed)
    r = P.pack_ratio(bits)
    rows = data.draw(st.integers(1, 5))
    cols = data.draw(st.integers(1, 16)) * r
    q = data.draw(
        st.lists(
            st.integers(spec.qmin, spec.qmax), min_size=rows * cols, max_size=rows * cols
        )
    )
    q = np.array(q, dtype=np.int8 if signed else np.uint8).reshape(rows, cols)
    out = P.unpack(P.pack(jnp.asarray(q), bits), bits, signed=signed)
    np.testing.assert_array_equal(np.asarray(out), q)
    # numpy twins agree with the jax path
    np.testing.assert_array_equal(P.pack_np(q, bits), np.asarray(P.pack(jnp.asarray(q), bits)))
    np.testing.assert_array_equal(P.unpack_np(P.pack_np(q, bits), bits, signed=signed), q)


# -------------------------------------------------------------- quant bounds


@given(
    bits=st.sampled_from(BITS),
    beta=st.floats(0.1, 100.0),
    data=st.data(),
)
@settings(max_examples=40, deadline=None)
def test_act_quant_dequant_error_bound(bits, beta, data):
    """|x - deq(q(x))| <= eps/2 for in-range x (round-to-nearest grid)."""
    spec = Q.ACT_SPECS[bits]
    eps = spec.scale_from_range(beta)
    n = data.draw(st.integers(1, 64))
    x = np.array(
        data.draw(st.lists(st.floats(0.0, beta * 0.999), min_size=n, max_size=n)),
        dtype=np.float32,
    )
    x = np.minimum(x, (spec.qmax) * eps + eps * 0.499)  # representable range
    q = Q.quantize(jnp.asarray(x), jnp.float32(eps), spec)
    xd = Q.dequantize(q, jnp.float32(eps), spec)
    assert np.all(np.abs(np.asarray(xd) - x) <= eps * 0.5 + 1e-6)


# ------------------------------------------------- requant: ladder == Eq. 3


@given(
    y_bits=st.sampled_from(BITS),
    kappa=st.floats(0.25, 4.0),
    lam=st.floats(-100.0, 100.0),
    log2r=st.floats(-12.0, -2.0),
    data=st.data(),
)
@settings(max_examples=60, deadline=None)
def test_ladder_matches_eq3_float64_oracle(y_bits, kappa, lam, log2r, data):
    """INT(y) = sum_i [phi >= T_i]  ==  clip(floor((kappa phi + lam) eps ratio))."""
    r = float(2.0**log2r)
    params = Q.make_requant_params(y_bits=y_bits, kappa=kappa, lam=lam, eps_phi=r, eps_y=1.0)
    n = data.draw(st.integers(1, 128))
    phi = np.array(
        data.draw(st.lists(st.integers(-(2**20), 2**20), min_size=n, max_size=n)),
        dtype=np.int32,
    )
    oracle = np.clip(
        np.floor((np.float64(kappa) * phi + np.float64(lam)) * np.float64(r)),
        0,
        2**y_bits - 1,
    ).astype(np.uint8)
    got = Q.requant_ladder(jnp.asarray(phi), jnp.asarray(params.thresholds))
    np.testing.assert_array_equal(np.asarray(got), oracle)


@given(
    shift=st.integers(2, 12),
    lam=st.floats(-50.0, 50.0),
    data=st.data(),
)
@settings(max_examples=40, deadline=None)
def test_shift_path_matches_ladder_when_pow2(shift, lam, data):
    """The paper's 8-bit shift-and-clamp equals the ladder when the requant
    scale is an exact power of two AND lambda lies on the 2^-shift grid (the
    shift path quantizes the bias onto that grid — a documented approximation
    for off-grid lambda)."""
    r = 2.0**-shift
    lam = round(lam * 2**shift) / 2**shift  # grid-representable bias
    params = Q.make_requant_params(y_bits=8, kappa=1.0, lam=lam, eps_phi=r, eps_y=1.0)
    assert params.shift == shift
    n = data.draw(st.integers(1, 128))
    phi = np.array(
        data.draw(st.lists(st.integers(-(2**24), 2**24), min_size=n, max_size=n)),
        dtype=np.int32,
    )
    a = Q.requant_shift(jnp.asarray(phi), params.shift, params.bias, 8)
    b = Q.requant_ladder(jnp.asarray(phi), jnp.asarray(params.thresholds))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_threshold_counts_match_paper():
    """2^N - 1 thresholds: 3 for 2-bit, 15 for 4-bit (4-bit needs 2x the
    comparisons of 2-bit at ladder granularity — paper Tab. 1 rationale is
    binary search depth; vectorized compare count is 15 vs 3)."""
    for b, n in [(2, 3), (4, 15), (8, 255)]:
        p = Q.make_requant_params(y_bits=b, eps_phi=2**-8, eps_y=1.0)
        assert p.thresholds.shape == (n,)
        assert np.all(np.diff(p.thresholds) >= 0)


# ------------------------------------------------------------------ QAT / STE


def test_fake_quant_act_ste_gradients():
    beta = jnp.float32(4.0)
    x = jnp.array([-1.0, 0.5, 2.0, 5.0], jnp.float32)

    def f(x, beta):
        return jnp.sum(Q.fake_quant_act(x, beta, 4))

    gx, gb = jax.grad(f, argnums=(0, 1))(x, beta)
    np.testing.assert_array_equal(np.asarray(gx), np.array([0.0, 1.0, 1.0, 0.0], np.float32))
    assert float(gb) == 1.0  # PACT: only the x > beta element contributes


def test_fake_quant_weight_ste_and_levels():
    w = jnp.array([-1.0, -0.3, 0.2, 0.9], jnp.float32)
    wq = Q.fake_quant_weight(w, 2)
    # 2-bit signed grid: {-2, -1, 0, 1} * eps with eps = max|w| / 2
    eps = 1.0 / 2
    np.testing.assert_allclose(np.asarray(wq) / eps, np.round(np.asarray(wq) / eps), atol=1e-6)
    g = jax.grad(lambda w: jnp.sum(Q.fake_quant_weight(w, 2)))(w)
    np.testing.assert_array_equal(np.asarray(g), np.ones(4, np.float32))


def test_quantize_weight_integer_range():
    w = jnp.asarray(np.random.RandomState(0).randn(32, 16).astype(np.float32))
    for bits in BITS:
        q, eps = Q.quantize_weight(w, bits)
        spec = Q.WGT_SPECS[bits]
        assert q.dtype == jnp.int8
        assert int(jnp.min(q)) >= spec.qmin and int(jnp.max(q)) <= spec.qmax
        # eps/2 everywhere except the +max element, which clips to qmax (err = eps)
        err = np.abs(np.asarray(q) * float(eps) - np.asarray(w)).max()
        assert err <= float(eps) + 1e-6

"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e
chip, at the published widths of internlm2-1.8b (d_model 2048, d_ff 8192,
16 query / 8 KV heads of 128). Nothing runs: each test lowers a kernel with
Mosaic, the chip's own kernel compiler, which refuses what interpret mode
accepts — a block that is not (8, 128)-aligned, an int8 arithmetic shift,
a lane reshape.

The kernels go through ``ops`` with the decisions a chip would make:
compiled (not interpreted) Pallas, and the static tiles (the tuned-tile
files hold no ``tpu/`` entries). The topology is described inside a fixture,
so importing this file loads no TPU library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.core import quant as Q
from repro.kernels import ops, tuning

D_MODEL, D_FF = 2048, 8192
HQ, HKV, HEAD = 16, 8, 128
SLOTS, CHUNK = 8, 256  # decode batch; mixed-step rows per slot
PAGE, S_MAX = 16, 1088
N_BLOCKS = S_MAX // PAGE
N_PAGES = SLOTS * N_BLOCKS + 1


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def as_chip(monkeypatch):
    """ops and the tile cache decide as they would on a TPU."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(tuning, "backend", lambda: "tpu")


def _compile(chip, fn, *shapes):
    args = [None if s is None else jax.ShapeDtypeStruct(s[0], s[1], sharding=chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the kernel itself, not a fallback


@pytest.mark.parametrize("w_bits", [8, 4, 2])
@pytest.mark.parametrize("m", [SLOTS, SLOTS * CHUNK])
@pytest.mark.parametrize("n,k", [(D_FF, D_MODEL), (D_MODEL, D_FF)])
def test_mpmm_u8_f32_out(chip, as_chip, w_bits, m, n, k):
    """The integer linear of every mixed_paper layer: signed 8-bit
    activations, 8- or 4-bit weights, f32 out (decode and mixed-step M);
    and 2-bit weights, which unpack like 4-bit ones."""
    rq = Q.make_requant_params(y_bits=8, eps_phi=2**-8, eps_y=1.0)
    fn = functools.partial(ops.mpmm, rq=rq, x_bits=8, w_bits=w_bits, y_bits=8,
                           x_signed=True, out_kind="f32", out_scale=0.5,
                           impl="pallas")
    _compile(chip, fn, ((m, k), jnp.int8), ((n, k * w_bits // 8), jnp.int8))


@pytest.mark.parametrize("y_bits", [8, 4, 2])
def test_mpmm_packed_out(chip, as_chip, y_bits):
    """The paper's layer-to-layer path: mpmm with its fused QntPack writes
    packed y_bits activations (a 2-bit output block is bn/4 lanes wide)."""
    rq = Q.make_requant_params(y_bits=y_bits, eps_phi=2**-8, eps_y=1.0)
    fn = functools.partial(ops.mpmm, rq=rq, x_bits=8, w_bits=4, y_bits=y_bits,
                           out_kind="packed", impl="pallas")
    _compile(chip, fn, ((SLOTS * CHUNK, D_MODEL), jnp.int8),
             ((D_FF, D_MODEL // 2), jnp.int8))


@pytest.mark.parametrize("y_bits", [8, 4, 2])
def test_qntpack(chip, as_chip, y_bits):
    rq = Q.make_requant_params(y_bits=y_bits, eps_phi=2**-8, eps_y=1.0)
    fn = functools.partial(ops.qntpack, rq=rq, y_bits=y_bits, impl="pallas")
    _compile(chip, fn, ((SLOTS * CHUNK, D_FF), jnp.int32))


@pytest.mark.parametrize("w_bits", [8, 4, 2])
def test_wdqmm(chip, as_chip, w_bits):
    fn = functools.partial(ops.wdqmm, eps_w=0.02, w_bits=w_bits, impl="pallas")
    _compile(chip, fn, ((SLOTS, D_MODEL), jnp.bfloat16),
             ((D_FF, D_MODEL * w_bits // 8), jnp.int8))


def _kv(bits):
    dt = jnp.bfloat16 if bits is None else jnp.int8
    r = 2 if bits == 4 else 1
    kv = ((N_PAGES, PAGE, HKV, HEAD // r), dt)
    sc = None if bits is None else ((N_PAGES, PAGE, HKV), jnp.float32)
    return kv, sc


@pytest.mark.parametrize("bits", [8, 4, None])
def test_paged_attn(chip, as_chip, bits):
    kv, sc = _kv(bits)
    fn = functools.partial(ops.paged_attn, bits=bits, impl="pallas")
    _compile(chip, lambda q, k, ks, v, vs, pos, bt: fn(q, k, ks, v, vs, pos,
                                                       block_table=bt),
             ((SLOTS, HQ, HEAD), jnp.float32), kv, sc, kv, sc,
             ((SLOTS,), jnp.int32), ((SLOTS, N_BLOCKS), jnp.int32))


@pytest.mark.parametrize("bits", [8, 4])
def test_paged_mla_attn(chip, as_chip, bits):
    """Absorbed-MLA decode (deepseek-v3 latent widths: kv_lora 512, rope 64)
    over latent pages laid out like the GQA pool with one head."""
    C, dr = 512, 64
    r = 2 if bits == 4 else 1
    fn = functools.partial(ops.paged_mla_attn, bits=bits, scale=0.1,
                           impl="pallas")
    _compile(chip, lambda ql, qr, c, cs, rr, pos, bt: fn(ql, qr, c, cs, rr, pos,
                                                         block_table=bt),
             ((SLOTS, HQ, C), jnp.float32), ((SLOTS, HQ, dr), jnp.float32),
             ((N_PAGES, PAGE, 1, C // r), jnp.int8),
             ((N_PAGES, PAGE, 1), jnp.float32),
             ((N_PAGES, PAGE, 1, dr), jnp.bfloat16),
             ((SLOTS,), jnp.int32), ((SLOTS, N_BLOCKS), jnp.int32))


@pytest.mark.parametrize("leaf", [((HKV, HEAD), jnp.int8), ((HKV,), jnp.float32)],
                         ids=["kv8", "scales"])
def test_paged_gather(chip, as_chip, leaf):
    tail, dt = leaf
    _compile(chip, functools.partial(ops.paged_gather, impl="pallas"),
             ((N_PAGES, PAGE, *tail), dt), ((SLOTS, N_BLOCKS), jnp.int32))


@pytest.mark.parametrize("rows", [1, CHUNK], ids=["decode", "chunk"])
@pytest.mark.parametrize("leaf", [((HKV, HEAD), jnp.int8), ((HKV,), jnp.float32)],
                         ids=["kv8", "scales"])
def test_paged_scatter(chip, as_chip, leaf, rows):
    tail, dt = leaf
    _compile(chip, functools.partial(ops.paged_scatter, impl="pallas"),
             ((N_PAGES, PAGE, *tail), dt), ((SLOTS, rows, *tail), dt),
             ((SLOTS,), jnp.int32), ((SLOTS, N_BLOCKS), jnp.int32))


def test_flash_mha_prefill_block(chip, as_chip):
    fn = functools.partial(ops.flash_mha, causal=True, window=None, bq=512,
                           bk=512)
    _compile(chip, fn, ((1, HQ, 1024, HEAD), jnp.bfloat16),
             ((1, HKV, 1024, HEAD), jnp.bfloat16),
             ((1, HKV, 1024, HEAD), jnp.bfloat16))

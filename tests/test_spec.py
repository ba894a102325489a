"""Speculative-decoding tests (PR 10 acceptance surface).

Covers: the cache-manager ``truncate`` verb (dense row scrub; paged
page-release + partial-page scrub + pool conservation; the shared-page
guard), the ``SelfDraft`` re-quantization math (4-bit grid rescale folded
into ``eps_w``; identity aliasing when the target is already 4-bit), the
DraftPolicy resolution seam, spec x mixed exclusivity, ``spec/`` metrics,
and the acceptance criteria proper — accepted token streams bit-identical
to the non-speculative engine on slot/paged/prefix for greedy AND seeded
sampling, with both draft policies — plus the rollback churn property:
random accept/reject traffic (forced by a lossy-requantization policy)
with cancels mid-speculation preserves ``free + distinct live + scratch ==
n_pages`` after every step, and survivors stay bit-equal to a
non-speculative baseline.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import pack as P
from repro.core.policy import get_policy
from repro.models import model as M
from repro.serve import (
    DraftModel,
    PagedKVCache,
    SamplingParams,
    SelfDraft,
    ServeEngine,
    SlotCache,
    make_spec,
)
from repro.serve.spec import derive_w4_policy, requantize_params_w4

from hypothesis import given, settings
from hypothesis import strategies as st

jax.config.update("jax_platform_name", "cpu")

TINY = configs.reduced(configs.get_arch("internlm2-1.8b"))
POLICY = get_policy("w4a8")


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.key(3), TINY, POLICY, mode="serve")


MIXED = get_policy("mixed_paper")


@pytest.fixture(scope="module")
def params_mixed():
    return M.init_params(jax.random.key(3), TINY, MIXED, mode="serve")


def _prompts(n=4, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, TINY.vocab, size=rng.randint(3, 9)).astype(np.int32)
            for _ in range(n)]


def _fill_ones(cache):
    cache.caches = jax.tree.map(lambda a: jnp.ones_like(a), cache.caches)


def _rows(cache, slot_or_page):
    """Per-row nonzero mask of one slot stripe / pool page, OR'd across
    layers, leaves, and trailing (head/dim) axes."""
    out = None
    for a in jax.tree.leaves(cache.caches):
        x = np.asarray(a[:, slot_or_page])  # (L, rows, ...)
        m = (x.reshape(x.shape[0], x.shape[1], -1) != 0).any(axis=(0, 2))
        out = m if out is None else out | m
    return out


# --- the truncate verb ------------------------------------------------------


def test_truncate_slot_rewinds_and_scrubs():
    c = SlotCache(TINY, POLICY, 2, 16)
    c.acquire(10)
    c.advance(0, 8)
    _fill_ones(c)
    c.truncate(0, 3)
    assert int(c.pos[0]) == 5
    rows = _rows(c, 0)
    assert rows[:5].all() and not rows[5:8].any()  # tail zeroed, head intact
    assert _rows(c, 1).all()                       # neighbor untouched
    assert c.truncates == 1
    c.truncate(0, 0)                               # no-op
    assert int(c.pos[0]) == 5 and c.truncates == 1
    with pytest.raises(ValueError):
        c.truncate(0, 6)                           # below position 0


def test_truncate_paged_frees_pages_and_scrubs_partial():
    c = PagedKVCache(TINY, POLICY, 2, 16, page_size=4)
    c.acquire(12)
    c.prepare(0, 10)
    c.advance(0, 10)
    assert int(c._alloc[0]) == 3
    _fill_ones(c)
    tail_page = int(c.block_tables[0, 2])
    kept_page = int(c.block_tables[0, 1])
    c.truncate(0, 5)  # 10 -> 5: drop page 3 entirely, scrub offsets 1..3
    assert int(c.pos[0]) == 5 and int(c._alloc[0]) == 2
    assert tail_page in c._free and int(c._ref[tail_page]) == 0
    assert not _rows(c, tail_page).any()           # freed page zeroed
    kept = _rows(c, kept_page)
    assert kept[0] and not kept[1:].any()          # partial scrub in place
    assert _rows(c, int(c.block_tables[0, 0])).all()
    # pool conservation: free + distinct live + scratch == n_pages
    live = {int(p) for s in range(c.n_slots)
            for p in c.block_tables[s, : int(c._alloc[s])]} - {0}
    assert len(c._free) + len(live) + 1 == c.n_pages
    # reservation untouched: the slot can re-draw within its promise
    c.prepare(0, 7)
    assert int(c._alloc[0]) == 3
    with pytest.raises(ValueError):
        c.truncate(0, 99)


def test_truncate_refuses_shared_partial_page():
    c = PagedKVCache(TINY, POLICY, 2, 16, page_size=4)
    c.acquire(12)
    c.prepare(0, 6)
    c.advance(0, 6)
    c._retain_page(int(c.block_tables[0, 0]))  # a second reader appears
    with pytest.raises(RuntimeError, match="readers"):
        c.truncate(0, 3)  # would scrub offset 3 of the shared page


# --- the DraftPolicy seam ---------------------------------------------------


def test_make_spec_resolution():
    assert make_spec(None) is None
    assert make_spec("off") is None
    assert isinstance(make_spec("self4"), SelfDraft)
    assert isinstance(make_spec("draft"), DraftModel)
    inst = DraftModel()
    assert make_spec(inst) is inst
    with pytest.raises(KeyError):
        make_spec("nope")


def test_derive_w4_policy():
    pol = derive_w4_policy(MIXED)
    assert pol.name == "mixed_paper+self4"
    assert pol.kv_cache_bits == MIXED.kv_cache_bits
    assert pol.default.w_bits == 4
    assert pol.of("expert").w_bits == 4          # 2-bit experts widen to 4
    assert pol.of("router").w_bits is None       # routers stay BF16
    assert pol.of("attn_out").x_bits == MIXED.of("attn_out").x_bits


def test_requantize_rescales_grid_and_eps():
    wq8 = jnp.array([[-127, -64, 0, 64, 127, 1, -1, 100]], jnp.int8)
    tree = {"wo": {"w_packed": P.pack(wq8, 8), "eps_w": jnp.float32(0.5)}}
    out = requantize_params_w4(tree, MIXED)      # mixed_paper: attn_out is 8b
    wq4 = P.unpack(out["wo"]["w_packed"], 4, signed=True)
    expect = np.clip(np.round(np.asarray(wq8, np.float32) * 7 / 127), -7, 7)
    assert (np.asarray(wq4) == expect).all()
    assert np.isclose(float(out["wo"]["eps_w"]), 0.5 * 127 / 7)


def test_requantize_is_identity_at_4bit(params):
    draft = requantize_params_w4(params, POLICY)  # w4a8: already 4-bit

    def leaves(t):
        return {str(k): v for k, v in
                jax.tree_util.tree_flatten_with_path(t)[0]}

    a, b = leaves(params), leaves(draft)
    assert a.keys() == b.keys()
    assert all(a[k] is b[k] for k in a)           # zero extra weight memory


def test_spec_mixed_mutually_exclusive(params):
    with pytest.raises(ValueError, match="mutually exclusive"):
        ServeEngine(params, TINY, POLICY, n_slots=2, s_max=32, impl="jnp",
                    mixed=True, spec="self4")
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(params, TINY, POLICY, n_slots=2, s_max=32, impl="jnp",
                    spec="self4", spec_k=0)


# --- bit-exactness vs the non-speculative engine ----------------------------

_BASELINES: dict = {}


def _run(params, policy, spec, cache, temp, *, spec_k=3, max_new=8):
    kw = dict(n_slots=2, s_max=32, impl="jnp", cache=cache,
              spec=spec, spec_k=spec_k)
    if cache != "slot":
        kw["page_size"] = 4
    eng = ServeEngine(params, TINY, policy, **kw)
    hs = [eng.submit(p, SamplingParams(temperature=temp, top_k=8, top_p=0.9,
                                       seed=17 + i, max_new=max_new))
          for i, p in enumerate(_prompts())]
    eng.drain()
    return [h.result() for h in hs], eng


def _baseline(params, policy, cache, temp):
    key = (id(params), cache, temp)
    if key not in _BASELINES:
        _BASELINES[key] = _run(params, policy, None, cache, temp)[0]
    return _BASELINES[key]


@pytest.mark.parametrize("cache", ["slot", "paged", "prefix"])
@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_selfdraft_bitexact(params, cache, temp):
    out, eng = _run(params, POLICY, "self4", cache, temp)
    assert out == _baseline(params, POLICY, cache, temp)
    m = eng.metrics()
    # w4a8 self-draft is the identity: every proposal must be accepted
    assert m["spec/acceptance_rate"] == 1.0
    assert m["cache/truncates"] == 0


@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_selfdraft_bitexact_lossy_policy(params_mixed, temp):
    # mixed_paper's 8/2-bit layers round-trip LOSSILY through the 4-bit
    # grid: drafts genuinely diverge, rounds truncate, streams still match
    out, eng = _run(params_mixed, MIXED, "self4", "paged", temp)
    assert out == _baseline(params_mixed, MIXED, "paged", temp)
    m = eng.metrics()
    assert 0.0 < m["spec/acceptance_rate"] <= 1.0
    if temp == 0.0:
        # greedy re-samples argmax exactly, so the lossy drafts visibly
        # diverge; seeded sampling can tolerate the drift (same PRNG draw)
        assert m["spec/acceptance_rate"] < 1.0
        assert m["cache/truncates"] > 0


def test_draftmodel_bitexact(params):
    for temp in (0.0, 0.8):
        out, eng = _run(params, POLICY, DraftModel(), "paged", temp)
        assert out == _baseline(params, POLICY, "paged", temp)
        assert eng.metrics()["spec/policy"] == "draft"


def test_spec_metrics_namespace(params):
    out, eng = _run(params, POLICY, "self4", "slot", 0.0)
    m = eng.metrics()
    assert m["spec/enabled"] and m["spec/policy"] == "self4"
    assert m["spec/k"] == 3
    assert m["spec/rounds"] > 0
    assert m["spec/proposed"] >= m["spec/accepted"] > 0
    assert m["spec/accepted_len_count"] > 0
    assert m["spec/accepted_len_p50_s"] == 4.0  # k+1 every round (identity)
    off = ServeEngine(params, TINY, POLICY, n_slots=2, s_max=32, impl="jnp")
    mo = off.metrics()
    assert not mo["spec/enabled"] and mo["spec/policy"] == "off"
    assert mo["spec/k"] == 0 and mo["spec/rounds"] == 0


# --- rollback churn: pool conservation + survivor bit-equality --------------


def _assert_pool_conserved(cache):
    """free + (distinct live block-table/index pages) + scratch == n_pages,
    and no page is simultaneously free and mapped."""
    live = {int(p) for s in range(cache.n_slots)
            for p in cache.block_tables[s, : int(cache._alloc[s])]}
    if hasattr(cache, "_root"):
        def walk(node):
            for ch in node.children.values():
                live.add(ch.page)
                walk(ch)
        walk(cache._root)
    live -= {0}
    assert len(cache._free) + len(live) + 1 == cache.n_pages
    assert not live.intersection(cache._free)


@pytest.mark.parametrize("cache", ["paged", "prefix"])
@settings(max_examples=2, deadline=None)
@given(data=st.data())
def test_spec_churn_conserves_pool(params_mixed, cache, data):
    spec_k = data.draw(st.integers(2, 3), label="spec_k")
    rng = np.random.RandomState(data.draw(st.integers(0, 3), label="seed"))
    shared = rng.randint(1, TINY.vocab, size=8).astype(np.int32)
    prompts = [np.concatenate(
        [shared, rng.randint(1, TINY.vocab, size=2 + i)]).astype(np.int32)
        for i in range(4)]
    cancel = {data.draw(st.integers(0, 3), label="victim"):
              data.draw(st.integers(1, 3), label="after")}

    def engine(spec):
        return ServeEngine(params_mixed, TINY, MIXED, n_slots=2, s_max=32,
                           impl="jnp", cache=cache, page_size=4,
                           spec=spec, spec_k=spec_k)

    eng = engine("self4")
    handles = {i: eng.submit(p, SamplingParams(max_new=6))
               for i, p in enumerate(prompts)}
    cancelled = set()
    while True:
        more = eng.step()
        _assert_pool_conserved(eng.cache)
        for rid, after in cancel.items():
            h = handles[rid]
            # a round retires up to k+1 tokens at once, so the victim can
            # finish before the threshold check — skip the cancel then
            if (rid not in cancelled and not h.done
                    and len(h.request.out or []) >= after):
                h.cancel()  # mid-speculation: rows this round already wrote
                cancelled.add(rid)
                _assert_pool_conserved(eng.cache)
        if not more:
            break
    key = ("churn-base", id(params_mixed), cache, tuple(map(len, prompts)),
           int(shared[0]))
    if key not in _BASELINES:
        base = engine(None)
        bh = {i: base.submit(p, SamplingParams(max_new=6))
              for i, p in enumerate(prompts)}
        base.drain()
        _BASELINES[key] = {i: h.result() for i, h in bh.items()}
    for rid, h in handles.items():
        if rid in cancelled:
            assert h.status == "cancelled"
        else:
            assert h.status == "done"
            assert h.request.out == _BASELINES[key][rid]  # survivors exact
    _assert_pool_conserved(eng.cache)

"""Prefix-sharing cache tests (fast tier + slow sweep).

Covers the PR-4 acceptance surface: shared-prefix decode bit-exactness vs a
cold ``cache="paged"`` run (per attention family, per kv_cache_bits),
refcount safety (completing one of two sharers never zeroes or recycles
shared pages; pages recycle exactly when the last reader leaves), the
copy-on-write clone (kernel pair + divergence isolation), the S-1 match cap
(last prompt token always re-prefills so first-token logits exist), LRU
leaf eviction under pool pressure (never a page with live readers), the
prefill jitted-call reduction, namespaced ``cache/`` metrics, and the pool
conservation invariant (free + live + scratch == n_pages) under random
admit/advance/complete/evict churn (hypothesis property test) — now also
under random MID-DECODE ``cancel()`` calls through the lifecycle API: a
cancelled sharer decrefs (never zeroes) pages with live readers, the pool
stays conserved at every step, and surviving sharers' token streams are
bit-identical to an uncancelled baseline run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.policy import get_policy
from repro.kernels import paged_gather as PG
from repro.serve import PrefixCache, Request, ServeEngine

from hypothesis import given, settings
from hypothesis import strategies as st

jax.config.update("jax_platform_name", "cpu")

TINY = configs.reduced(configs.get_arch("internlm2-1.8b"))
POLICY = get_policy("w4a8")


@pytest.fixture(scope="module")
def params():
    return M_init()


def M_init():
    from repro.models import model as M
    return M.init_params(jax.random.key(3), TINY, POLICY, mode="serve")


def _shared_prefix_requests(cfg, *, shared_len=10, uniq_len=5, max_new=4,
                            seed=0):
    """A prefix-heavy stream: two sharers that diverge mid-page, one exact
    duplicate (full-match cap path), one unrelated cold prompt, and one
    shorter sharer (partial-page-only match)."""
    rng = np.random.RandomState(seed)
    shared = rng.randint(1, cfg.vocab, size=shared_len).astype(np.int32)
    u = [rng.randint(1, cfg.vocab, size=uniq_len).astype(np.int32)
         for _ in range(3)]
    prompts = [
        np.concatenate([shared, u[0]]),
        np.concatenate([shared, u[1]]),
        np.concatenate([shared, u[0]]),          # exact duplicate
        rng.randint(1, cfg.vocab, size=shared_len + uniq_len).astype(np.int32),
        shared[: shared_len - 2].copy(),         # shorter sharer
    ]
    return [Request(rid=i, prompt=p.astype(np.int32), max_new=max_new)
            for i, p in enumerate(prompts)]


def _paired_outputs(arch, pol_name, *, prefill="auto"):
    """Same request stream through a cold paged engine and a prefix engine;
    returns (tokens_paged, tokens_prefix, paged_engine, prefix_engine)."""
    from repro.models import model as M

    cfg = configs.reduced(configs.get_arch(arch))
    pol = get_policy(pol_name)
    p = M.init_params(jax.random.key(1), cfg, pol, mode="serve")
    kw = dict(n_slots=2, s_max=32, impl="jnp", prefill=prefill,
              prefill_chunk=4, page_size=4)
    cold = ServeEngine(p, cfg, pol, cache="paged", **kw)
    out_c = cold.run(_shared_prefix_requests(cfg))
    warm = ServeEngine(p, cfg, pol, cache="prefix", **kw)
    out_w = warm.run(_shared_prefix_requests(cfg))
    return out_c, out_w, cold, warm


# -------------------------------------------- prefix == cold paged bit-exact

#: (arch, policy) cells: attention family x kv_cache_bits {None, 8, 4}.
FAST_CELLS = [
    ("internlm2-1.8b", "bf16"),     # dense GQA, bf16 KV
    ("internlm2-1.8b", "w4a8"),     # dense GQA, int8 KV
    ("internlm2-1.8b", "w4a8kv4"),  # dense GQA, packed int4 KV
    ("deepseek-v3-671b", "w4a8"),   # MLA latent cache (absorbed decode)
]
SLOW_CELLS = [
    ("deepseek-v3-671b", "bf16"),
    ("deepseek-v3-671b", "w4a8kv4"),
    ("granite-moe-1b-a400m", "bf16"),
    ("granite-moe-1b-a400m", "w4a8"),
    ("granite-moe-1b-a400m", "w4a8kv4"),
    ("h2o-danube-1.8b", "bf16"),
    ("h2o-danube-1.8b", "w4a8"),
    ("h2o-danube-1.8b", "w4a8kv4"),
]


@pytest.mark.parametrize("arch,pol", FAST_CELLS)
def test_prefix_decode_bit_identical_to_cold_paged(arch, pol):
    """The acceptance regression: a shared-prefix stream decodes token for
    token like a cold paged run — mapped pages, COW clones and skipped
    prefill change the work done, never the numerics."""
    out_c, out_w, _, warm = _paired_outputs(arch, pol)
    assert out_c == out_w
    m = warm.metrics()
    assert m["cache/prefix_hit_rate"] > 0.0   # sharing actually happened
    assert m["cache/cow_copies"] >= 1         # divergence exercised COW


@pytest.mark.slow
@pytest.mark.parametrize("arch,pol", SLOW_CELLS)
def test_prefix_decode_bit_identical_to_cold_paged_full(arch, pol):
    out_c, out_w, _, _ = _paired_outputs(arch, pol)
    assert out_c == out_w


def test_prefix_stepwise_prefill_bit_identical():
    """The stepwise (token-by-token) prefill path also skips the matched
    prefix and stays bit-exact."""
    out_c, out_w, cold, warm = _paired_outputs("internlm2-1.8b", "w4a8",
                                               prefill="stepwise")
    assert out_c == out_w
    assert (warm.metrics()["prefill_jit_calls"]
            < cold.metrics()["prefill_jit_calls"])


def test_prefix_prefill_call_reduction(params):
    """Jitted prefill calls drop from O(S/chunk) to O(S_new/chunk): on a
    share-heavy stream (one cold template, then re-users) the prefix engine
    spends >= 2x fewer calls and draws fewer fresh pages."""
    rng = np.random.RandomState(3)
    shared = rng.randint(1, TINY.vocab, size=16).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.randint(1, TINY.vocab, size=4)])
               for _ in range(4)]
    reqs = lambda: [Request(rid=i, prompt=p.astype(np.int32).copy(),  # noqa: E731
                            max_new=4) for i, p in enumerate(prompts)]
    kw = dict(n_slots=2, s_max=32, impl="jnp", prefill="chunked",
              prefill_chunk=4, page_size=4)
    cold = ServeEngine(params, TINY, POLICY, cache="paged", **kw)
    out_c = cold.run(reqs())
    warm = ServeEngine(params, TINY, POLICY, cache="prefix", **kw)
    out_w = warm.run(reqs())
    assert out_c == out_w
    calls_cold = cold.metrics()["prefill_jit_calls"]
    calls_warm = warm.metrics()["prefill_jit_calls"]
    assert calls_cold >= 2 * calls_warm
    # and fewer fresh pages were drawn from the pool
    assert (cold.metrics()["cache/pages_drawn"]
            > warm.metrics()["cache/pages_drawn"])


# ------------------------------------------------------------ refcount safety


def test_completing_one_sharer_keeps_shared_pages():
    """The acceptance invariant: completing one of two requests sharing a
    prefix never zeroes or recycles the shared pages; they recycle exactly
    when the LAST reader releases them (and the index itself is a reader,
    so committed pages outlive both requests until evicted)."""
    cache = PrefixCache(TINY, POLICY, n_slots=2, s_max=32, page_size=4,
                        n_pages=24)
    prompt = np.arange(1, 13, dtype=np.int32)  # 12 tokens = 3 full pages

    def admit(prompt, need):
        s = cache.acquire(need, prompt=prompt)
        matched = int(cache.pos[s])
        n = len(prompt) - matched
        cache.prepare(s, n)
        cache.advance(s, n)
        cache.commit(s, prompt)
        return s, matched

    s0, matched0 = admit(prompt, 16)
    assert matched0 == 0  # cold: nothing matched
    s1, matched1 = admit(prompt, 16)
    assert matched1 == 11  # 2 full pages + 3 COW rows (S-1 cap)
    # full pages 0,1 matched ((d+1)*ps <= S-1); page 2 COW'd at m=3 (S-1 cap)
    assert int(cache._shared[s1]) == 2
    shared_pages = [int(cache.block_tables[s1, d]) for d in range(2)]
    assert shared_pages == [int(cache.block_tables[s0, d]) for d in range(2)]
    assert cache.block_tables[s1, 2] != cache.block_tables[s0, 2]  # COW clone
    # ref = s0 + s1 + index
    assert all(int(cache._ref[p]) == 3 for p in shared_pages)

    cache.release(s0)
    assert all(int(cache._ref[p]) == 2 for p in shared_pages)
    assert not any(p in cache._free for p in shared_pages)  # NOT recycled

    cache.release(s1)
    # index still reads them: resident, unzeroed accounting-wise
    assert all(int(cache._ref[p]) == 1 for p in shared_pages)
    assert not any(p in cache._free for p in shared_pages)
    assert cache.pages_live() == cache.index_pages() == 3

    # evicting the whole index releases the last references -> recycle
    while cache._evict_one(set()):
        pass
    assert cache.pages_live() == 0
    assert sorted([0] + cache._free) == list(range(cache.n_pages))
    for leaf in jax.tree.leaves(cache.caches):
        assert not np.asarray(leaf).any()  # zeroed at last-reader release


def test_shared_page_content_survives_sharer_completion(params):
    """Engine-level: a short sharer admitting and completing mid-run must
    not perturb the longer sharer's decode (its pages are live-read)."""
    from repro.models import model as M  # noqa: F401  (params fixture dep)

    rng = np.random.RandomState(7)
    shared = rng.randint(1, TINY.vocab, size=9).astype(np.int32)
    long_p = np.concatenate([shared, rng.randint(1, TINY.vocab, size=4)])
    reqs = lambda: [  # noqa: E731
        Request(rid=0, prompt=long_p.astype(np.int32).copy(), max_new=6),
        Request(rid=1, prompt=shared.copy(), max_new=1),  # admit+complete fast
    ]
    kw = dict(n_slots=2, s_max=24, impl="jnp", prefill="chunked",
              prefill_chunk=4, page_size=4)
    cold = ServeEngine(params, TINY, POLICY, cache="paged", **kw)
    warm = ServeEngine(params, TINY, POLICY, cache="prefix", **kw)
    assert cold.run(reqs()) == warm.run(reqs())


# ------------------------------------------------------------- COW semantics


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float32, jnp.bfloat16])
def test_paged_copy_pallas_matches_ref(dtype):
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randint(-100, 100, size=(7, 4, 2, 6))).astype(dtype)
    src = jnp.asarray([3, 1], jnp.int32)
    dst = jnp.asarray([5, 6], jnp.int32)
    a = PG.paged_copy_ref(pool, src, dst)
    b = PG.paged_copy_pallas(pool, src, dst, interpret=True)
    np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                  np.asarray(b.astype(jnp.float32)))
    # copied pages match their sources; every other page persists
    np.testing.assert_array_equal(np.asarray(a)[5], np.asarray(pool)[3])
    np.testing.assert_array_equal(np.asarray(a)[6], np.asarray(pool)[1])
    for p in (0, 1, 2, 3, 4):
        np.testing.assert_array_equal(np.asarray(a)[p], np.asarray(pool)[p])
    # src/dst overlap: a dst page reappearing as a later src must read the
    # ORIGINAL bits on both impls (sources snapshot before the in-place
    # write — the twin contract)
    src2 = jnp.asarray([1, 2], jnp.int32)
    dst2 = jnp.asarray([2, 3], jnp.int32)
    o_ref = PG.paged_copy_ref(pool, src2, dst2)
    o_pal = PG.paged_copy_pallas(pool, src2, dst2, interpret=True)
    np.testing.assert_array_equal(np.asarray(o_ref.astype(jnp.float32)),
                                  np.asarray(o_pal.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(o_ref)[3], np.asarray(pool)[2])


def test_cow_divergence_leaves_source_page_frozen():
    """A request diverging mid-page writes into its clone, never the shared
    source page (the source's other reader sees frozen bits)."""
    cache = PrefixCache(TINY, POLICY, n_slots=2, s_max=16, page_size=4,
                        n_pages=12)
    p_a = np.arange(1, 11, dtype=np.int32)          # 10 tokens
    s0 = cache.acquire(12, prompt=p_a)
    cache.prepare(s0, 10)
    cache.advance(s0, 10)
    cache.commit(s0, p_a)
    src_page = int(cache.block_tables[s0, 1])
    # poke recognizable content into the source page on one leaf
    leaf0 = jax.tree.leaves(cache.caches)[0]
    marked = leaf0.at[:, src_page].set(jnp.ones((), leaf0.dtype))
    cache.caches = jax.tree.map(
        lambda a: marked if a is jax.tree.leaves(cache.caches)[0] else a,
        cache.caches)

    p_b = p_a.copy()
    p_b[6] = 99  # diverge inside page 1 (rows 4..7): lcp m=2
    s1 = cache.acquire(12, prompt=p_b)
    assert int(cache.pos[s1]) == 6  # 1 full page + 2 COW rows
    dst_page = int(cache.block_tables[s1, 1])
    assert dst_page != src_page
    leaves = jax.tree.leaves(cache.caches)
    np.testing.assert_array_equal(  # clone took the marked bits
        np.asarray(leaves[0][:, dst_page]), np.asarray(leaves[0][:, src_page]))
    # simulate the suffix write: prepare/advance never touches src_page refs
    cache.prepare(s1, 4)
    cache.advance(s1, 4)
    assert int(cache._ref[src_page]) == 2  # s0 + index (clone is private)


def test_full_match_caps_at_s_minus_1():
    """An exact-duplicate prompt reuses everything but the last token: the
    final page is COW-cloned and exactly one token re-prefills, so the
    engine still samples the first output token from real logits."""
    cache = PrefixCache(TINY, POLICY, n_slots=2, s_max=16, page_size=4,
                        n_pages=12)
    prompt = np.arange(1, 9, dtype=np.int32)  # 8 tokens = 2 exact pages
    s0 = cache.acquire(10, prompt=prompt)
    cache.prepare(s0, 8)
    cache.advance(s0, 8)
    cache.commit(s0, prompt)
    s1 = cache.acquire(10, prompt=prompt)
    assert int(cache.pos[s1]) == 7          # S-1, never S
    assert int(cache._shared[s1]) == 1      # page 0 shared
    assert cache.block_tables[s1, 1] != cache.block_tables[s0, 1]  # COW'd
    assert cache.cow_copies == 1


# --------------------------------------------------------------- LRU eviction


def test_lru_eviction_frees_cold_leaves_only():
    """Pool pressure evicts cold index leaves in LRU order; pages with live
    readers (mapped by a busy slot) are never freed."""
    cache = PrefixCache(TINY, POLICY, n_slots=3, s_max=16, page_size=4,
                        n_pages=7)  # 6 usable pages

    def admit(prompt, need):
        s = cache.acquire(need, prompt=prompt)
        assert s is not None
        n = len(prompt) - int(cache.pos[s])
        cache.prepare(s, n)
        cache.advance(s, n)
        cache.commit(s, prompt)
        return s

    p_a = np.arange(1, 9, dtype=np.int32)
    s0 = admit(p_a, 8)          # 2 pages, both committed to the index
    cache.release(s0)           # index-only now (ref 1 each)
    assert cache.pages_live() == 2 and cache.index_pages() == 2

    p_b = np.arange(50, 58, dtype=np.int32)
    s1 = admit(p_b, 8)          # fits without eviction (4 free >= 2)
    assert cache.evictions == 0

    # a third, 3-page request: 0 free after b committed? live: a(2)+b(2),
    # free 2 -> needs 3 -> must evict a's LRU leaf chain
    p_c = np.arange(90, 102, dtype=np.int32)
    s2 = cache.acquire(12, prompt=p_c)
    assert s2 is not None
    assert cache.evictions >= 1
    live_pages = {int(p) for s in (s1, s2)
                  for p in cache.block_tables[s, : int(cache._alloc[s])]}
    assert all(int(cache._ref[p]) >= 1 for p in live_pages)
    assert not any(p in cache._free for p in live_pages)
    # conservation after churn
    assert cache.pages_free() + cache.pages_live() + 1 == cache.n_pages


def test_eviction_cannot_starve_live_reader():
    """can_admit must answer False (queue signal) when covering the request
    would require evicting pages a busy slot still reads."""
    cache = PrefixCache(TINY, POLICY, n_slots=2, s_max=16, page_size=4,
                        n_pages=5)  # 4 usable
    p_a = np.arange(1, 9, dtype=np.int32)
    s0 = cache.acquire(16, prompt=p_a)  # reserves all 4 pages
    cache.prepare(s0, 8)
    cache.advance(s0, 8)
    cache.commit(s0, p_a)
    p_b = np.arange(50, 58, dtype=np.int32)
    assert not cache.can_admit(16, prompt=p_b)
    assert cache.acquire(16, prompt=p_b) is None  # queue, not corruption
    # s0's pages untouched by the failed admission
    assert int(cache._alloc[s0]) == 2
    assert all(int(cache._ref[cache.block_tables[s0, d]]) == 2
               for d in range(2))


# ------------------------------------------------- namespaced cache metrics


def test_metrics_namespace_cache_keys(params):
    """cache.stats() keys mount under cache/ (no collision with engine
    counters), and the sharing backend surfaces hit-rate observability."""
    eng = ServeEngine(params, TINY, POLICY, n_slots=2, s_max=24, impl="jnp",
                      prefill="chunked", prefill_chunk=4,
                      cache="prefix", page_size=4)
    eng.run(_shared_prefix_requests(TINY, max_new=2)[:3])
    m = eng.metrics()
    assert m["cache/backend"] == "prefix"
    for k in ("cache/prefix_hit_rate", "cache/pages_shared",
              "cache/cow_copies", "cache/index_pages", "cache/pages_drawn"):
        assert k in m
    assert m["cache/prefix_hit_rate"] > 0.0
    # engine-level keys unchanged and un-shadowed
    for k in ("decode_steps", "tokens_per_s", "slot_resets", "queue_depth"):
        assert k in m
    assert not any(k.startswith("cache/cache/") for k in m)
    # slot backend namespaces too
    eng2 = ServeEngine(params, TINY, POLICY, n_slots=1, s_max=16, impl="jnp")
    assert eng2.metrics()["cache/backend"] == "slot"


# -------------------------- cancellation under sharing (lifecycle API v1)


def _assert_pool_conserved(cache):
    """free + (distinct live block-table/index pages) + scratch == n_pages,
    and no page is simultaneously free and mapped."""
    table = {int(p) for s in range(cache.n_slots)
             for p in cache.block_tables[s, : int(cache._alloc[s])]}
    index = set()

    def walk(node):
        for ch in node.children.values():
            index.add(ch.page)
            walk(ch)
    walk(cache._root)
    live = (table | index) - {0}
    assert len(cache._free) + len(live) + 1 == cache.n_pages
    assert not live.intersection(cache._free)


def _sharing_prompts():
    """Four sharers of one 12-token template plus one cold prompt."""
    rng = np.random.RandomState(11)
    shared = rng.randint(1, TINY.vocab, size=12).astype(np.int32)
    prompts = [np.concatenate(
        [shared, rng.randint(1, TINY.vocab, size=3 + i)]).astype(np.int32)
        for i in range(4)]
    prompts.append(rng.randint(1, TINY.vocab, size=10).astype(np.int32))
    return prompts


_CANCEL_BASELINE: dict[int, list] = {}


def _uncancelled_baseline(params):
    """Tokens of the churn workload run to completion with no cancels —
    computed once; greedy decode on the prefix backend is bit-exact
    regardless of sharing, eviction, or admission order."""
    if not _CANCEL_BASELINE:
        eng = ServeEngine(params, TINY, POLICY, n_slots=3, s_max=32,
                          impl="jnp", prefill="chunked", prefill_chunk=4,
                          cache="prefix", page_size=4)
        out = eng.run([Request(rid=i, prompt=p.copy(), max_new=6)
                       for i, p in enumerate(_sharing_prompts())])
        _CANCEL_BASELINE.update(out)
    return _CANCEL_BASELINE


@settings(max_examples=5, deadline=None)
@given(data=st.data())
def test_mid_decode_cancellation_conserves_pool_and_sharers(data, params):
    """Property: random mid-decode cancel() calls against a shared-prefix
    stream (small pools included, so admission queues and LRU eviction
    fires) keep the pool conserved after EVERY step and cancellation, never
    perturb a surviving sharer's tokens, and leak nothing once drained."""
    from repro.serve import SamplingParams

    prompts = _sharing_prompts()
    n_pages = data.draw(st.integers(14, 25), label="pages")
    cancel_after = {
        rid: data.draw(st.integers(1, 5), label=f"after{rid}")
        for rid in set(data.draw(
            st.lists(st.sampled_from(range(len(prompts))), min_size=0,
                     max_size=3), label="cancel"))}
    eng = ServeEngine(params, TINY, POLICY, n_slots=3, s_max=32, impl="jnp",
                      prefill="chunked", prefill_chunk=4,
                      cache="prefix", page_size=4, n_pages=n_pages)
    handles = {i: eng.submit(p.copy(), SamplingParams(max_new=6), rid=i)
               for i, p in enumerate(prompts)}
    while True:
        more = eng.step()
        _assert_pool_conserved(eng.cache)
        for rid, k in cancel_after.items():
            h = handles[rid]
            if not h.done and len(h.request.out or []) >= k:
                h.cancel()
                _assert_pool_conserved(eng.cache)
        if not more:
            break
    baseline = _uncancelled_baseline(params)
    for rid, h in handles.items():
        if rid in cancel_after:
            assert h.status == "cancelled"
            assert len(h.request.out) >= cancel_after[rid]
        else:
            assert h.status == "done"
            assert h.request.out == baseline[rid]  # survivors untouched
    assert eng.metrics()["cancelled"] == len(cancel_after)
    # drained: every page is either free or pinned by the warm index
    assert eng.cache.pages_live() == eng.cache.index_pages()
    _assert_pool_conserved(eng.cache)


# ------------------------------------- pool conservation under random churn


@settings(max_examples=12, deadline=None)
@given(data=st.data())
def test_page_accounting_invariant_under_churn(data):
    """Property: at every point of a random admit/advance/complete/evict
    interleaving, free + (distinct live block-table/index pages) + scratch
    == n_pages, every live page's refcount equals its reader count, and no
    page is simultaneously free and referenced."""
    cache = PrefixCache(TINY, POLICY, n_slots=3, s_max=24, page_size=4,
                        n_pages=data.draw(st.integers(6, 14), label="pages"))
    vocab = [np.asarray(p, np.int32) for p in (
        list(range(1, 17)), list(range(1, 9)) + list(range(30, 38)),
        list(range(60, 72)), list(range(1, 6)))]
    pending: dict[int, tuple] = {}  # slot -> (prompt, need)

    def check():
        table_pages = {int(p)
                       for s in range(cache.n_slots)
                       for p in cache.block_tables[s, : int(cache._alloc[s])]}
        index = set()

        def walk(node):
            for ch in node.children.values():
                index.add(ch.page)
                walk(ch)
        walk(cache._root)
        live = (table_pages | index) - {0}
        assert len(cache._free) + len(live) + 1 == cache.n_pages
        assert not live.intersection(cache._free)
        for p in live:
            readers = sum(
                1 for s in range(cache.n_slots)
                for q in cache.block_tables[s, : int(cache._alloc[s])]
                if int(q) == p) + (1 if p in index else 0)
            assert int(cache._ref[p]) == readers
        for p in cache._free:
            assert int(cache._ref[p]) == 0

    for _ in range(12):
        op = data.draw(st.sampled_from(["admit", "advance", "complete"]),
                       label="op")
        if op == "admit" and not all(cache._busy):
            prompt = data.draw(st.sampled_from(vocab), label="prompt")
            need = len(prompt) + data.draw(st.integers(1, 4), label="new")
            if cache.can_admit(need, prompt=prompt):
                s = cache.acquire(need, prompt=prompt)
                assert s is not None
                n = len(prompt) - int(cache.pos[s])
                cache.prepare(s, n)
                cache.advance(s, n)
                cache.commit(s, prompt)
                pending[s] = (prompt, need)
        elif op == "advance" and pending:
            s = data.draw(st.sampled_from(sorted(pending)), label="slot")
            _, need = pending[s]
            if int(cache.pos[s]) < need:
                cache.prepare(s, 1)
                cache.advance(s, 1)
        elif op == "complete" and pending:
            s = data.draw(st.sampled_from(sorted(pending)), label="slot")
            cache.release(s)
            del pending[s]
        check()
    for s in sorted(pending):
        cache.release(s)
    check()

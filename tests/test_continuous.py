"""Continuous-batching tests (fast tier): mixed prefill+decode steps must be
BIT-IDENTICAL to the serialized engine on every cache backend (greedy and
seeded-stochastic), the ahead-of-time dispatch pipeline must respect its
in-flight bound, mixed-step churn (admissions, cancellations, stop
sequences interleaved with in-flight decode) must conserve the page pool
and never perturb a survivor's stream, one packed mixed step must equal
each of its lanes run alone (moe keeps the padded step), drain() must
yield (and eventually raise) instead of busy-spinning on queue-only work,
and the supporting
pieces — LatencyHistogram, SnapshotRing, PrefillCursor, Scheduler.allot —
hold their unit contracts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from repro import configs
from repro.core.policy import get_policy
from repro.models import model as M
from repro.models.attention import PAD_POS
from repro.serve import (
    LatencyHistogram,
    PrefillCursor,
    Request,
    SamplingParams,
    Scheduler,
    ServeEngine,
    SnapshotRing,
    Tracer,
    make_scheduler,
)
from repro.serve.engine import MIXED_MAX_CHUNKS

jax.config.update("jax_platform_name", "cpu")

TINY = configs.reduced(configs.get_arch("internlm2-1.8b"))
POLICY = get_policy("w4a8")

BACKENDS = {
    "slot": {},
    "paged": dict(page_size=8, n_pages=40),
    "prefix": dict(page_size=8, n_pages=40),
}


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.key(3), TINY, POLICY, mode="serve")


def _requests(lengths=(3, 9, 21, 2, 7, 13), seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(1, TINY.vocab, size=n).astype(np.int32),
                    max_new=4 + (i % 3))
            for i, n in enumerate(lengths)]


def _engine(params, *, backend="slot", mixed=False, **kw):
    return ServeEngine(params, TINY, POLICY, n_slots=2, s_max=48, impl="jnp",
                       cache=backend, mixed=mixed,
                       **{**BACKENDS[backend], **kw})


# ---------------------------------- bit-exactness vs the serialized engine


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_continuous_tokens_bit_identical_to_serialized(params, backend):
    """THE acceptance regression: greedy token streams from the continuous
    engine (mixed steps + ahead-of-time dispatch) equal the serialized
    engine's bit for bit, on every cache backend."""
    out_ser = _engine(params, backend=backend).run(_requests())
    e_mix = _engine(params, backend=backend, mixed=True, mixed_budget=4,
                    inflight=2)
    out_mix = e_mix.run(_requests())
    assert out_mix == out_ser
    m = e_mix.metrics()
    assert m["mode"] == "continuous"
    assert m["mixed_steps"] > 0          # prefill actually rode decode steps
    assert m["prefill_jit_calls"] == 0   # the blocking prefill loop never ran
    assert m["inflight"] == 0            # drained: pipeline fully retired


def test_continuous_stochastic_bit_identical_to_serialized(params):
    """Seeded stochastic streams survive the pipeline: sampler counters
    advance speculatively at dispatch, yet every token matches the
    serialized engine (fused_attn pinned off on both sides — mixed steps
    take the unfused branch, and stochastic equality needs logit
    bit-equality, not just argmax agreement)."""
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, TINY.vocab, size=n).astype(np.int32)
               for n in (4, 11, 6)]
    sp = SamplingParams(temperature=0.9, top_k=12, top_p=0.9, seed=7,
                        max_new=6)

    def mk():
        return [Request(rid=i, prompt=p.copy(), params=sp)
                for i, p in enumerate(prompts)]

    o_ser = _engine(params, backend="paged", fused_attn=False).run(mk())
    o_mix = _engine(params, backend="paged", fused_attn=False, mixed=True,
                    mixed_budget=4, inflight=3).run(mk())
    assert o_ser == o_mix


def test_inflight_bound_and_mixed_step_accounting(params):
    """The dispatch queue never exceeds ``inflight`` (observed mid-run from
    token callbacks) and every prompt token enters through a mixed step,
    so at least ceil(total_prompt_tokens / budget) mixed steps ran."""
    depth_seen = []
    eng = _engine(params, backend="paged", mixed=True, mixed_budget=4,
                  inflight=3)
    reqs = _requests()
    for r in reqs:
        r.on_token = lambda rid, tok: depth_seen.append(
            eng.metrics()["inflight"])
    eng.run(reqs)
    assert depth_seen and max(depth_seen) <= 3
    total_prompt = sum(len(r.prompt) for r in _requests())
    assert eng.metrics()["mixed_steps"] >= -(-total_prompt // 4)


def test_mixed_requires_chunkable_prefill(params):
    with pytest.raises(ValueError, match="chunked prefill"):
        _engine(params, mixed=True, prefill="stepwise")


# ------------------------------------------------ churn under mixed steps

#: shared 12-token template + suffixes (exercises prefix COW/sharing) plus
#: one cold prompt — the test_prefix cancellation workload, continuous now
_RNG = np.random.RandomState(11)
_SHARED = _RNG.randint(1, TINY.vocab, size=12).astype(np.int32)
_PROMPTS = [np.concatenate(
    [_SHARED, _RNG.randint(1, TINY.vocab, size=3 + i)]).astype(np.int32)
    for i in range(4)]
_PROMPTS.append(_RNG.randint(1, TINY.vocab, size=10).astype(np.int32))

_BASE: dict = {}


def _churn_engine(params, backend="prefix", **kw):
    return ServeEngine(params, TINY, POLICY, n_slots=3, s_max=32, impl="jnp",
                       cache=backend, page_size=4, fused_attn=False, **kw)


def _churn_baseline(params):
    """Serialized greedy baseline of the churn workload. Stop sequences are
    chosen FROM a no-stop baseline (a 2-gram of request 1's stream, the 4th
    token of request 3) so stops genuinely fire mid-decode; the with-stops
    serialized run defines the expected tokens AND statuses. Computed once
    per module."""
    if not _BASE:
        plain = _churn_engine(params).run(
            [Request(rid=i, prompt=p.copy(), max_new=6)
             for i, p in enumerate(_PROMPTS)])
        stops = {1: (tuple(plain[1][2:4]),), 3: ((plain[3][3],),)}
        eng = _churn_engine(params)
        handles = {i: eng.submit(
            p.copy(), SamplingParams(max_new=6, stop=stops.get(i, ())),
            rid=i) for i, p in enumerate(_PROMPTS)}
        eng.drain()
        _BASE.update(
            stops=stops,
            expect={i: list(h.request.out) for i, h in handles.items()},
            status={i: h.status for i, h in handles.items()})
        assert "stopped" in _BASE["status"].values()  # stops really fire
    return _BASE["stops"], _BASE["expect"], _BASE["status"]


def _assert_pool_conserved(cache):
    """free + (distinct live block-table/index pages) + scratch == n_pages,
    and no page is simultaneously free and mapped. Works on both paged
    backends (the radix walk only runs when an index exists)."""
    table = {int(p) for s in range(cache.n_slots)
             for p in cache.block_tables[s, : int(cache._alloc[s])]}
    index = set()
    if hasattr(cache, "_root"):
        def walk(node):
            for ch in node.children.values():
                index.add(ch.page)
                walk(ch)
        walk(cache._root)
    live = (table | index) - {0}
    assert len(cache._free) + len(live) + 1 == cache.n_pages
    assert not live.intersection(cache._free)


@settings(max_examples=4, deadline=None)
@given(data=st.data())
def test_mixed_step_churn_conserves_pool_and_survivors(data, params):
    """Property (the churn satellite): random mid-flight cancel() calls
    against the continuous engine — admissions, stop-sequence releases, and
    slot turnover all interleaved with speculative in-flight decode — keep
    the page pool conserved after EVERY step, stop exactly where the
    serialized engine stops, and leave survivors' streams bit-equal to the
    serialized baseline. Cancelled requests hold a prefix of their baseline
    stream (in-flight tickets for a turned-over lane must drop, not
    emit)."""
    stops, expect, status = _churn_baseline(params)
    backend = data.draw(st.sampled_from(["paged", "prefix"]), label="backend")
    n_pages = data.draw(st.integers(18, 30), label="pages")
    cancel_after = {
        rid: data.draw(st.integers(1, 4), label=f"after{rid}")
        for rid in set(data.draw(
            st.lists(st.sampled_from(range(len(_PROMPTS))), min_size=0,
                     max_size=2), label="cancel"))}
    eng = _churn_engine(params, backend=backend, n_pages=n_pages,
                        mixed=True, mixed_budget=4, inflight=2)
    handles = {i: eng.submit(
        p.copy(), SamplingParams(max_new=6, stop=stops.get(i, ())), rid=i)
        for i, p in enumerate(_PROMPTS)}
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
    for rid, h in handles.items():
        if h.status == "cancelled":
            assert rid in cancel_after
            got = list(h.request.out)
            assert got == expect[rid][:len(got)]  # prefix: no phantom emits
        else:
            assert list(h.request.out) == expect[rid]
            assert h.status == status[rid]
    assert eng.metrics()["cancelled"] == sum(
        1 for h in handles.values() if h.status == "cancelled")
    assert eng.metrics()["inflight"] == 0
    _assert_pool_conserved(eng.cache)


# ----------------------------------------- drain(): no busy-spin, no wedge


class _DecliningScheduler(Scheduler):
    """Admission policy that never yields a request — the queue-only-work
    wedge: pending() > 0 forever, nothing active, nothing in flight."""

    name = "decline"

    def pick(self, fits=None, cost=None):
        return 0

    def next_request(self, fits=None, cost=None):
        return None


@pytest.mark.parametrize("mixed", [False, True])
def test_drain_raises_on_wedge_instead_of_spinning(params, mixed):
    """Regression for the drain() busy-spin: when every step is a no-op
    (queued work that admission can never place, nothing in flight to free
    capacity), drain() must raise after a bounded number of yielding no-op
    steps — the old loop spun at 100% CPU forever."""
    eng = _engine(params, mixed=mixed, scheduler=_DecliningScheduler())
    eng.submit(np.array([5, 6, 7], np.int32), SamplingParams(max_new=2))
    assert eng.step()  # work remains, but nothing progressed
    with pytest.raises(RuntimeError, match="wedged"):
        eng.drain()
    # the engine is not corrupted: the queued request is still visible
    assert eng.metrics()["queue_depth"] == 1


def test_drain_completes_normally_after_transient_queueing(params):
    """Sanity twin: a genuinely admissible backlog (more requests than
    slots) drains to completion — the no-progress valve never fires on
    ordinary queueing."""
    eng = _engine(params, backend="paged", mixed=True)
    out = eng.run(_requests())
    assert all(len(v) >= 4 for v in out.values())


# ------------------------------------------------------ the packed step

#: one packed step at budget 8 over 7 slots: (slot, tokens already cached,
#: role). Slot 0 carries the final chunk of its prompt and slot 1 a
#: non-final one, both at non-zero offsets; slots 2-4 decode; slot 5 is
#: mid-prompt but not allotted this step; slot 6 is idle.
_PACKED_SLOTS = [(0, 5, "final"), (1, 4, "chunk"), (2, 6, "decode"),
                 (3, 9, "decode"), (4, 3, "decode"), (5, 4, "waiting"),
                 (6, 0, "idle")]
_PACKED_CHUNKS = {0: 3, 1: 4}  # slot -> tokens of its chunk this step
_PACKED_BUDGET, _PACKED_S_MAX, _PACKED_PS = 8, 16, 4
_PACKED_PARAMS: dict = {}


def _packed_params(arch):
    cfg = configs.reduced(configs.get_arch(arch))
    if arch not in _PACKED_PARAMS:
        _PACKED_PARAMS[arch] = M.init_params(jax.random.key(4), cfg, POLICY,
                                             mode="serve")
    return cfg, _PACKED_PARAMS[arch]


def _packed_case(cfg, params, seed):
    """A dense cache filled by real prefills, the step's packed host
    arrays (laid out as the engine lays them) and each working slot's
    tokens of the step."""
    rng = np.random.RandomState(seed)
    n, W = len(_PACKED_SLOTS), _PACKED_BUDGET
    caches = M.init_cache(cfg, POLICY, n, _PACKED_S_MAX)
    for s, cached, _ in _PACKED_SLOTS:
        if cached:
            prompt = jnp.asarray(rng.randint(1, cfg.vocab, (1, cached)), jnp.int32)
            _, caches = M.prefill_into_slot(params, prompt, jnp.int32(s),
                                            jnp.int32(0), caches, cfg, POLICY,
                                            head=False, impl="jnp")
    toks = np.zeros(W + n, np.int32)
    pos = np.full(W + n, PAD_POS, np.int32)
    lanes = np.concatenate([np.zeros(W, np.int32), np.arange(n, dtype=np.int32)])
    emit = np.arange(W, W + n, dtype=np.int32)
    starts = np.full(MIXED_MAX_CHUNKS, W, np.int32)
    step_toks = {}
    row = 0
    for i, (s, k) in enumerate(_PACKED_CHUNKS.items()):
        step_toks[s] = rng.randint(1, cfg.vocab, k).astype(np.int32)
        toks[row:row + k] = step_toks[s]
        pos[row:row + k] = _PACKED_SLOTS[s][1] + np.arange(k)
        lanes[row:row + k] = s
        starts[i], emit[s] = row, row + k - 1
        row += k
    for s, cached, role in _PACKED_SLOTS:
        if role == "decode":
            step_toks[s] = rng.randint(1, cfg.vocab, 1).astype(np.int32)
            toks[W + s], pos[W + s] = step_toks[s][0], cached
    return caches, (toks, pos, lanes, emit, starts), step_toks


def _lane_reference(cfg, params, caches, step_toks):
    """Each working slot's step run on its cache row alone: prefill_chunk
    for a chunk, decode_step (unfused) for a decode token. Returns the
    emitting slots' logits (1, V) and the dense cache the steps leave."""
    logits, expect = {}, caches
    for s, cached, role in _PACKED_SLOTS:
        if s not in step_toks:
            continue
        row = jax.tree.map(lambda a: a[:, s:s + 1], caches)
        t, p = jnp.asarray(step_toks[s][None]), jnp.int32([cached])
        if role == "decode":
            lg, row = M.decode_step(params, t, p, row, cfg, POLICY, impl="jnp")
        else:
            lg, row = M.prefill_chunk(params, t, p, row, cfg, POLICY,
                                      head=role == "final", impl="jnp")
        if lg is not None:
            logits[s] = np.asarray(lg[:, -1])
        expect = jax.tree.map(lambda a, r: a.at[:, s:s + 1].set(r), expect, row)
    return logits, expect


def _as_pages(caches, bt, n_pages):
    """The dense cache laid into a zeroed page pool through ``bt``."""
    n, nb = bt.shape

    def lay(a):
        pool = jnp.zeros((a.shape[0], n_pages, _PACKED_PS) + a.shape[3:], a.dtype)
        rows = a.reshape(a.shape[0], n * nb, _PACKED_PS, *a.shape[3:])
        return pool.at[:, bt.reshape(-1)].set(rows)

    return jax.tree.map(lay, caches)


@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "h2o-danube-1.8b"])
def test_packed_mixed_step_matches_each_lane_alone(arch, layout):
    """One packed mixed step holding two prefill chunks at non-zero
    offsets (one final), three decode lanes, a still-prefilling slot and an
    idle slot: every emitting slot's logits equal ``prefill_chunk`` /
    ``decode_step`` run on that lane alone, the cache holds exactly what
    those runs write, and — paged — no page outside the working lanes'
    written rows changes except the scratch page 0."""
    cfg, params = _packed_params(arch)
    caches, (toks, pos, lanes, emit, starts), step_toks = _packed_case(
        cfg, params, seed=1)
    want_logits, want_cache = _lane_reference(cfg, params, caches, step_toks)
    n = len(_PACKED_SLOTS)
    bt = None
    if layout == "paged":
        nb = _PACKED_S_MAX // _PACKED_PS
        n_pages = 1 + n * nb
        bt = jnp.asarray(np.random.RandomState(2).permutation(
            np.arange(1, n_pages)).reshape(n, nb), jnp.int32)
        caches = _as_pages(caches, bt, n_pages)
    logits, got = jax.jit(lambda c: M.mixed_step(
        params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(lanes), c,
        cfg, POLICY, emit=jnp.asarray(emit), starts=jnp.asarray(starts),
        impl="jnp", block_tables=bt))(caches)
    assert logits.shape == (n, 1, cfg.vocab_padded)
    for s, lg in want_logits.items():
        np.testing.assert_array_equal(np.asarray(logits[s]), lg, err_msg=f"slot {s}")
    assert set(want_logits) == {0, 2, 3, 4}
    if layout == "paged":
        # the pages holding the rows the working lanes wrote, and page 0
        written = {int(bt[s, p // _PACKED_PS]) for s, cached, _ in _PACKED_SLOTS
                   if s in step_toks
                   for p in range(cached, cached + len(step_toks[s]))}
        for a, b in zip(jax.tree.leaves(caches), jax.tree.leaves(got)):
            diff = np.asarray(a != b).reshape(a.shape[0], a.shape[1], -1)
            changed = set(np.nonzero(diff.any(axis=(0, 2)))[0].tolist())
            assert changed <= written | {0}, changed - written
        got = jax.tree.map(lambda a: a[:, bt].reshape(
            a.shape[0], n, _PACKED_S_MAX, *a.shape[3:]), got)
    for a, b in zip(jax.tree.leaves(want_cache), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_step_is_dense_only_and_moe_stays_padded(params):
    """``moe`` (and ``mla_moe``) keep the padded mixed step: the packed one
    refuses them, and a moe engine's mixed steps compute n_slots x budget
    rows while its tokens stay equal to the serialized engine's; a dense
    engine's compute budget + n_slots."""
    moe = configs.reduced(configs.get_arch("granite-moe-1b-a400m"))
    assert moe.family not in M.PACKED_FAMILIES
    assert "mla_moe" not in M.PACKED_FAMILIES
    with pytest.raises(NotImplementedError, match="mixed_step_padded"):
        M.mixed_step(None, jnp.zeros(6, jnp.int32), None, None, None, moe,
                     POLICY, emit=jnp.zeros(2, jnp.int32), starts=None)
    mparams = M.init_params(jax.random.key(5), moe, POLICY, mode="serve")
    for cfg, p in ((moe, mparams), (TINY, params)):
        def mk():
            return [Request(rid=i, prompt=r.prompt.copy(), max_new=r.max_new)
                    for i, r in enumerate(_requests((3, 9, 6)))]

        tr = Tracer()
        kw = dict(n_slots=2, s_max=48, impl="jnp", cache="paged", page_size=8,
                  n_pages=40)
        want = ServeEngine(p, cfg, POLICY, **kw).run(mk())
        got = ServeEngine(p, cfg, POLICY, mixed=True, mixed_budget=4, trace=tr,
                          **kw).run(mk())
        assert got == want
        rows = {e.args["rows"] for e in tr.events() if e.name == "mixed_step"}
        assert rows == ({2 * 4} if cfg is moe else {4 + 2})


# ------------------------------------------------------------- unit pieces


def test_latency_histogram_contract():
    h = LatencyHistogram()
    assert h.percentile(50) == 0.0 and h.n == 0
    h.observe(3e-3)
    # single sample: every percentile IS the sample (clamped to vmin==vmax)
    assert h.percentile(50) == pytest.approx(3e-3)
    assert h.percentile(99) == pytest.approx(3e-3)
    rng = np.random.RandomState(0)
    for v in rng.lognormal(-5, 2, size=5000):
        h.observe(float(v))
    p50, p95, p99 = (h.percentile(q) for q in (50, 95, 99))
    assert 0 < p50 <= p95 <= p99 <= h.vmax
    assert h.n == 5001 and h.mean > 0
    s = h.summary("slo/tpot")
    assert set(s) == {"slo/tpot_p50_s", "slo/tpot_p95_s", "slo/tpot_p99_s",
                      "slo/tpot_mean_s", "slo/tpot_max_s", "slo/tpot_count"}
    assert s["slo/tpot_mean_s"] == pytest.approx(h.mean)
    assert s["slo/tpot_count"] == 5001
    # out-of-range observations clamp into the edge bins, never crash; the
    # percentile stays a bin edge (pessimistic) while vmax keeps the truth
    h.observe(0.0)
    h.observe(1e9)
    assert h.vmax == 1e9 and h.vmin == 0.0
    assert h.percentile(100) == pytest.approx(h.hi)  # top-bin upper edge


def test_snapshot_ring_isolation_and_reuse():
    ring = SnapshotRing(3)
    a = np.array([1, 2, 3], np.int32)
    s1 = ring.take("pos", a)
    a[:] = [4, 5, 6]
    s2 = ring.take("pos", a)
    a[:] = [7, 8, 9]
    s3 = ring.take("pos", a)
    # snapshots are immune to later host mutation (the host_copy contract)
    assert np.asarray(s1).tolist() == [1, 2, 3]
    assert np.asarray(s2).tolist() == [4, 5, 6]
    # the 4th take recycles snapshot 1's buffer (generations=3), leaving
    # the two most recent generations — the in-flight window — intact
    a[:] = [10, 11, 12]
    ring.take("pos", a)
    assert np.asarray(s2).tolist() == [4, 5, 6]
    assert np.asarray(s3).tolist() == [7, 8, 9]
    # same-shaped values under DIFFERENT names never share buffers
    t1 = ring.take("temps", np.array([1.0, 2.0], np.float32))
    for v in (9.0, 8.0, 7.0):
        ring.take("top_ps", np.array([v, v], np.float32))
    assert np.asarray(t1).tolist() == [1.0, 2.0]
    # a shape change mid-stream reallocates instead of writing garbage
    s = ring.take("pos", np.zeros(5, np.int32))
    assert np.asarray(s).shape == (5,)
    with pytest.raises(ValueError):
        SnapshotRing(1)


def test_allot_chunk_cap_serves_two_and_leaves_the_third_whole():
    """``max_chunks=2`` with three cursors and budget to spare serves the
    first two in allotment order and leaves the third untouched, in its
    place for the next step; the default (no cap) serves all three."""
    reqs = [Request(rid=i, prompt=np.arange(1, n + 1, dtype=np.int32),
                    max_new=2) for i, n in enumerate((5, 4, 6))]

    def curs():
        return [PrefillCursor(r, r.prompt, slot=i, order=i)
                for i, r in enumerate(reqs)]

    cs = curs()
    got = make_scheduler("fcfs").allot(cs, 32, max_chunks=2)
    assert [(c.slot, n) for c, n in got] == [(0, 5), (1, 4)]
    assert cs[2].remaining == 6 and cs[2].chunks == 0
    # the held-back cursor goes first once the others are done
    for c, n in got:
        c.take(n)
    later = make_scheduler("fcfs").allot([c for c in cs if not c.done], 32,
                                         max_chunks=2)
    assert [(c.slot, n) for c, n in later] == [(2, 6)]
    assert [(c.slot, n) for c, n in make_scheduler("fcfs").allot(curs(), 32)] \
        == [(0, 5), (1, 4), (2, 6)]


def test_prefill_cursor_and_allot():
    reqs = [Request(rid=i, prompt=np.arange(1, n + 1, dtype=np.int32),
                    max_new=2) for i, n in enumerate((10, 3, 6))]
    curs = [PrefillCursor(r, r.prompt, slot=i, order=i)
            for i, r in enumerate(reqs)]
    assert curs[0].remaining == 10 and not curs[0].done
    assert curs[0].take(4).tolist() == [1, 2, 3, 4]
    assert curs[0].remaining == 6
    # fcfs: admission order, greedy to the budget; chunks stay consecutive
    got = make_scheduler("fcfs").allot(curs, 8)
    assert [(c.slot, n) for c, n in got] == [(0, 6), (1, 2)]
    # spf: shortest REMAINING prompt drains first (ties: admission order)
    got = make_scheduler("spf").allot(curs, 8)
    assert [(c.slot, n) for c, n in got] == [(1, 3), (0, 5)]
    # priority: the higher class preempts the whole budget
    reqs[2].priority = 5
    got = make_scheduler("priority").allot(curs, 8)
    assert (got[0][0].slot, got[0][1]) == (2, 6)
    assert sum(n for _, n in got) <= 8
    # a matched shared prefix starts the cursor past the resident tokens
    c = PrefillCursor(reqs[0], reqs[0].prompt, slot=0, order=9, off=8)
    assert c.remaining == 2 and c.take(16).tolist() == [9, 10]
    assert c.done

"""Shared-prefix KV page reuse: refcounted content-addressed pool,
copy-on-write discipline, sentinel table hygiene, and the allocator/
scheduler bugfix batch (see serving/kv_pages.py module docstring)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import Runtime, ServingConfig, get_config
from repro.models.attention import attention_core
from repro.serving.api import poisson_trace, run_trace, shared_prefix_trace
from repro.serving.engine import InferenceEngine
from repro.serving.kv_pages import (
    ContinuousKVCache,
    PagedKVCacheManager,
    encode_kv,
    init_paged_attn_cache,
    paged_read,
    paged_write,
)
from repro.serving.scheduler import (
    CANCELLED,
    Request,
    Scheduler,
    ShedError,
    TIMEOUT,
)


SV = ServingConfig(layout="paged", max_batch=2, page_size=4, num_pages=8,
                   max_ctx=16)


def _req(rid, prompt, max_new=4, arrival=0.0):
    return Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                   max_new=max_new, arrival=arrival)


# ----------------------------------------------------------- page manager --
def test_admit_request_shares_pages_and_refcounts():
    kv = PagedKVCacheManager(SV)
    tokens = np.arange(12, dtype=np.int32)
    assert kv.admit_request(0, tokens, 13) == 0     # nothing indexed yet
    kv.register_upto(0, tokens, 12)                 # pages 0..2 full
    donor = list(kv.pages[0])

    # same prefix: full pages are shared, capped below the full length
    hit = kv.admit_request(1, tokens, 13)
    assert hit == 8                                 # (12-1)//4 = 2 pages
    assert kv.pages[1][:2] == donor[:2]
    assert kv.refcount[donor[0]] == 2 and kv.refcount[donor[1]] == 2
    assert kv.pages[1][2] not in donor              # COW: fresh, not shared

    # diverging prefix stops at the divergence page (smaller allocation:
    # rid 0/1 already hold 6 of the 8 pool pages)
    other = tokens.copy()
    other[5] = 99
    assert kv.admit_request(2, other, 5) == 4
    kv.release(2)


def test_admission_miss_leaves_no_holds_or_counters():
    """A queue head blocked on capacity retries every step: failed
    admissions must not bump hit counters or churn warm-pool LRU order."""
    kv = PagedKVCacheManager(SV)
    tokens = np.arange(16, dtype=np.int32)
    assert kv.admit_request(0, tokens, 16) == 0     # 4 pages
    kv.register_upto(0, tokens, 16)
    assert kv.admit_request(1, 100 + np.arange(16, dtype=np.int32), 16) == 0
    lookups, hits = kv.n_lookups, kv.n_hit_tokens
    warm_before = list(kv.warm)
    # pool exhausted (8/8 in use): same-prefix admission must fail cleanly
    assert kv.admit_request(2, tokens, 16) is None
    assert 2 not in kv.pages and 2 not in kv._chain
    assert kv.n_lookups == lookups and kv.n_hit_tokens == hits
    assert list(kv.warm) == warm_before
    assert all(c == 1 for c in kv.refcount.values())


def test_release_keeps_registered_pages_warm_and_hittable():
    kv = PagedKVCacheManager(SV)
    tokens = np.arange(9, dtype=np.int32)
    assert kv.admit_request(0, tokens, 9) == 0
    kv.register_upto(0, tokens, 9)                  # 2 full pages indexed
    pages = list(kv.pages[0])
    kv.release(0)
    assert kv.available == SV.num_pages             # warm pages still free
    assert kv.in_use == 0
    # resubmission hits the warm pages with the same physical ids
    assert kv.admit_request(1, tokens, 9) == 8
    assert kv.pages[1][:2] == pages[:2]
    kv.release(1)

    # prefix_lru=off forgets content at release
    sv = ServingConfig(layout="paged", max_batch=2, page_size=4, num_pages=8,
                       max_ctx=16, prefix_lru=False)
    kv = PagedKVCacheManager(sv)
    assert kv.admit_request(0, tokens, 9) == 0
    kv.register_upto(0, tokens, 9)
    kv.release(0)
    assert kv.admit_request(1, tokens, 9) == 0
    assert not kv.index and not kv.page_hash


def test_warm_pages_evict_lru_when_blanks_run_dry():
    kv = PagedKVCacheManager(SV)
    a, b = np.arange(16, dtype=np.int32), 100 + np.arange(16, dtype=np.int32)
    assert kv.admit_request(0, a, 16) == 0          # 4 pages each
    assert kv.admit_request(1, b, 16) == 0
    kv.register_upto(0, a, 16)
    kv.register_upto(1, b, 16)
    kv.release(0)                                   # a's pages: oldest warm
    kv.release(1)
    assert kv.available == 8 and len(kv.index) == 8
    # a fresh full-pool request must evict — LRU order takes a's pages first
    assert kv.ensure(2, 16)
    assert kv.n_evictions == 4
    kv.release(2)
    assert kv.admit_request(3, a, 16) == 0          # a evicted...
    kv.release(3)
    assert kv.admit_request(4, b, 16) > 0           # ...b survived
    kv.release(4)


def test_zero_token_semantics_unified():
    """Bugfix: pages_for(0) returned 1 (paged) vs 0 (contiguous)."""
    assert PagedKVCacheManager(SV).pages_for(0) == 0
    assert ContinuousKVCache(SV).pages_for(0) == 0


def test_submit_error_is_layout_aware():
    """Bugfix: the capacity error printed page-pool numbers for the
    contiguous layout, where pages are meaningless."""
    big = _req(0, np.arange(64), max_new=64)
    with pytest.raises(ValueError, match=r"pool=8 pages"):
        Scheduler(PagedKVCacheManager(SV), 2).submit(big)
    with pytest.raises(ValueError) as ei:
        Scheduler(ContinuousKVCache(SV), 2).submit(big)
    assert "pages" not in str(ei.value)


def test_table_row_sentinel_for_unused_slots():
    """Bugfix: zero-filled table rows aliased physical page 0."""
    kv = PagedKVCacheManager(SV)
    kv.ensure(0, 5)
    row = kv.table_row(0)
    assert list(row[:2]) == kv.pages[0]
    assert (row[2:] == SV.num_pages).all()          # sentinel, not page 0


def test_poisoned_page0_cannot_leak_through_dead_table_slots():
    """Regression: a request whose table never references page 0 must not
    gather page-0 bytes through its unused (sentinel) slots — a NaN in a
    recycled page used to poison the PV contraction via 0 * NaN."""
    cfg = get_config("qwen2-0.5b").reduced()
    rt = Runtime(cache_dtype="bfloat16", aligned_decode=False)
    kv = PagedKVCacheManager(SV)
    kv.ensure(0, 8)                     # rid 0 owns pages 0..1
    kv.ensure(1, 8)                     # rid 1 owns pages 2..3
    cache = init_paged_attn_cache(cfg, rt, 1, SV)
    cache = dict(cache, tbl=jnp.asarray(kv.table_row(1))[None],
                 layer=jnp.int32(0))

    n = 6
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((1, n, cfg.n_kv_heads, cfg.hd)),
                    jnp.bfloat16)
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    cache = paged_write(cache, k, k, pos)
    q = jnp.asarray(rng.standard_normal((1, 1, cfg.n_heads, cfg.hd)),
                    jnp.bfloat16)
    last = jnp.asarray([n - 1], jnp.int32)

    def decode_out(c):
        kf, vf, kpos = paged_read(c, last, cfg.n_kv_heads)
        return np.asarray(attention_core(
            q, kf, vf, q_positions=last[:, None], k_positions=kpos,
            window=0, impl="full", chunk_q=512), np.float32)

    clean = decode_out(cache)
    nan = encode_kv(jnp.full((1, cfg.n_kv_heads, cfg.hd), jnp.nan),
                    cache["k"].dtype)[0][0]        # a NaN as the pool holds it
    poisoned = dict(cache, k=cache["k"].at[0, 0].set(nan),
                    v=cache["v"].at[0, 0].set(nan))
    out = decode_out(poisoned)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(clean, out)


# ------------------------------------------------- allocator property test --
def _run_sim(trace_spec, num_pages, max_new, events=(), max_queue=0):
    """Drive submit/step/preempt/finish through the real Scheduler+manager
    (model replaced by a deterministic token stream), asserting allocator
    invariants after every event.  `events` injects request-lifecycle
    hazards — ("cancel", k) aborts the k-th live request, ("expire", k)
    backdates its deadline so the step-boundary sweep retires it — and
    `max_queue` bounds admission so oversubscribed traces shed.  Structural
    invariants come from the shared checkers (`kv.check_invariants` /
    `sched.check_invariants`, the same ones the chaos harness asserts);
    the sim adds the write-discipline checks only it can make (it knows
    which page every token lands in)."""
    sv = ServingConfig(layout="paged", max_batch=2, page_size=4,
                       num_pages=num_pages, max_ctx=16, max_queue=max_queue)
    kv = PagedKVCacheManager(sv)
    sched = Scheduler(kv, max_batch=2, max_queue=max_queue)
    ps = sv.page_size
    bases = [np.arange(16, dtype=np.int32),
             1000 + np.arange(16, dtype=np.int32)]

    def check():
        kv.check_invariants()
        sched.check_invariants()

    def write(req, position):
        # COW discipline: the page a position lands in is exclusively ours
        # and not yet registered (registration == sealed/immutable)
        page = kv.pages[req.rid][position // ps]
        assert kv.refcount[page] == 1, "write into a shared page"
        assert page not in kv.page_hash, "write into a sealed page"

    rid, n_shed = 0, 0
    for arrival, base_i, L in trace_spec:
        try:
            sched.submit(_req(rid, bases[base_i][:L], max_new=max_new,
                              arrival=float(arrival)))
        except ShedError:
            n_shed += 1
            assert rid not in kv.pages        # shed before holding anything
        rid += 1
    if max_queue:
        assert len(sched.waiting) <= max_queue
    ev = list(events)
    now, guard = 0.0, 0
    while not sched.idle:
        guard += 1
        assert guard < 500
        if ev:
            kind, k = ev.pop(0)
            live = sorted({r.rid for r in sched.waiting} | set(sched.running))
            target = live[k % len(live)]
            if kind == "cancel":
                retired = sched.cancel(target, now)
                assert retired is not None and retired.outcome == CANCELLED
                assert target not in kv.pages, "cancel leaked pages"
            else:                               # backdate: expires this step
                req = sched.running.get(target) or next(
                    r for r in sched.waiting if r.rid == target)
                req.deadline = now
            check()
        for req in sched.expire(now):
            assert req.outcome == TIMEOUT
            assert req.rid not in kv.pages, "expiry leaked pages"
        check()
        for req in sched.admit(now):
            L = len(req.prefix)
            for p in range(req.n_cached, L):         # tail prefill writes
                write(req, p)
            req.n_cached = L
            kv.register_upto(req.rid, req.prefix, L)
            req.tokens.append(int(req.prefix[-1]) + 1)
            check()
        sched.ensure_decode()
        check()
        for req in list(sched.batch()):
            write(req, req.n_cached)                 # decode write
            req.n_cached += 1
            req.tokens.append(req.tokens[-1] + 1)
            if req.n_cached % ps == 0:
                kv.register_upto(req.rid, req.prefix, req.n_cached)
            check()
            if req.done:
                sched.finish(req, now)
                check()
        now += 1.0
    assert kv.in_use == 0, "drained scheduler left pages held"


@given(st.lists(
    st.sampled_from([(a, b, L)
                     for a in (0, 1, 2) for b in (0, 1)
                     for L in (3, 5, 8, 10)]),
    min_size=1, max_size=6),
    st.sampled_from([4, 6, 8]))
@settings(max_examples=25, deadline=None)
def test_allocator_invariants_under_random_traces(spec, num_pages):
    _run_sim(spec, num_pages, max_new=4)


@given(st.lists(
    st.sampled_from([(a, b, L)
                     for a in (0, 1, 2) for b in (0, 1)
                     for L in (3, 5, 8, 10)]),
    min_size=2, max_size=8),
    st.sampled_from([4, 6, 8]),
    st.lists(st.tuples(st.sampled_from(["cancel", "expire"]),
                       st.integers(0, 7)), max_size=6),
    st.sampled_from([0, 2, 3]))
@settings(max_examples=25, deadline=None)
def test_allocator_invariants_under_lifecycle_events(spec, num_pages,
                                                     events, max_queue):
    """Hardening: cancels, deadline expiries, and bounded-queue shedding
    interleaved with admission/preemption/finish must preserve every
    allocator invariant and leak no pages."""
    _run_sim(spec, num_pages, max_new=4, events=events, max_queue=max_queue)


# ------------------------------------------------------------- engine e2e --
@pytest.fixture(scope="module")
def reduced_cfg():
    return get_config("qwen2-0.5b").reduced()


def _engine(cfg, *, prefix_cache, num_pages=32, page_size=8, max_ctx=64,
            layout="paged"):
    rt = Runtime(quant_backend="float", cache_dtype="bfloat16", remat="none",
                 loss_chunk=0)
    sv = ServingConfig(layout=layout, max_batch=2, page_size=page_size,
                       num_pages=num_pages, max_ctx=max_ctx,
                       prefix_cache=prefix_cache)
    return InferenceEngine(cfg, rt, sv, seed=0)


def test_shared_prefix_hits_are_bit_identical_and_profitable(reduced_cfg):
    """Acceptance: with prefix_cache=on a shared-system-prompt trace decodes
    token-identically to the cold run, with hit rate > 0.5 and measurably
    fewer prefilled tokens."""
    trace = shared_prefix_trace(6, 1.0, 16, [8], [4], reduced_cfg.vocab,
                                seed=3)
    s_on, fin_on = run_trace(_engine(reduced_cfg, prefix_cache=True), trace)
    s_off, fin_off = run_trace(_engine(reduced_cfg, prefix_cache=False),
                               trace)
    assert [r.tokens for r in fin_on] == [r.tokens for r in fin_off]
    assert s_on["prefix_hit_rate"] > 0.5
    assert s_on["tokens_prefilled_saved"] > 0
    assert s_on["prefill_tokens"] < s_off["prefill_tokens"]
    assert s_off["tokens_prefilled_saved"] == 0


def test_shared_prefix_matches_contiguous(reduced_cfg):
    """Cache-hit prefills must agree with the contiguous layout too (the
    second cold reference of the compare harness)."""
    trace = shared_prefix_trace(4, 1.0, 16, [8], [4], reduced_cfg.vocab,
                                seed=11)
    _, fin_p = run_trace(_engine(reduced_cfg, prefix_cache=True), trace)
    _, fin_c = run_trace(_engine(reduced_cfg, prefix_cache=False,
                                 layout="contiguous"), trace)
    assert [r.tokens for r in fin_p] == [r.tokens for r in fin_c]


def test_preempt_resume_reprefills_only_uncached_suffix(reduced_cfg):
    """Bugfix: a preempted victim whose prefix pages survive in the warm
    pool re-admits at its hit length instead of re-prefilling everything."""
    rt = Runtime(quant_backend="float", cache_dtype="bfloat16", remat="none",
                 loss_chunk=0)
    sv = ServingConfig(layout="paged", max_batch=2, page_size=4, num_pages=6,
                       max_ctx=16)
    engine = InferenceEngine(reduced_cfg, rt, sv, seed=0)
    trace = poisson_trace(4, 2.0, [8], [8], reduced_cfg.vocab, seed=9)
    stats, fin = run_trace(engine, trace)
    assert stats["requests_finished"] == 4
    assert stats["requests_preempted"] >= 1
    assert stats["tokens_prefilled_saved"] > 0      # resume hit the cache
    # identical tokens vs an unconstrained run (no preemption, no resume)
    _, fin_big = run_trace(
        _engine(reduced_cfg, prefix_cache=True, page_size=8, max_ctx=32),
        trace)
    assert [r.tokens for r in fin] == [r.tokens for r in fin_big]

"""Continuous-batching serving engine: page allocator, scheduler policies
(admission ordering, exhaustion -> preemption -> resume, block-table reuse),
and bit-exactness of paged vs contiguous KV attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import Runtime, ServingConfig, get_config
from repro.models.attention import _cache_read, _cache_write, init_attn_cache
from repro.serving.api import poisson_trace, run_trace
from repro.serving.engine import InferenceEngine
from repro.serving.kv_pages import (
    PagedKVCacheManager,
    init_paged_attn_cache,
    paged_read,
    paged_write,
)
from repro.serving.scheduler import Request, Scheduler


SV = ServingConfig(layout="paged", max_batch=2, page_size=4, num_pages=8,
                   max_ctx=16)


def _req(rid, L=4, max_new=4, arrival=0.0):
    return Request(rid=rid, prompt=np.arange(L, dtype=np.int32),
                   max_new=max_new, arrival=arrival)


# ------------------------------------------------------------ page manager --
def test_page_manager_alloc_release_reuse():
    kv = PagedKVCacheManager(SV)
    assert kv.ensure(0, 6)            # 2 pages
    assert kv.ensure(1, 9)            # 3 pages
    assert kv.in_use == 5
    first = list(kv.table_row(0)[:2])
    # growth is incremental: +1 page for 12 tokens
    assert kv.ensure(0, 12) and kv.in_use == 6
    assert list(kv.table_row(0)[:2]) == first       # existing pages stable
    # exhaustion: 3 more pages don't exist
    assert not kv.ensure(2, 10)
    assert kv.in_use == 6                           # all-or-nothing
    # release -> the pages are reusable by a new sequence
    kv.release(0)
    assert kv.available == 5
    assert kv.ensure(2, 10)
    assert set(kv.table_row(2)[:3]) <= set(range(SV.num_pages))


def test_page_manager_respects_max_ctx():
    kv = PagedKVCacheManager(SV)
    assert not kv.ensure(0, SV.max_ctx + 1)
    assert not kv.fits_alone(SV.max_ctx + 1)


# --------------------------------------------------------------- scheduler --
def test_admission_fifo_order_and_slots():
    sched = Scheduler(PagedKVCacheManager(SV), max_batch=2)
    for rid in range(3):
        sched.submit(_req(rid))
    admitted = sched.admit(now=0.0)
    assert [r.rid for r in admitted] == [0, 1]      # FIFO
    assert [r.rid for r in sched.batch()] == [0, 1]  # slot order
    assert admitted[0].slot == 0 and admitted[1].slot == 1
    assert [r.rid for r in sched.waiting] == [2]
    # a future arrival is not admitted even with a free slot
    sched.finish(admitted[0], now=1.0)
    sched.submit(_req(3, arrival=99.0))
    admitted = sched.admit(now=1.0)
    assert [r.rid for r in admitted] == [2]
    assert admitted[0].slot == 0                    # freed slot reused


def test_exhaustion_preempts_latest_then_resumes():
    sv = ServingConfig(layout="paged", max_batch=2, page_size=4,
                       num_pages=4, max_ctx=16)
    sched = Scheduler(PagedKVCacheManager(sv), max_batch=2)
    a, b = _req(0, L=7, max_new=9), _req(1, L=4, max_new=4)
    sched.submit(a)
    sched.submit(b)
    assert len(sched.admit(now=0.0)) == 2           # 2 + 2 pages (prefix+1)
    a.n_cached, b.n_cached = 7, 4
    # a grows to 9, 11 cached tokens: needs a 3rd page -> pool dry -> the
    # latest-admitted request (b) is preempted back to the queue front
    a.n_cached = 11
    preempted = sched.ensure_decode()
    assert [r.rid for r in preempted] == [1]
    assert b.state == "waiting" and b.n_preempts == 1 and b.n_cached == 0
    assert sched.waiting[0] is b
    assert [r.rid for r in sched.batch()] == [0]
    # resume: once a finishes, b re-admits and re-allocates
    sched.finish(a, now=5.0)
    assert [r.rid for r in sched.admit(now=5.0)] == [1]
    assert sched.kv.ensure(1, 4)


def test_preemption_keeps_generated_prefix():
    sv = ServingConfig(layout="paged", max_batch=2, page_size=4,
                       num_pages=4, max_ctx=16)
    sched = Scheduler(PagedKVCacheManager(sv), max_batch=2)
    b = _req(1, L=4, max_new=8)
    sched.submit(_req(0, L=8, max_new=8))
    sched.submit(b)
    sched.admit(now=0.0)
    b.tokens.extend([7, 8, 9])
    sched.running[0].n_cached = 12
    sched.ensure_decode()
    # recompute-style preemption: prefix carries generated tokens for resume
    assert list(b.prefix) == [0, 1, 2, 3, 7, 8, 9]


# ------------------------------------------- paged vs contiguous KV caches --
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8", "int4"])
def test_paged_read_bit_identical_to_contiguous(cache_dtype):
    cfg = get_config("qwen2-0.5b").reduced()
    rt = Runtime(cache_dtype=cache_dtype, aligned_decode=False)
    sv = ServingConfig(layout="paged", max_batch=2, page_size=4,
                       num_pages=16, max_ctx=16)
    B, n = 2, 10
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((B, n, cfg.n_kv_heads, cfg.hd)),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, n, cfg.n_kv_heads, cfg.hd)),
                    jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))

    cont = init_attn_cache(cfg, rt, B, sv.max_ctx)
    cont = _cache_write(cont, k, v, pos)
    kc, vc = _cache_read(cont)

    # non-trivial block tables: permuted page order per row
    kv_mgr = PagedKVCacheManager(sv)
    kv_mgr.ensure(99, 5)               # burn pages so tables aren't 0,1,2..
    kv_mgr.ensure(0, n)
    kv_mgr.release(99)
    kv_mgr.ensure(1, n)
    tbl = jnp.asarray(np.stack([kv_mgr.table_row(0), kv_mgr.table_row(1)]))
    # the middle layer of a three-layer stack
    paged = init_paged_attn_cache(cfg, rt, B, sv, layers=3)
    paged = dict(paged, tbl=tbl, layer=jnp.int32(1))
    paged = paged_write(paged, k, v, pos)
    kp, vp, kpos = paged_read(paged, jnp.full((B,), n - 1, jnp.int32),
                              cfg.n_kv_heads)

    valid = np.asarray(kpos) >= 0
    assert valid[:, :n].all() and not valid[:, n:].any()
    np.testing.assert_array_equal(np.asarray(kc, np.float32)[:, :n],
                                  np.asarray(kp, np.float32)[:, :n])
    np.testing.assert_array_equal(np.asarray(vc, np.float32)[:, :n],
                                  np.asarray(vp, np.float32)[:, :n])
    np.testing.assert_array_equal(np.asarray(cont["kpos"])[:, :n],
                                  np.asarray(kpos)[:, :n])


def test_negative_positions_are_dropped():
    """Left-pad and inactive-row writes must not touch any page."""
    cfg = get_config("qwen2-0.5b").reduced()
    rt = Runtime(cache_dtype="bfloat16", aligned_decode=False)
    sv = ServingConfig(layout="paged", max_batch=1, page_size=4,
                       num_pages=4, max_ctx=16)
    paged = init_paged_attn_cache(cfg, rt, 1, sv, layers=2)
    paged = dict(paged, tbl=jnp.arange(4, dtype=jnp.int32)[None],
                 layer=jnp.int32(1))
    k = jnp.ones((1, 3, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    out = paged_write(paged, k, k, jnp.asarray([[-2, -1, 5]], jnp.int32))
    pool = np.asarray(out["k"]).reshape(2, 16, -1)
    assert pool[1, 5].all()                         # the valid slot landed
    assert (pool[1, [0, 1, 2, 3, 4]] == 0).all()
    assert (pool[0] == 0).all()                     # the other layer


# ------------------------------------------------------------- engine e2e --
@pytest.fixture(scope="module")
def reduced_cfg():
    return get_config("qwen2-0.5b").reduced()


def _engine(cfg, layout, num_pages=32, seed=0):
    rt = Runtime(quant_backend="float", cache_dtype="bfloat16", remat="none",
                 loss_chunk=0)
    sv = ServingConfig(layout=layout, max_batch=2, page_size=8,
                       num_pages=num_pages, max_ctx=32)
    return InferenceEngine(cfg, rt, sv, seed=seed)


def test_engine_paged_vs_contiguous_bit_identical(reduced_cfg):
    trace = poisson_trace(4, 1.0, [8], [4], reduced_cfg.vocab, seed=5)
    _, fin_p = run_trace(_engine(reduced_cfg, "paged"), trace)
    _, fin_c = run_trace(_engine(reduced_cfg, "contiguous"), trace)
    assert [r.tokens for r in fin_p] == [r.tokens for r in fin_c]
    assert all(len(r.tokens) == 4 for r in fin_p)


def test_engine_preemption_resume_completes(reduced_cfg):
    # 6 pages of 4 tokens for 2 slots of up to 8+8 tokens: decode pressure
    rt = Runtime(quant_backend="float", cache_dtype="bfloat16", remat="none")
    sv = ServingConfig(layout="paged", max_batch=2, page_size=4,
                       num_pages=6, max_ctx=16)
    engine = InferenceEngine(reduced_cfg, rt, sv, seed=0)
    trace = poisson_trace(4, 2.0, [8], [8], reduced_cfg.vocab, seed=9)
    stats, fin = run_trace(engine, trace)
    assert stats["requests_finished"] == 4
    assert all(len(r.tokens) == 8 for r in fin)
    assert stats["requests_preempted"] >= 1
    # preemption is recompute-style and greedy decode is deterministic:
    # an unconstrained pool must produce the same tokens
    _, fin_big = run_trace(_engine(reduced_cfg, "paged", num_pages=32), trace)
    assert [r.tokens for r in fin] == [r.tokens for r in fin_big]

"""Ragged token-major serving step: kernel twin parity, token-budget
planner, engine bit-identity vs the bucketed step under batch-composition
churn, budget growth (a compile, never a steady-state recompile), and the
packing-waste telemetry both step modes share."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import Runtime, ServingConfig, get_config
from repro.kernels.ragged_attention import (
    ragged_attention_xla,
    ragged_decode_attention,
)
from repro.serving.api import bursty_trace, mixed_trace, run_trace
from repro.serving.engine import InferenceEngine
from repro.serving.kv_pages import (
    PagedKVCacheManager,
    encode_kv,
    pool_storage,
)
from repro.serving.scheduler import Request, Scheduler


# ------------------------------------------------- kernel vs XLA twin -----
#: the pools here are layer-stacked over LAYERS layers, read at LAYER
LAYERS, LAYER = 2, 1


def _pool(rng, P, ps, KV, hd, dtype):
    """A random layer-stacked pool leaf as the serving cache holds it, and
    its scales (None for a float pool)."""
    vals = rng.standard_normal((LAYERS, P, ps, KV, hd)).astype(np.float32)
    return encode_kv(jnp.asarray(vals), pool_storage(dtype))


@pytest.mark.parametrize("ps", [1, 4, 16])
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8", "int4"])
def test_ragged_kernel_matches_xla_twin(ps, cache_dtype):
    """The Pallas ragged kernel (interpret mode off-TPU) and its pure-XLA
    twin agree on every packed row and emit exact zeros on padding rows,
    across page sizes and pool dtypes."""
    rng = np.random.default_rng(seed=ps * 7 + len(cache_dtype))
    P, KV, G, hd = 8, 2, 2, 8
    H = KV * G
    pps = 3                                        # pages per sequence
    maxB = 3
    k_pool, k_scale = _pool(rng, P, ps, KV, hd, cache_dtype)
    v_pool, v_scale = _pool(rng, P, ps, KV, hd, cache_dtype)
    # distinct physical pages per table row; row 2 left at the sentinel P
    # (a dead slot) so clamped fetches must mask to zero contribution
    tbl = np.full((maxB, pps), P, np.int32)
    perm = rng.permutation(P)[: 2 * pps].reshape(2, pps)
    tbl[:2] = perm
    # packed rows: two live slots at assorted positions + interior padding
    token_slot = np.asarray([0, 1, -1, 0, 1, -1], np.int32)
    max_pos = pps * ps - 1
    token_pos = np.asarray(
        [0, max_pos, -1, max_pos // 2, max_pos // 3, -1], np.int32)
    T = token_slot.shape[0]
    q = jnp.asarray(rng.standard_normal((T, H, hd)), jnp.bfloat16)

    for pp in (1, 2):
        out_k = ragged_decode_attention(
            q, k_pool, v_pool, jnp.asarray(tbl), jnp.asarray(token_slot),
            jnp.asarray(token_pos), LAYER, k_scale, v_scale, pp=pp,
            interpret=True)
        out_x = ragged_attention_xla(
            q, k_pool, v_pool, jnp.asarray(tbl), jnp.asarray(token_slot),
            jnp.asarray(token_pos), LAYER, k_scale, v_scale, pp=pp)
        a = np.asarray(out_k, np.float32)
        b = np.asarray(out_x, np.float32)
        assert np.max(np.abs(a - b)) < 2e-2, (ps, cache_dtype, pp)
        assert (a[token_slot < 0] == 0).all()
        assert (b[token_slot < 0] == 0).all()


# --------------------------------------------------- plan_tokens ----------
def _sched(max_batch=4, num_pages=32, page_size=4, max_ctx=32):
    sv = ServingConfig(layout="paged", max_batch=max_batch,
                       page_size=page_size, num_pages=num_pages,
                       max_ctx=max_ctx)
    return Scheduler(PagedKVCacheManager(sv), max_batch=max_batch)


def test_plan_tokens_decode_first_then_fifo_chunks():
    sched = _sched()
    for rid, L in enumerate((6, 10, 5)):
        sched.submit(Request(rid=rid, prompt=np.arange(L, dtype=np.int32),
                             max_new=4))
    sched.admit(now=0.0)
    # rid 0 already decoding (emitted once), rids 1-2 still in prefill
    r0 = sched.running[0]
    r0.n_cached, r0.decoding = 6, True
    r0.tokens.append(1)
    plan = sched.plan_tokens(8)
    # decode token first (slot order), then prefill chunks oldest-admit
    # first; rid 1 takes 7 of the remaining budget, rid 2 gets none
    assert [(r.rid, s, n) for r, s, n in plan] == [(0, 6, 1), (1, 0, 7)]
    # next step (after rid 1 cached those 7): rid 1 finishes its prefix,
    # leftover budget flows to rid 2
    sched.running[1].n_cached = 7
    plan = sched.plan_tokens(8)
    assert [(r.rid, s, n) for r, s, n in plan] == \
        [(0, 6, 1), (1, 7, 3), (2, 0, 4)]
    # a budget smaller than the decode set still plans only decode tokens
    r1, r2 = sched.running[1], sched.running[2]
    r1.n_cached, r1.decoding = 10, True
    r2.n_cached, r2.decoding = 5, True
    plan = sched.plan_tokens(2)
    assert [(r.rid, n) for r, _, n in plan] == [(0, 1), (1, 1)]


# ----------------------------------------- engine: ragged == bucketed -----
@functools.lru_cache(maxsize=1)
def _cfg():
    return get_config("qwen2-0.5b").reduced()


def _engines(cfg, *, cache_dtype, page_size, token_budget, num_pages=48):
    """(bucketed, ragged) engine pair over identical params/pool geometry.
    Lossy pools prefill over the cache on the bucketed side too — that is
    what the ragged step inherently does (write-then-attend), and the only
    configuration where per-token math can match bit-for-bit."""
    rt = Runtime(quant_backend="float", cache_dtype=cache_dtype,
                 remat="none", loss_chunk=0,
                 prefill_over_cache=(cache_dtype != "bfloat16"))
    mk = lambda step, tb: InferenceEngine(
        cfg, rt,
        ServingConfig(layout="paged", max_batch=4, page_size=page_size,
                      num_pages=num_pages, max_ctx=64, step=step,
                      token_budget=tb),
        seed=0)
    return mk("bucketed", 0), mk("ragged", token_budget)


@given(st.sampled_from([
    ("bfloat16", 4, 0, "mixed"),       # auto budget
    ("bfloat16", 1, 6, "bursty"),      # 1-token pages, tight budget
    ("bfloat16", 16, 8, "mixed"),
    ("int8", 4, 6, "bursty"),
    ("int4", 4, 9, "mixed"),
    ("bfloat16", 4, 5, "bursty"),      # odd budget, chunk boundaries shift
]), st.integers(0, 3))
@settings(max_examples=6, deadline=None)
def test_ragged_step_bit_identical_to_bucketed(spec, seed):
    """Property: under interleaved admissions, chunked prefills and decodes
    the ragged step emits exactly the bucketed engine's tokens — across
    page sizes {1,4,16}, bf16/int8/int4 pools, and budget choices that
    split prefixes at different chunk boundaries."""
    cache_dtype, ps, tb, kind = spec
    trace = (mixed_trace(6, [5, 9, 14], [3, 4], _cfg().vocab, seed=seed)
             if kind == "mixed" else
             bursty_trace(6, 3, 3, [5, 9, 14], [3, 4], _cfg().vocab,
                          seed=seed))
    num_pages = 96 if ps == 1 else 48
    eng_b, eng_r = _engines(_cfg(), cache_dtype=cache_dtype,
                            page_size=ps, token_budget=tb,
                            num_pages=num_pages)
    s_b, fin_b = run_trace(eng_b, trace)
    s_r, fin_r = run_trace(eng_r, trace)
    assert [r.tokens for r in fin_r] == [r.tokens for r in fin_b]
    assert s_r["recompiles"]["steady_state"] == 0
    # one compiled signature regardless of batch composition (no growth:
    # these budgets all cover max_batch)
    assert s_r["recompiles"]["by_fn"]["ragged"] == 1


def test_ragged_preemption_resume_bit_identical():
    """Pool pressure: both engines preempt and resume; tokens still match
    (recompute-style resume over a bf16 pool is lossless)."""
    trace = mixed_trace(5, [9, 14], [6], _cfg().vocab, seed=2)
    eng_b, eng_r = _engines(_cfg(), cache_dtype="bfloat16",
                            page_size=4, token_budget=8, num_pages=14)
    _, fin_b = run_trace(eng_b, trace)
    s_r, fin_r = run_trace(eng_r, trace)
    assert [r.tokens for r in fin_r] == [r.tokens for r in fin_b]
    assert s_r["requests_preempted"] >= 1
    assert s_r["recompiles"]["steady_state"] == 0


# ------------------------------------------------------ budget growth -----
def test_budget_growth_is_a_compile_not_a_recompile():
    """An explicit token_budget below max_batch doubles the step the decode
    set outgrows it: the budget metric bumps, the `compiles` count grows,
    steady_state stays zero, and tokens still match the bucketed run."""
    # short prompts + long generations: two requests decode simultaneously
    # while a third still prefills, so demand (2 decode + 1 chunk slot)
    # outgrows the budget of 2
    trace = mixed_trace(5, [3, 4], [6], _cfg().vocab, seed=1)
    eng_b, eng_r = _engines(_cfg(), cache_dtype="bfloat16",
                            page_size=4, token_budget=2)
    assert eng_r.stats()["token_budget"] == 2
    _, fin_b = run_trace(eng_b, trace)
    s_r, fin_r = run_trace(eng_r, trace)
    assert [r.tokens for r in fin_r] == [r.tokens for r in fin_b]
    grows = eng_r.metrics.counter("ragged_budget_grows_total").value
    assert grows >= 1
    assert s_r["token_budget"] >= 4                # 2 -> 4 at least once
    assert s_r["recompiles"]["by_fn"]["ragged"] == 1 + grows
    assert s_r["recompiles"]["steady_state"] == 0


# -------------------------------------------------- packing telemetry -----
def test_padding_waste_metrics_both_step_modes():
    """padding_tokens_wasted / token_utilization are live in both step
    modes: the ragged engine charges unused budget rows, the bucketed
    engine charges prefill-bucket and decode-bucket padding."""
    trace = mixed_trace(4, [5, 9], [3], _cfg().vocab, seed=0)
    eng_b, eng_r = _engines(_cfg(), cache_dtype="bfloat16",
                            page_size=4, token_budget=8)
    s_b, _ = run_trace(eng_b, trace)
    s_r, _ = run_trace(eng_r, trace)
    for s in (s_b, s_r):
        assert s["padding_tokens_wasted"] > 0       # 5/9 prompts never
        assert 0.0 < s["token_utilization"] <= 1.0  # align to buckets/budget
        assert s["padding_tokens_wasted"] == \
            eng_b.metrics.counter("padding_tokens_wasted_total").value \
            if s is s_b else True
    # accounting closes: packed + wasted == steps * capacity consumed
    assert eng_r.metrics.counter("padding_tokens_wasted_total").value == \
        s_r["padding_tokens_wasted"]

"""Fused paged-attention kernels: parity with the gather-then-attend
reference across page sizes, ragged contexts, cache dtypes, and window
masking; flash prefill vs the dense core; engine-level bit-exactness of the
fused path including a preempt->resume trace; autotune attn tags."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import Runtime, ServingConfig, get_config
from repro.kernels import autotune, ops
from repro.kernels import paged_attention as pa
from repro.kernels.ragged_attention import (
    ragged_attention_xla,
    ragged_decode_attention,
)
from repro.models.attention import attention_core, dequantize_kv
from repro.serving.api import poisson_trace, run_trace
from repro.serving.engine import InferenceEngine, build_params
from repro.serving.kv_pages import encode_kv, paged_read, pool_storage

#: every pool here is a layer-stacked one of LAYERS layers, read at LAYER
LAYERS, LAYER = 3, 1


def _pool_setup(rng, B, KV, hd, ps, pps, cache_dtype="bfloat16",
                layers=LAYERS, layer=LAYER):
    """Random layer-stacked, lane-dense pool (as the serving cache holds
    it) + permuted block tables (pages deliberately scattered), read at
    `layer`."""
    P = B * pps + 4
    k32 = jnp.asarray(rng.standard_normal((layers, P, ps, KV, hd)),
                      jnp.float32)
    v32 = jnp.asarray(rng.standard_normal((layers, P, ps, KV, hd)),
                      jnp.float32)
    tbl = jnp.asarray(rng.permutation(P)[: B * pps].reshape(B, pps),
                      jnp.int32)
    cache = {"tbl": tbl, "layer": jnp.int32(layer)}
    for name, val in (("k", k32), ("v", v32)):
        cache[name], scale = encode_kv(val, pool_storage(cache_dtype))
        if scale is not None:
            cache[name + "_scale"] = scale
    return cache


def _reference(q, cache, last, window=0):
    """The gather path: paged_read + dense attention (masked softmax)."""
    kv_heads = pa.pool_heads(cache["k"], q.shape[-1])
    kf, vf, kpos = paged_read(cache, last, kv_heads)
    return attention_core(
        q[:, None], kf, vf, q_positions=last[:, None], k_positions=kpos,
        window=window, impl="full", chunk_q=64)[:, 0]


# ----------------------------------------------------------- decode parity --
@pytest.mark.parametrize("ps,pps", [(1, 16), (4, 8), (16, 2)])
def test_decode_xla_twin_bit_identical_across_page_sizes(ps, pps):
    """The XLA twin (what CPU serving executes) must be *bit-identical* to
    the gather reference for ragged per-row contexts, page-partial
    positions, and inactive rows."""
    rng = np.random.default_rng(ps)
    B, KV, G, hd = 4, 4, 2, 16
    cache = _pool_setup(rng, B, KV, hd, ps, pps)
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd)), jnp.bfloat16)
    # ragged: mid-page, page-boundary, full, inactive
    last = jnp.asarray([ps * pps // 2 - 1, ps - 1, ps * pps - 1, -1],
                       jnp.int32)
    ref = _reference(q, cache, last)
    out = pa.paged_decode_attention_xla(q, cache["k"], cache["v"],
                                        cache["tbl"], last, cache["layer"],
                                        pp=3)
    act = np.asarray(last) >= 0
    np.testing.assert_array_equal(np.float32(out)[act], np.float32(ref)[act])
    # inactive rows are masked to zero (finite, never NaN)
    assert not np.isnan(np.float32(out)).any()
    assert (np.float32(out)[~act] == 0).all()


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8", "int4"])
def test_decode_xla_twin_quantized_pools(cache_dtype):
    rng = np.random.default_rng(7)
    B, KV, G, hd, ps, pps = 3, 4, 2, 16, 4, 6
    cache = _pool_setup(rng, B, KV, hd, ps, pps, cache_dtype)
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd)), jnp.bfloat16)
    last = jnp.asarray([ps * pps - 1, 5, 0], jnp.int32)
    ref = _reference(q, cache, last)
    out = pa.paged_decode_attention_xla(
        q, cache["k"], cache["v"], cache["tbl"], last, cache["layer"],
        cache.get("k_scale"), cache.get("v_scale"), pp=2)
    np.testing.assert_array_equal(np.float32(out), np.float32(ref))


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
def test_decode_pallas_kernel_matches_reference(cache_dtype):
    """The Pallas kernel (interpret mode) runs single-pass online softmax:
    tolerance parity with the dense reference, inactive rows masked."""
    rng = np.random.default_rng(11)
    B, KV, G, hd, ps, pps = 3, 4, 2, 16, 4, 6
    cache = _pool_setup(rng, B, KV, hd, ps, pps, cache_dtype)
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd)), jnp.bfloat16)
    last = jnp.asarray([ps * pps - 1, 9, -1], jnp.int32)
    ref = _reference(q, cache, last)
    for pp, bkv in [(1, 0), (4, 2)]:
        out = pa.paged_decode_attention(
            q, cache["k"], cache["v"], cache["tbl"], last, cache["layer"],
            cache.get("k_scale"), cache.get("v_scale"),
            pp=pp, bkv=bkv, interpret=True)
        act = np.asarray(last) >= 0
        np.testing.assert_allclose(np.float32(out)[act],
                                   np.float32(ref)[act], atol=2e-2)
        assert (np.float32(out)[~act] == 0).all()


# rows ending mid-page (5), on a page's last slot (7), on a page's first
# slot (8), on a 4-page block's last and first slot (15, 16), at position 0
# and at max_ctx - 1 (31), and an idle row; ps 4, 8 pages a row
_ENDS = [5, 7, 8, 15, 16, 0, 31, -1]
_PS, _PPS = 4, 8


def _walk(kernel, q, cache, last, window=0, pp=4):
    """Run one of the two page-walking kernels (interpret mode) over a
    batch of query rows; the ragged kernel gets each row as one token of
    its own table row (idle rows as padding, slot -1)."""
    scales = cache.get("k_scale"), cache.get("v_scale")
    if kernel == "paged":
        return pa.paged_decode_attention(
            q, cache["k"], cache["v"], cache["tbl"], last, cache["layer"],
            *scales, window=window, pp=pp, interpret=True)
    slot = jnp.where(last >= 0, jnp.arange(last.shape[0], dtype=jnp.int32),
                     -1)
    return ragged_decode_attention(
        q, cache["k"], cache["v"], cache["tbl"], slot, last, cache["layer"],
        *scales, window=window, pp=pp, interpret=True)


@pytest.mark.parametrize("window", [0, 6])
@pytest.mark.parametrize("pp", [1, 4, _PPS])
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("kernel", ["paged", "ragged"])
def test_decode_kernels_match_reference_at_row_ends(kernel, cache_dtype, pp,
                                                    window):
    """Both page-walking kernels stop at each row's last live page; rows
    that end anywhere in a page or a page block, and idle rows, still
    match the gather reference."""
    rng = np.random.default_rng(pp * 10 + window)
    KV, G, hd = 2, 2, 16
    cache = _pool_setup(rng, len(_ENDS), KV, hd, _PS, _PPS, cache_dtype)
    q = jnp.asarray(rng.standard_normal((len(_ENDS), KV * G, hd)),
                    jnp.bfloat16)
    last = jnp.asarray(_ENDS, jnp.int32)
    ref = _reference(q, cache, last, window=window)
    out = np.float32(_walk(kernel, q, cache, last, window=window, pp=pp))
    act = np.asarray(last) >= 0
    np.testing.assert_allclose(out[act], np.float32(ref)[act], atol=2e-2)
    assert (out[~act] == 0).all()


@pytest.mark.parametrize("window", [0, 6])
@pytest.mark.parametrize("pp", [1, 3, 4, _PPS])
def test_live_page_fetches_each_live_page_once(pp, window):
    """The index map of the page walk: every live page maps to itself, no
    spec ever leaves the row's live pages, and past the row's end a spec's
    page stops changing from one block to the next, so the pipeline copies
    each live page once, the row's last page once more for each spec that
    is never live, and no dead page at all."""
    nj = -(-_PPS // pp)
    j = np.arange(nj, dtype=np.int32)[:, None]
    u = np.arange(pp, dtype=np.int32)[None, :]
    lasts = np.arange(-1, _PS * _PPS, dtype=np.int32)
    pages = np.asarray(jnp.vectorize(functools.partial(
        pa.live_page, pp=pp, ps=_PS, window=window))(
            j[None], u[None], lasts[:, None, None]))
    for last, page in zip(lasts.tolist(), pages):
        end = max(last, 0) // _PS
        first = max(last - window + 1, 0) // _PS if window else 0
        logical = j * pp + u
        live = (logical >= first) & (logical <= end) & (last >= 0)
        assert (page[live] == logical[live]).all(), last
        assert ((page >= first) & (page <= end)).all(), last
        fetched = np.ones_like(page, bool)
        fetched[1:] = page[1:] != page[:-1]
        assert not (fetched & (logical > end))[1:].any(), last
        if not window:
            assert fetched.sum() == live.sum() + (last < 0) + max(
                0, pp - end - 1), last


def _nan(leaf):
    """A NaN as `leaf` holds it (a float pool holds its values' bits)."""
    if leaf.dtype in pa.FLOAT_OF_BITS:
        return jax.lax.bitcast_convert_type(
            jnp.asarray(jnp.nan, pa.FLOAT_OF_BITS[leaf.dtype]), leaf.dtype)
    return jnp.nan


def _poisoned_pool(rng, B, KV, hd, ends, cache_dtype):
    """A pool in which every page of the read layer that holds no live
    position of any row, and every page of the other layers, is NaN (float
    pools) or has NaN scales (int8), with the clean pool the reference
    reads.  Rows own distinct pages drawn from all but the last physical
    page; past its last live page a row's table holds the out-of-bounds
    sentinel (even rows, clamped to the last page) or other dead pages
    (odd rows)."""
    P = B * _PPS + 4
    clean = _pool_setup(rng, B, KV, hd, _PS, _PPS, cache_dtype)
    n_live = [e // _PS + 1 if e >= 0 else 0 for e in ends]
    perm = rng.permutation(P - 1)
    tbl = np.full((B, _PPS), P, np.int32)
    off = 0
    for b, n in enumerate(n_live):
        tbl[b, :n] = perm[off:off + n]
        off += n
    live = set(perm[:off].tolist())
    dead = np.asarray([p for p in range(P) if p not in live])
    for b, n in enumerate(n_live):
        if b % 2:
            tbl[b, n:] = rng.choice(dead, _PPS - n)
    clean["tbl"] = jnp.asarray(tbl)
    poisoned = dict(clean)
    names = ("k_scale", "v_scale") if "k_scale" in clean else ("k", "v")
    others = np.asarray([r for r in range(LAYERS) if r != LAYER])
    for name in names:
        nan = _nan(clean[name])
        poisoned[name] = clean[name].at[LAYER, dead].set(nan).at[
            others].set(nan)
    return clean, poisoned


@pytest.mark.parametrize("pp", [1, 4])
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("kernel", ["paged", "ragged"])
def test_decode_kernels_never_read_dead_pages(kernel, cache_dtype, pp):
    """Dead pages are neither attended nor multiplied by a zero weight: a
    NaN in every page that holds no live position (the pages a row's
    table maps past its end, and the last physical page, where sentinel
    slots clamp) leaves live rows finite and equal to the reference, and
    idle rows, and an all-idle batch, exactly zero."""
    rng = np.random.default_rng(31 + pp)
    KV, G, hd = 2, 2, 16
    B = len(_ENDS)
    clean, poisoned = _poisoned_pool(rng, B, KV, hd, _ENDS, cache_dtype)
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd)), jnp.bfloat16)
    last = jnp.asarray(_ENDS, jnp.int32)
    act = np.asarray(last) >= 0
    out = np.float32(_walk(kernel, q, poisoned, last, pp=pp))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[act], np.float32(_reference(q, clean,
                                                               last))[act],
                               atol=2e-2)
    assert (out[~act] == 0).all()
    idle = jnp.full((B,), -1, jnp.int32)
    assert (np.float32(_walk(kernel, q, poisoned, idle, pp=pp)) == 0).all()


def _twin(kernel, q, cache, last):
    """The XLA twin of one of the two page walks, called as `_walk` calls
    the kernel."""
    scales = cache.get("k_scale"), cache.get("v_scale")
    if kernel == "paged":
        return pa.paged_decode_attention_xla(
            q, cache["k"], cache["v"], cache["tbl"], last, cache["layer"],
            *scales)
    slot = jnp.where(last >= 0, jnp.arange(last.shape[0], dtype=jnp.int32),
                     -1)
    return ragged_attention_xla(
        q, cache["k"], cache["v"], cache["tbl"], slot, last, cache["layer"],
        *scales)


def _slice_reference(q, cache, r, last):
    """Dense attention over layer r's slice of the stack viewed [P, ps, KV,
    hd], the layout a pool had before it was stacked: its values decoded,
    each row's pages gathered through its table (sentinel pages zero) —
    no layer index and no paged_read."""
    B, H, hd = q.shape
    KV = pa.pool_heads(cache["k"], hd)
    P, ps = cache["k"].shape[1:3]
    tbl = np.asarray(cache["tbl"])
    ctx = tbl.shape[1] * ps

    def dense(name):
        pages = cache[name][r].reshape(P, ps, KV, -1)[np.minimum(tbl, P - 1)]
        if "k_scale" in cache:
            scale = cache[name + "_scale"][r][np.minimum(tbl, P - 1)]
            pages = dequantize_kv(pages, scale[..., None])
        else:
            pages = pa.pool_values(pages, False)
        pages = jnp.where((tbl < P)[..., None, None, None], pages, 0)
        return pages.reshape(B, ctx, KV, hd)

    j = jnp.arange(ctx, dtype=jnp.int32)[None]
    kpos = jnp.where((j <= last[:, None]) & (last[:, None] >= 0), j, -1)
    return attention_core(
        q[:, None], dense("k"), dense("v"), q_positions=last[:, None],
        k_positions=kpos, window=0, impl="full", chunk_q=64)[:, 0]


@pytest.mark.parametrize("KV,G,hd", [(2, 7, 64), (8, 2, 128)],
                         ids=["hd64", "hd128"])
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("kernel", ["paged", "ragged"])
def test_each_layer_of_the_stack_reads_as_its_slice(kernel, cache_dtype, KV,
                                                    G, hd):
    """Over a layer-stacked, lane-dense pool, the page walk at each layer
    index gives bit-identical output to the same walk over that layer's
    slice alone, and its XLA twin gives bit-identical output to dense
    attention over the slice viewed [P, ps, KV, hd].  The idle row, whose
    table holds only the out-of-bounds sentinel, gives zeros."""
    rng = np.random.default_rng(KV * hd + len(cache_dtype))
    B, ps, pps = 4, 4, 4
    cache = _pool_setup(rng, B, KV, hd, ps, pps, cache_dtype)
    P = cache["k"].shape[1]
    cache["tbl"] = cache["tbl"].at[3].set(P)
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd)), jnp.bfloat16)
    last = jnp.asarray([ps * pps - 1, 6, 0, -1], jnp.int32)
    act = np.asarray(last) >= 0
    for r in range(LAYERS):
        at_r = dict(cache, layer=jnp.int32(r))
        alone = dict(cache, layer=jnp.int32(0),
                     **{name: cache[name][r:r + 1] for name in cache
                        if name in ("k", "v", "k_scale", "v_scale")})
        ref = np.float32(_slice_reference(q, cache, r, last))
        twin = np.float32(_twin(kernel, q, at_r, last))
        np.testing.assert_array_equal(twin[act], ref[act])
        assert (twin[~act] == 0).all()
        out = np.float32(_walk(kernel, q, at_r, last))
        np.testing.assert_array_equal(out,
                                      np.float32(_walk(kernel, q, alone,
                                                       last)))
        # the kernel's single-pass online softmax is held to the dense
        # reference within bf16 tolerance, as in every test above
        np.testing.assert_allclose(  # repro: ignore[tolerance-claim-mismatch]
            out[act], ref[act], atol=2e-2)
        assert (out[~act] == 0).all()


def test_decode_window_masking():
    rng = np.random.default_rng(13)
    B, KV, G, hd, ps, pps = 2, 2, 2, 16, 4, 8
    cache = _pool_setup(rng, B, KV, hd, ps, pps)
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd)), jnp.bfloat16)
    last = jnp.asarray([ps * pps - 1, 11], jnp.int32)
    for window in (5, 16):
        ref = _reference(q, cache, last, window=window)
        tw = pa.paged_decode_attention_xla(
            q, cache["k"], cache["v"], cache["tbl"], last, cache["layer"],
            window=window, pp=2)
        np.testing.assert_array_equal(np.float32(tw), np.float32(ref))
        kr = pa.paged_decode_attention(
            q, cache["k"], cache["v"], cache["tbl"], last, cache["layer"],
            window=window, pp=2, interpret=True)
        np.testing.assert_allclose(np.float32(kr), np.float32(tw), atol=2e-2)


def test_ops_dispatch_routes_xla_twin_off_tpu():
    """interpret=None off-TPU must take the XLA twin (never the slow
    interpreter) and agree with the explicit twin call bitwise."""
    rng = np.random.default_rng(17)
    B, KV, G, hd, ps, pps = 2, 2, 2, 16, 4, 4
    cache = _pool_setup(rng, B, KV, hd, ps, pps)
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd)), jnp.bfloat16)
    last = jnp.asarray([7, 14], jnp.int32)
    via_ops = ops.paged_decode_attention(q, cache["k"], cache["v"],
                                         cache["tbl"], last, cache["layer"])
    blocks = autotune.get_blocks("attn.paged_decode", B, ps * pps,
                                 KV * G * hd, "bfloat16", group_size=ps)
    direct = pa.paged_decode_attention_xla(
        q, cache["k"], cache["v"], cache["tbl"], last, cache["layer"],
        pp=max(1, blocks["bk"] // ps))
    np.testing.assert_array_equal(np.float32(via_ops), np.float32(direct))


# ---------------------------------------------------------- flash prefill --
def test_flash_prefill_matches_dense_core():
    rng = np.random.default_rng(19)
    B, KV, G, hd, S = 2, 2, 2, 16, 24
    H = KV * G
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.bfloat16)
    pos = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S)).copy()
    pos[1, :5] = -1                                # left-pad row
    pos = jnp.asarray(pos)
    for window in (0, 7):
        ref = attention_core(q, k, v, q_positions=pos, k_positions=pos,
                             window=window, impl="full", chunk_q=64)
        tw = pa.flash_prefill_xla(q, k, v, pos, pos, window=window, bk=8)
        kr = pa.flash_prefill(q, k, v, pos, pos, window=window,
                              bq=8, bk=8, bkv=1, interpret=True)
        valid = np.asarray(pos) >= 0
        np.testing.assert_allclose(np.float32(tw)[valid],
                                   np.float32(ref)[valid], atol=2e-2)
        np.testing.assert_allclose(np.float32(kr)[valid],
                                   np.float32(tw)[valid], atol=2e-2)


def test_flash_impl_dispatches_from_attention_core():
    """attention_core(impl='flash') routes through kernels.ops and agrees
    with the chunked production path."""
    rng = np.random.default_rng(23)
    B, KV, G, hd, S = 2, 2, 2, 16, 16
    q = jnp.asarray(rng.standard_normal((B, S, KV * G, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ref = attention_core(q, k, v, q_positions=pos, k_positions=pos,
                         window=0, impl="chunked", chunk_q=8)
    out = attention_core(q, k, v, q_positions=pos, k_positions=pos,
                         window=0, impl="flash", chunk_q=8)
    np.testing.assert_allclose(np.float32(out), np.float32(ref), atol=2e-2)


# ------------------------------------------------------------ engine e2e ---
@pytest.fixture(scope="module")
def reduced_cfg():
    return get_config("qwen2-0.5b").reduced()


def _engine(cfg, rt, num_pages=32, page_size=8, max_ctx=32, params=None):
    sv = ServingConfig(layout="paged", max_batch=2, page_size=page_size,
                       num_pages=num_pages, max_ctx=max_ctx)
    return InferenceEngine(cfg, rt, sv, params=params, seed=0)


def test_engine_fused_vs_gather_bit_identical(reduced_cfg):
    import dataclasses
    rt = Runtime(quant_backend="float", cache_dtype="bfloat16", remat="none",
                 loss_chunk=0)
    params = build_params(reduced_cfg, rt)
    trace = poisson_trace(4, 1.0, [8], [6], reduced_cfg.vocab, seed=5)
    _, fin_f = run_trace(_engine(reduced_cfg, rt, params=params), trace)
    rt_g = dataclasses.replace(rt, paged_attn="gather")
    _, fin_g = run_trace(_engine(reduced_cfg, rt_g, params=params), trace)
    assert [r.tokens for r in fin_f] == [r.tokens for r in fin_g]


def test_engine_counts_live_and_table_pages(reduced_cfg):
    """Each paged decode step adds its rows' live pages (up to the newest
    position) and its bucket's whole table to two counters."""
    rt = Runtime(quant_backend="float", cache_dtype="bfloat16", remat="none",
                 loss_chunk=0)
    eng = _engine(reduced_cfg, rt, page_size=4)
    steps = []
    decode = eng._decode_batch

    def spy(batch):
        steps.append(([r.n_cached for r in batch],
                      eng.sv.decode_bucket(len(batch))))
        decode(batch)

    eng._decode_batch = spy
    run_trace(eng, poisson_trace(3, 1.0, [8, 13], [6], reduced_cfg.vocab,
                                 seed=3))
    assert any(len(n) == 2 for n, _ in steps)
    counters = eng.metrics.snapshot()["counters"]
    assert counters["paged_decode_pages_live_total"] == sum(
        c // 4 + 1 for n, _ in steps for c in n)
    assert counters["paged_decode_pages_table_total"] == sum(
        nb * eng.sv.pages_per_seq for _, nb in steps)


def test_engine_fused_preempt_resume_matches_gather(reduced_cfg):
    """A pool small enough to force preemption: the fused engine's
    recompute-resume trace must produce exactly the gather engine's
    tokens (and an unconstrained fused run's)."""
    import dataclasses
    rt = Runtime(quant_backend="float", cache_dtype="bfloat16", remat="none",
                 loss_chunk=0)
    params = build_params(reduced_cfg, rt)
    trace = poisson_trace(4, 2.0, [8], [8], reduced_cfg.vocab, seed=9)
    eng = _engine(reduced_cfg, rt, num_pages=6, page_size=4, max_ctx=16,
                  params=params)
    stats, fin = run_trace(eng, trace)
    assert stats["requests_preempted"] >= 1
    assert stats["paged_attn"] == "fused"
    rt_g = dataclasses.replace(rt, paged_attn="gather")
    _, fin_g = run_trace(
        _engine(reduced_cfg, rt_g, num_pages=6, page_size=4, max_ctx=16,
                params=params), trace)
    _, fin_big = run_trace(
        _engine(reduced_cfg, rt, num_pages=32, page_size=4, max_ctx=16,
                params=params), trace)
    assert [r.tokens for r in fin] == [r.tokens for r in fin_g]
    assert [r.tokens for r in fin] == [r.tokens for r in fin_big]


def test_engine_profile_reports_attn_split(reduced_cfg):
    rt = Runtime(quant_backend="float", cache_dtype="bfloat16", remat="none",
                 loss_chunk=0)
    eng = _engine(reduced_cfg, rt)
    trace = poisson_trace(2, 1.0, [8], [4], reduced_cfg.vocab, seed=1)
    run_trace(eng, trace)
    prof = eng.profile(reps=1)
    stats = eng.stats()
    assert stats["profile"] is prof
    assert prof["attn_us"] > 0 and prof["decode_step_us"] > 0
    assert prof["gemm_other_us"] == pytest.approx(
        max(prof["decode_step_us"] - prof["attn_us"], 0.0), abs=0.2)


# --------------------------------------------------------------- autotune --
def test_attn_autotune_tags():
    """attn.* ops get attention-shaped defaults, constraint-clean
    candidates, and cached entries round-trip through get_blocks."""
    b = autotune.get_blocks("attn.paged_decode", 4, 256, 1024, "bfloat16",
                            group_size=16)
    assert b["bk"] % 16 == 0 and b["bk"] <= 256
    cands = autotune.attn_candidate_blocks("attn.paged_decode", 4, 256, 1024,
                                           group_size=16)
    assert cands and all(c["bk"] % 16 == 0 for c in cands)
    b = autotune.get_blocks("attn.prefill", 64, 64, 1024, "bfloat16")
    assert b["bm"] <= 64 and b["bk"] <= 64

    autotune.reset()
    calls = []

    def make_call(blocks):
        calls.append(blocks)
        return lambda: None

    best, _ = autotune.tune(
        "attn.paged_decode", make_call, 4, 256, 1024, "bfloat16",
        group_size=16, timer=lambda fn: 1.0, save=False)
    assert best in calls
    hit = autotune.get_blocks("attn.paged_decode", 4, 256, 1024, "bfloat16",
                              group_size=16)
    assert hit == best
    autotune.reset()

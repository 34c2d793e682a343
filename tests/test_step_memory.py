"""The paged step programs hold one copy of the KV pool.

The layer loop carries the layer-stacked pool and writes each layer's
pages back into it in place, so a step's temporary memory is its
activations, however large the pool.  Scanning the pool as the loop's
input and output stacks instead held a second pool-sized buffer through
every step (XLA copied the donated pool before the loop): at qwen3-4b's
57,344-token chat pool that asked for 5.7 GB beside an 8.5 GB pool.

Here, on the CPU: eight-layer models whose pool is far larger than their
activations.  The compiled prefill, tail-prefill and decode programs'
temporary bytes stay under half the pool's, and prefill then paged decode
through the engine's step jits gives the logits of the whole sequence's
forward pass without a cache.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import Runtime, ServingConfig, get_config
from repro.launch.steps import make_serving_steps
from repro.models import init_model
from repro.models.transformer import _logits, forward, prefill
from repro.serving.kv_pages import (
    encode_kv,
    init_paged_attn_cache,
    init_paged_caches,
    paged_write,
    ragged_paged_write,
    with_block_tables,
)

#: qwen3-shaped: qk-norm, no QKV bias, attention width 2·d (H·hd = 256,
#: d = 128); qwen2-shaped: QKV bias, no qk-norm, H·hd = d
SHAPES = {
    "qwen3": ("qwen3-4b", dict(n_layers=8, d_model=128, n_heads=4,
                               n_kv_heads=2, head_dim=64, d_ff=256,
                               vocab=512)),
    "qwen2": ("qwen2-0.5b", dict(n_layers=8, d_model=128, n_heads=4,
                                 n_kv_heads=2, head_dim=32, d_ff=256,
                                 vocab=512)),
}
PS, MAX_CTX, MAX_BATCH = 16, 128, 4


def _cfg(shape):
    arch, sizes = SHAPES[shape]
    return dataclasses.replace(get_config(arch), tie_embeddings=True,
                               **sizes)


def _sv(num_pages):
    return ServingConfig(layout="paged", max_batch=MAX_BATCH, page_size=PS,
                         num_pages=num_pages, max_ctx=MAX_CTX)


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_step_temp_bytes_under_half_the_pool(shape):
    cfg = _cfg(shape)
    rt = Runtime(scan_layers=True, attn_impl="chunked", remat="none",
                 loss_chunk=0, quant_backend="w4a16")
    sv = _sv(num_pages=4096)             # 65,536 tokens of pool
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    caches = jax.eval_shape(lambda: init_paged_caches(cfg, rt, 0, sv))
    pool = _nbytes(caches)
    steps = dict(zip(("prefill", "prefill_tail", "decode"),
                     make_serving_steps(cfg, rt, paged=True)))
    tbl = jax.ShapeDtypeStruct((MAX_BATCH, sv.pages_per_seq), jnp.int32)

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    shapes = {"prefill": (1, MAX_CTX), "prefill_tail": (1, MAX_CTX),
              "decode": (MAX_BATCH, 1)}
    for name, fn in steps.items():
        rows, n = shapes[name]
        mem = fn.lower(params, i32(rows, n), caches, i32(rows, n), tbl,
                       i32(rows)).compile().memory_analysis()
        assert mem.temp_size_in_bytes < pool / 2, (
            name, mem.temp_size_in_bytes, pool)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_prefill_then_paged_decode_matches_the_full_forward(shape):
    cfg = _cfg(shape)
    rt = Runtime(scan_layers=True, attn_impl="chunked", remat="none",
                 loss_chunk=0, compute_dtype="float32",
                 cache_dtype="float32", aligned_decode=False)
    sv = _sv(num_pages=64)
    params = init_model(jax.random.PRNGKey(3), cfg)
    prompt, n_dec = 21, 6
    seq = jax.random.randint(jax.random.PRNGKey(4), (1, prompt + n_dec), 0,
                             cfg.vocab)
    want, _, _ = forward(params, seq, cfg, rt)          # [1, S, Vp]

    # request in table row 2, on scattered pages of a pool it shares
    tbl = np.full((MAX_BATCH, sv.pages_per_seq), sv.num_pages, np.int32)
    tbl[2, :sv.pages_per_seq] = np.arange(sv.pages_per_seq) * 3 + 1
    tbl = jnp.asarray(tbl)
    slots = jnp.array([2], jnp.int32)
    caches = init_paged_caches(cfg, rt, 0, sv)
    got = []
    pre = jax.jit(lambda p, t, c, pos: prefill(
        p, t, cfg, rt, with_block_tables(c, jnp.take(tbl, slots, 0)), pos))
    logits, caches = pre(params, seq[:, :prompt], caches,
                         jnp.arange(prompt, dtype=jnp.int32)[None])
    got.append(logits[0])
    _, _, dec = make_serving_steps(cfg, rt, paged=True)

    def logits_at(c, tok, pos):
        hidden, c, _ = forward(params, tok, cfg, rt, pos,
                               with_block_tables(c, jnp.take(tbl, slots, 0)),
                               update_cache=True, return_hidden=True)
        return _logits(params, hidden, cfg, rt)[:, 0], c

    step = jax.jit(logits_at)
    for p in range(prompt, prompt + n_dec - 1):
        tok, pos = seq[:, p:p + 1], jnp.array([[p]], jnp.int32)
        lg, _ = step(caches, tok, pos)
        got.append(lg[0])
        # the engine's decode step writes position p into the pool
        nxt, caches = dec(params, tok, caches, pos, tbl, slots)
        assert int(nxt[0]) == int(jnp.argmax(lg[0, :cfg.vocab]))
    got = jnp.stack(got)
    want = want[0, prompt - 1:prompt + n_dec - 1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_decode_step_temp_bytes_under_one_layers_slice(shape):
    """The decode step reads and writes the layer-stacked pool where it
    lives: its temporaries stay under one layer's K and V slice, so no
    layer's slice is ever copied out of the stack or back."""
    cfg = _cfg(shape)
    rt = Runtime(scan_layers=True, attn_impl="chunked", remat="none",
                 loss_chunk=0, quant_backend="w4a16")
    sv = _sv(num_pages=4096)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    caches = jax.eval_shape(lambda: init_paged_caches(cfg, rt, 0, sv))
    _, _, decode = make_serving_steps(cfg, rt, paged=True)
    tbl = jax.ShapeDtypeStruct((MAX_BATCH, sv.pages_per_seq), jnp.int32)
    rows = jax.ShapeDtypeStruct((MAX_BATCH, 1), jnp.int32)
    mem = decode.lower(params, rows, caches, rows, tbl,
                       jax.ShapeDtypeStruct((MAX_BATCH,), jnp.int32)
                       ).compile().memory_analysis()
    layer_slice = _nbytes(caches) / cfg.n_layers
    assert mem.temp_size_in_bytes < layer_slice, (
        mem.temp_size_in_bytes, layer_slice)


def _random_bytes(rng, leaf):
    """A pool leaf of random contents, so that an untouched byte tells."""
    if leaf.dtype == jnp.float32:
        return jnp.asarray(rng.standard_normal(leaf.shape), jnp.float32)
    info = np.iinfo(leaf.dtype)
    return jnp.asarray(rng.integers(info.min, info.max, leaf.shape,
                                    endpoint=True), leaf.dtype)


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8", "int4"])
@pytest.mark.parametrize("write", ["paged", "ragged"])
def test_writes_change_only_their_layers_slots(write, cache_dtype):
    """paged_write and ragged_paged_write at layer r put each token's K and
    V, as the pool encodes them, in the (page, slot) its table addresses
    in layer r; every other byte of the stack, in every layer, keeps its
    value (negative positions, padding rows and sentinel pages drop)."""
    cfg = _cfg("qwen2")
    rt = Runtime(cache_dtype=cache_dtype)
    ps, P, L, r = 4, 12, 3, 1
    sv = ServingConfig(layout="paged", max_batch=2, page_size=ps,
                       num_pages=P, max_ctx=16)
    rng = np.random.default_rng(len(cache_dtype))
    cache = {name: _random_bytes(rng, leaf) for name, leaf in
             init_paged_attn_cache(cfg, rt, 2, sv, layers=L).items()}
    cache["tbl"] = jnp.asarray([[5, 2, 9, P], [7, 0, 3, 11]], jnp.int32)
    cache["layer"] = jnp.int32(r)
    pos = np.asarray([[6, 7, 8, 13], [0, 15, -1, 3]], np.int32)
    shape = pos.shape + (cfg.n_kv_heads, cfg.hd)
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    if write == "paged":
        out = paged_write(cache, k, v, jnp.asarray(pos))
    else:                          # the same tokens packed, one padding row
        slots = np.repeat(np.arange(2, dtype=np.int32), 4)
        slots[1] = -1
        out = ragged_paged_write(
            dict(cache, slots=jnp.asarray(slots)), k.reshape(1, 8, *shape[2:]),
            v.reshape(1, 8, *shape[2:]), jnp.asarray(pos.reshape(1, 8)))
    want = {name: np.asarray(cache[name]).copy()
            for name in ("k", "v", "k_scale", "v_scale") if name in cache}
    tbl = np.asarray(cache["tbl"])
    for name, val in (("k", k), ("v", v)):
        enc = encode_kv(val, cache[name].dtype)
        for b, i in np.ndindex(pos.shape):
            page = tbl[b, pos[b, i] // ps] if pos[b, i] >= 0 else P
            if page == P or (write == "ragged" and b * 4 + i == 1):
                continue
            for leaf, e in zip((name, name + "_scale"), enc):
                if e is not None:
                    want[leaf][r, page, pos[b, i] % ps] = np.asarray(e)[b, i]
    for name, arr in want.items():
        np.testing.assert_array_equal(np.asarray(out[name]), arr, name)

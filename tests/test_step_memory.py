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
    init_paged_caches,
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

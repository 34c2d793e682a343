"""Mosaic compiles the serving path's Pallas kernels for a described TPU
v5e at qwen2-0.5b widths (d 896, 14 query / 2 KV heads, head_dim 64,
d_ff 4864), with the tiles the autotuner gives by default — no chip
needed.

Interpret mode cannot show what Mosaic refuses (int8 vector arithmetic,
reshapes that do not align to the tiling, too much VMEM); this compile
does, in about two seconds a kernel.  The topology is described inside a
module fixture, never at import time, so every pytest worker collects the
same tests and only the worker that runs this file loads the TPU compiler.
"""

import functools
import importlib
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.int4_matmul import int4_matmul_fused
from repro.kernels.paged_attention import flash_prefill, paged_decode_attention
from repro.kernels.ragged_attention import ragged_decode_attention
from repro.kernels.w4a16_matmul import w4a16_matmul

H, KV, HD, D, F = 14, 2, 64, 896, 4864      # qwen2-0.5b widths
B, PS, MAX_CTX, T, S = 8, 16, 2048, 64, 512  # decode batch, page, ctx, ...
PAGES = B * MAX_CTX // PS
LAYERS = 24                                  # the pool's layer stack
#: the serving cache's pool leaves: layer-stacked and lane-dense, a float
#: pool holding its values' bits
STORAGE = {jnp.bfloat16: jnp.uint16, jnp.int8: jnp.int8}
LAYER = ((), jnp.int32)                      # the layer-index operand


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("M", [8, 512], ids=["decode", "prefill"])
def test_int4_matmul_fused_compiles(one_chip, no_persistent_cache, M):
    b = autotune.default_blocks(M, D, F)
    _compile(functools.partial(int4_matmul_fused, interpret=False, **b),
             one_chip, ((M, D), jnp.float32), ((D // 2, F), jnp.uint8),
             ((1, F), jnp.float32))


@pytest.mark.parametrize("group", [0, 128], ids=["per_channel", "grouped"])
def test_w4a16_matmul_compiles(one_chip, no_persistent_cache, group):
    b = autotune.default_blocks(B, D, F, group_size=group)
    scale = ((1, F) if not group else (D // group, 1, F), jnp.float32)
    # grouped packing pads K to whole groups per planar half (2 * group)
    rows = -(-D // (2 * group)) * group if group else D // 2
    _compile(functools.partial(w4a16_matmul, group_size=group,
                               interpret=False, **b),
             one_chip, ((B, D), jnp.bfloat16), ((rows, F), jnp.uint8), scale)


def _paged_blocks(op):
    b = autotune.attn_default_blocks(op, B, MAX_CTX, H * HD, group_size=PS)
    return {"pp": b["bk"] // PS, "bkv": b["bn"]}


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_attention_compiles(one_chip, no_persistent_cache,
                                         cache_dtype):
    pool = ((LAYERS, PAGES, PS, KV * HD), STORAGE[cache_dtype])
    shapes = [((B, H, HD), jnp.bfloat16), pool, pool,
              ((B, MAX_CTX // PS), jnp.int32), ((B,), jnp.int32), LAYER]
    if cache_dtype == jnp.int8:
        shapes += [((LAYERS, PAGES, PS, KV), jnp.float32)] * 2
    _compile(functools.partial(paged_decode_attention, interpret=False,
                               **_paged_blocks("attn.paged_decode")),
             one_chip, *shapes)


def test_ragged_decode_attention_compiles(one_chip, no_persistent_cache):
    pool = ((LAYERS, PAGES, PS, KV * HD), jnp.uint16)
    _compile(functools.partial(ragged_decode_attention, interpret=False,
                               **_paged_blocks("attn.ragged")),
             one_chip, ((T, H, HD), jnp.bfloat16), pool, pool,
             ((B, MAX_CTX // PS), jnp.int32), ((T,), jnp.int32),
             ((T,), jnp.int32), LAYER)


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["paged_decode_attention",
                                    "ragged_decode_attention"])
def test_page_walks_compile_at_chat_shapes(one_chip, no_persistent_cache,
                                           kernel, cache_dtype):
    """The page walks that stop at each row's last live page, at the chat
    deployment's shapes: a 64-row bucket, max_ctx 2560 in 16-token pages
    over an 81,920-token pool of 24 layers."""
    rows, max_ctx, pages = 64, 2560, 81920 // PS
    pool = ((LAYERS, pages, PS, KV * HD), STORAGE[cache_dtype])
    scales = ([((LAYERS, pages, PS, KV), jnp.float32)] * 2
              if cache_dtype == jnp.int8 else [])
    tbl = ((rows, max_ctx // PS), jnp.int32)
    b = autotune.attn_default_blocks(
        "attn.paged_decode" if kernel == "paged_decode_attention"
        else "attn.ragged", rows, max_ctx, H * HD, group_size=PS)
    blocks = {"pp": b["bk"] // PS, "bkv": b["bn"]}
    if kernel == "paged_decode_attention":
        call = functools.partial(paged_decode_attention, interpret=False,
                                 **blocks)
        rest = [((rows,), jnp.int32), LAYER]
    else:
        call = functools.partial(ragged_decode_attention, interpret=False,
                                 **blocks)
        rest = [((rows,), jnp.int32)] * 2 + [LAYER]
    _compile(call, one_chip, ((rows, H, HD), jnp.bfloat16), pool, pool, tbl,
             *rest, *scales)


#: (layers, pool tokens, query heads, KV heads, head size) of the chat
#: cells' pools: qwen2-0.5b's 81,920 tokens, qwen3-4b's 57,344
CHAT_POOLS = {"qwen2-0.5b.chat": (24, 81920, 14, 2, 64),
              "qwen3-4b.chat": (36, 57344, 32, 8, 128)}


@pytest.mark.parametrize("kernel", ["paged_decode_attention",
                                    "ragged_decode_attention"])
@pytest.mark.parametrize("cell", sorted(CHAT_POOLS))
def test_stacked_pool_walks_compile_at_chat_shapes(one_chip,
                                                   no_persistent_cache,
                                                   cell, kernel):
    """The page walks over a chat cell's whole layer-stacked pool, at a
    traced layer index, for a 64-row decode bucket: one compiled kernel
    reads two layers of the stack where it lives, with no copy or
    relayout of the pool (the compiled program's temporaries stay under
    one layer's share of it)."""
    L, tokens, heads, kv, hd = CHAT_POOLS[cell]
    rows, max_ctx = 64, 2560
    pool = ((L, tokens // PS, PS, kv * hd), jnp.uint16)
    b = autotune.attn_default_blocks(
        "attn.paged_decode" if kernel == "paged_decode_attention"
        else "attn.ragged", rows, max_ctx, heads * hd, group_size=PS)
    call = functools.partial(
        paged_decode_attention if kernel == "paged_decode_attention"
        else ragged_decode_attention, interpret=False, pp=b["bk"] // PS,
        bkv=b["bn"])
    rest = 1 if kernel == "paged_decode_attention" else 2

    def two_layers(*operands, layer):
        return call(*operands, layer) + call(*operands, layer + 1)

    shapes = [((rows, heads, hd), jnp.bfloat16), pool, pool,
              ((rows, max_ctx // PS), jnp.int32),
              *[((rows,), jnp.int32)] * rest, LAYER]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(two_layers).lower(*args[:-1],
                                         layer=args[-1]).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    pool_bytes = L * tokens * kv * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / L


def test_flash_prefill_compiles(one_chip, no_persistent_cache):
    b = autotune.attn_default_blocks("attn.prefill", S, S, H * HD)
    kv = ((1, S, KV, HD), jnp.bfloat16)
    _compile(functools.partial(flash_prefill, bq=b["bm"], bk=b["bk"],
                               bkv=b["bn"], interpret=False),
             one_chip, ((1, S, H, HD), jnp.bfloat16), kv, kv,
             ((1, S), jnp.int32), ((1, S), jnp.int32))


def _trace_names(kernel):
    """The names the benchmark reads a kernel's ops by in the device
    trace (``bench/work/<kernel>.py``)."""
    bench = str(Path(__file__).resolve().parents[1] / "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return importlib.import_module(f"work.{kernel}").TRACE_NAMES


def _serving_call(kernel):
    """(call, operand shapes) of a serving-path kernel at qwen2-0.5b
    widths."""
    pool = ((LAYERS, PAGES, PS, KV * HD), jnp.uint16)
    if kernel == "w4a16_matmul":
        b = autotune.default_blocks(B, D, F, group_size=0)
        return (functools.partial(w4a16_matmul, group_size=0,
                                  interpret=False, **b),
                [((B, D), jnp.bfloat16), ((D // 2, F), jnp.uint8),
                 ((1, F), jnp.float32)])
    if kernel == "paged_decode_attention":
        return (functools.partial(paged_decode_attention, interpret=False,
                                  **_paged_blocks("attn.paged_decode")),
                [((B, H, HD), jnp.bfloat16), pool, pool,
                 ((B, MAX_CTX // PS), jnp.int32), ((B,), jnp.int32), LAYER])
    if kernel == "ragged_decode_attention":
        return (functools.partial(ragged_decode_attention, interpret=False,
                                  **_paged_blocks("attn.ragged")),
                [((T, H, HD), jnp.bfloat16), pool, pool,
                 ((B, MAX_CTX // PS), jnp.int32), ((T,), jnp.int32),
                 ((T,), jnp.int32), LAYER])
    b = autotune.attn_default_blocks("attn.prefill", S, S, H * HD)
    kv = ((1, S, KV, HD), jnp.bfloat16)
    return (functools.partial(flash_prefill, bq=b["bm"], bk=b["bk"],
                              bkv=b["bn"], interpret=False),
            [((1, S, H, HD), jnp.bfloat16), kv, kv, ((1, S), jnp.int32),
             ((1, S), jnp.int32)])


@pytest.mark.parametrize("kernel", ["w4a16_matmul", "paged_decode_attention",
                                    "flash_prefill",
                                    "ragged_decode_attention"])
def test_serving_kernels_carry_their_trace_names(one_chip,
                                                 no_persistent_cache,
                                                 kernel):
    names = (_trace_names(kernel) if kernel != "ragged_decode_attention"
             else (kernel,))
    call, shapes = _serving_call(kernel)

    def serving_step(*args):            # a caller of another name
        return call(*args)

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    lowered = jax.jit(serving_step).lower(*args)
    # the Mosaic kernel is named by its pallas_call ...
    assert set(re.findall(r'kernel_name = "([^"]*)"', lowered.as_text())) \
        == set(names)
    # ... and the compiled custom call, which the trace lists, likewise
    calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(.*tpu_custom_call",
                       lowered.compile().as_text())
    assert calls and {c.rsplit(".", 1)[0] for c in calls} == set(names)

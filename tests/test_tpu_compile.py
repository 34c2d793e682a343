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
import os

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
    pool = ((PAGES, PS, KV, HD), cache_dtype)
    shapes = [((B, H, HD), jnp.bfloat16), pool, pool,
              ((B, MAX_CTX // PS), jnp.int32), ((B,), jnp.int32)]
    if cache_dtype == jnp.int8:
        shapes += [((PAGES, PS, KV, 1), jnp.float32)] * 2
    _compile(functools.partial(paged_decode_attention, interpret=False,
                               **_paged_blocks("attn.paged_decode")),
             one_chip, *shapes)


def test_ragged_decode_attention_compiles(one_chip, no_persistent_cache):
    pool = ((PAGES, PS, KV, HD), jnp.bfloat16)
    _compile(functools.partial(ragged_decode_attention, interpret=False,
                               **_paged_blocks("attn.ragged")),
             one_chip, ((T, H, HD), jnp.bfloat16), pool, pool,
             ((B, MAX_CTX // PS), jnp.int32), ((T,), jnp.int32),
             ((T,), jnp.int32))


def test_flash_prefill_compiles(one_chip, no_persistent_cache):
    b = autotune.attn_default_blocks("attn.prefill", S, S, H * HD)
    kv = ((1, S, KV, HD), jnp.bfloat16)
    _compile(functools.partial(flash_prefill, bq=b["bm"], bk=b["bk"],
                               bkv=b["bn"], interpret=False),
             one_chip, ((1, S, H, HD), jnp.bfloat16), kv, kv,
             ((1, S), jnp.int32), ((1, S), jnp.int32))

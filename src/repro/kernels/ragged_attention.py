"""Ragged token-major paged attention: one launch for mixed prefill+decode.

The bucketed serving step runs prefill and decode as separate jits over
padded batch shapes, so a mixed step pays two launches plus the padding of
both buckets, and every new bucket is a recompile.  This kernel is the
serving-side version of the paper's dense-packing argument: pack every
live request's tokens — chunked-prefill slices and single decode tokens
alike — into one flat ``[total_tokens, ...]`` buffer (the MAX
``flash_attention_ragged`` idiom) and attend them all in one grid.

Each packed row carries two scalars:

  ``token_slot[t]``  which request (block-table row) the token belongs to
                     (-1 = padding row),
  ``token_pos[t]``   its absolute position in that request's sequence
                     (-1 = padding row).

The engine writes the step's K/V through the block tables *before*
attending (``kv_pages.ragged_paged_write``), so by the time this kernel
runs the pool holds every position ``<= token_pos[t]`` for row ``t`` and
the decode mask ``pos <= token_pos`` is exactly causal for prefill rows
and exactly last-token for decode rows — one rule covers both.

``ragged_decode_attention``
    One program per (token row, KV-head tile); grid (T, nh, nj).  The
    per-token slot/pos vectors and the whole block-table matrix ride in as
    scalar-prefetch operands, so the BlockSpec index_map resolves
    ``tbl[slot[t], logical_page]`` to a physical pool page per program —
    the same in-place page walk as ``paged_decode_attention``, just
    indexed per token instead of per batch row.

``ragged_attention_xla``
    The twin CPU/GPU hosts execute and the compare harness gates.  It
    gathers each token's table row (padding rows get the out-of-bounds
    sentinel page) and defers to ``paged_decode_attention_xla`` with
    batch == tokens — so ragged decode rows are *bit-identical* to the
    bucketed fused/gather decode paths by construction, and prefill rows
    get the identical exact-softmax-over-pages math the tail-prefill
    (prefill-over-cache) path runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import default_interpret
from .paged_attention import (
    _walk_pages,
    live_page,
    page_walk_call,
    paged_decode_attention_xla,
)


def _ragged_kernel(slot_ref, pos_ref, tbl_ref, layer_ref, q_ref, *refs,
                   **kw):
    # slot_ref/tbl_ref/layer_ref are consumed by the BlockSpec index_maps;
    # the body only needs the token's own position for masking.  pos <=
    # token_pos is causal for prefill rows (the chunk's K/V is already in
    # the pool) and last-token for decode rows; padding rows (token_pos ==
    # -1) skip every page and emit zeros.
    del slot_ref, tbl_ref, layer_ref
    _walk_pages(pos_ref[pl.program_id(0)], pl.program_id(2), q_ref, refs,
                **kw)


@functools.partial(
    jax.jit, static_argnames=("window", "pp", "bkv", "interpret"))
def ragged_decode_attention(
    q: jnp.ndarray,            # [T, H, hd] packed token rows
    k_pool: jnp.ndarray,       # [L, P, ps, KV·hd]  (uint8: KV·hd/2)
    v_pool: jnp.ndarray,
    tbl: jnp.ndarray,          # [max_batch, pages_per_seq] int32
    token_slot: jnp.ndarray,   # [T] int32 table row per token (-1 = pad)
    token_pos: jnp.ndarray,    # [T] int32 absolute position (-1 = pad)
    layer: jnp.ndarray,        # int32 scalar: the layer of the stack read
    k_scale: jnp.ndarray = None,   # [L, P, ps, KV] f32 when quantized
    v_scale: jnp.ndarray = None,
    window: int = 0,
    pp: int = 4,               # pages per program (autotuned: attn.ragged)
    bkv: int = 0,              # KV-head tile, 0 = all heads
    interpret: bool = None,
) -> jnp.ndarray:
    P, ps = k_pool.shape[1:3]
    pps = tbl.shape[1]
    pp = max(1, min(pp, pps))

    def page_index(u):
        # two scalar hops per program: token row -> table row -> physical
        # page.  The walk stops at the token's own position (`live_page`:
        # later pages keep an index already fetched, so they are never
        # copied).  Padding rows (slot -1) clamp to row 0, whose slot may
        # carry the out-of-bounds sentinel (== P); both clamp into bounds
        # and the kernel body skips them.
        def index(t, j, slot_ref, pos_ref, tbl_ref):
            row = jnp.maximum(slot_ref[t], 0)
            logical = live_page(j, u, pos_ref[t], pp=pp, ps=ps,
                                window=window)
            return jnp.minimum(tbl_ref[row, logical], P - 1)
        return index

    scalars = (token_slot.astype(jnp.int32), token_pos.astype(jnp.int32),
               tbl.astype(jnp.int32))
    return page_walk_call(
        _ragged_kernel, scalars, q, k_pool, v_pool, layer, k_scale, v_scale,
        page_index=page_index, pp=pp, bkv=bkv, nj=-(-pps // pp),
        window=window, interpret=default_interpret(interpret),
        name="ragged_decode_attention")


@functools.partial(jax.jit, static_argnames=("window", "pp"))
def ragged_attention_xla(
    q, k_pool, v_pool, tbl, token_slot, token_pos, layer,
    k_scale=None, v_scale=None, window: int = 0, pp: int = 4,
) -> jnp.ndarray:
    """Pure-XLA twin: gather each token's block-table row (padding rows
    become all-sentinel rows, so their clamped page fetches mask to zero)
    and run the exact-softmax blocked decode twin with batch == tokens.
    Per-token rows are independent in that twin, so decode tokens here are
    bit-identical to what the bucketed decode step produced for the same
    (pool, table, position) — regardless of how many rows share a step."""
    P = k_pool.shape[1]
    maxB = tbl.shape[0]
    slot = token_slot.astype(jnp.int32)
    tbl_pt = jnp.where(
        slot[:, None] >= 0,
        jnp.take(tbl.astype(jnp.int32), jnp.clip(slot, 0, maxB - 1), axis=0),
        P)                                          # [T, pages_per_seq]
    return paged_decode_attention_xla(
        q, k_pool, v_pool, tbl_pt, token_pos.astype(jnp.int32), layer,
        k_scale, v_scale, window=window, pp=pp)

"""Fused paged-attention Pallas kernels: decode that reads KV pages in
place, and a tiled flash prefill.

The serving hot path used to be gather-then-attend: every decode step
``paged_read`` materialized a sequence's whole KV history out of the page
pool into a dense ``[B, max_ctx, KV, hd]`` buffer before attention ran, so
per-step memory traffic was ~3x the KV bytes (read pool + write dense +
re-read dense for QK and PV) and always paid for ``max_ctx`` slots no
matter how short the actual context.  This module is the paper's roofline
argument applied to serving: the multiplier only wins once the surrounding
data movement is gone, so attention must consume the pages where they live.

``paged_decode_attention``
    One program per (batch row, KV-head tile); the block table rides in as
    a scalar-prefetch operand so the BlockSpec index_map fetches *physical*
    pages straight from the pool — no gather, no dense intermediate.  Each
    program walks its row's logical pages ``pages_per_program`` at a time
    with flash-style online-softmax accumulation in VMEM scratch.  The walk
    stops at each row's last live page: past ``last_pos`` the index_map
    keeps returning a page the program already holds, so the pipeline
    starts no copy, and the body skips every page block that holds no live
    position; fully inactive rows (``last_pos == -1``) skip every block and
    emit zeros.  Slots past ``last_pos`` inside a live block are masked.
    int8/int4 pools dequantize per fetched page with the same
    ``q * scale -> bf16`` rounding as ``serving.kv_pages`` gather path.

    The pool is the serving cache's own: stacked over the layers and
    lane-dense, ``[L, P, ps, KV·hd]`` (``serving.kv_pages``).  The kernel
    takes the whole stack and a layer index and fetches each page at
    (layer, page) where it lives, so no layer's slice of the pool is copied
    out for it; a page arrives as one [ps, KV·hd] tile, which a lane-dense
    layout keeps row-major in HBM whatever the head size.

``flash_prefill``
    Tiled causal attention over the in-flight prompt: grid over
    (batch, head tile, q tile, kv tile) with online-softmax scratch carried
    across the kv dimension — scores only ever exist as ``[bq, bk]`` tiles,
    never as the ``[S, S]`` matrix the chunked path builds per chunk.

Numerics: QK products are rounded to the compute dtype before the f32
softmax when activations are bf16 — exactly the rounding the dense
reference path gets from its bf16 einsum.  The Pallas decode kernel runs
classic single-pass online softmax (f32 PV accumulation; bf16-tolerance vs
the gather path — the right trade on TPU, where a second pool sweep costs
real HBM bandwidth).  Its XLA twin ``paged_decode_attention_xla`` — the
path CPU/GPU hosts execute, and the one the `--layout compare` harness
gates — instead does two blocked passes (scores buffer, then the *exact*
dense softmax + probs cast, then blocked PV), which makes it bit-identical
to the gather reference for bf16/int8/int4 pools while still never
materializing the dense KV layout and stopping at the last active page.

``kernels.ops`` picks Mosaic vs interpreter vs twin the same way it does
for the GEMM kernels.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import default_interpret
from .packing import unpack_nibbles

NEG_INF = -1e30

#: A float pool holds its values' bits, each as the unsigned integer of its
#: width: a backend without a bfloat16 scatter (the CPU) would otherwise
#: widen the whole layer-stacked pool to float32 and back around every
#: write into it.  Readers reinterpret the pages they fetch (`pool_values`).
FLOAT_OF_BITS = {jnp.dtype(jnp.uint16): jnp.dtype(jnp.bfloat16),
                 jnp.dtype(jnp.uint32): jnp.dtype(jnp.float32)}
BITS_OF_FLOAT = {f: b for b, f in FLOAT_OF_BITS.items()}


def pool_values(x, quant: bool):
    """Pages of a float pool (stored as bits) as their values; a quantized
    pool's int8 values or packed int4 pairs as they are."""
    if quant:
        return x
    return jax.lax.bitcast_convert_type(x, FLOAT_OF_BITS[x.dtype])


def _largest_divisor(n: int, bound: int) -> int:
    """Largest divisor of n that is <= bound (self-heal head tiles)."""
    b = max(1, min(bound, n))
    while n % b:
        b -= 1
    return b


def _dequant_slab(kq, scale, hd: int):
    """Pool slab [..., hd or hd//2] -> bf16, matching kv_pages'
    ``dequantize_kv`` rounding exactly (int4 nibbles interleave along hd)."""
    if kq.dtype == jnp.uint8:                      # packed int4 pairs
        lo, hi = unpack_nibbles(kq)
        kq = jnp.stack([lo, hi], axis=-1).reshape(*kq.shape[:-1], hd)
    if kq.dtype == jnp.int8:
        return (kq.astype(jnp.float32) * scale).astype(jnp.bfloat16)
    return kq                                      # float pool: passthrough


def _round_scores(s, compute_dtype):
    """f32-accumulated QK tile -> the dense path's score values: bf16
    activations round the einsum result to bf16 before the f32 softmax."""
    if compute_dtype == jnp.bfloat16:
        s = s.astype(jnp.bfloat16)
    return s.astype(jnp.float32)


# ------------------------------------------------------- decode (paged) ----
def live_page(j, u, last, *, pp: int, ps: int, window: int):
    """The logical page that the u-th page spec of page block `j` fetches
    for a query row whose newest position is `last` (-1 = idle row).

    A live page maps to itself.  Past the row's last live page a spec keeps
    the page it held in the last block where it was live, so its block
    index stops changing and the pipeline starts no copy for it; a spec
    that is never live holds the last live page (page 0 for an idle row).
    With a window, pages wholly before it map to its first page.

    Plain `lax` ops on non-negative scalars: each page spec traces its own
    index map, in every compile of every decode bucket."""
    end = lax.div(lax.max(last, 0), ps)
    block = lax.min(lax.div(lax.max(lax.sub(end, u), 0), pp), j)
    page = lax.min(lax.add(lax.mul(block, pp), u), end)
    if window:
        page = lax.max(page, lax.div(lax.max(lax.sub(last, window - 1), 0),
                                     ps))
    return page


@functools.partial(jax.jit, static_argnames=("hd", "window", "scale"))
def _attend_page(q, kb, vb, ks, vs, start, last, state, *, hd: int,
                 window: int, scale: float):
    """One page of a query row's online softmax: q the G query heads of
    each KV head ([G, hd] each), kb/vb the lane-dense page [ps, bkv·hd]
    (uint8: [ps, bkv·hd/2]) whose KV head h owns lanes [h·w, (h+1)·w)
    (ks/vs its scales [ps, bkv], or None), `start` its first position;
    `state` the per-head (m, l, acc), returned updated.  Jitted, so that a
    kernel traces it once for all the pages it unrolls: traced inline, the
    unrolled pages made tracing the decode step's most expensive part of
    the engine's warmup."""
    cd = q[0].dtype
    w = kb.shape[-1] // len(q)

    def head(page, scales, h, dtype):              # KV head h: [ps, hd]
        slab = pool_values(page[:, h * w:(h + 1) * w], scales is not None)
        if scales is not None:
            slab = _dequant_slab(slab, scales[:, h:h + 1], hd)
        return slab.astype(dtype)

    pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, kb.shape[0]), 1)
    mask = pos <= last
    if window:
        mask &= (last - pos) < window
    new = []
    for h, (m_prev, l_prev, acc_prev) in enumerate(zip(*state)):
        # scores [G, ps]: contract hd
        s = jax.lax.dot_general(q[h], head(kb, ks, h, cd),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(mask, _round_scores(s, cd) * scale, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit zero: exp(0)=1 would leak masked slots into l/acc while
        # m is still NEG_INF
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        new.append((m_new,
                    l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                    acc_prev * alpha + jax.lax.dot_general(
                        p, head(vb, vs, h, jnp.float32),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)))    # [G, hd]
    return tuple(zip(*new))


def _walk_pages(last, j, q_ref, refs, *, pp: int, ps: int, nj: int,
                bkv: int, hd: int, window: int, quant: bool, scale: float):
    """One program's share of a page walk, shared by the paged-decode and
    ragged kernels: `pp` pages of one query row, each KV head of the tile
    attended by its G query heads with online-softmax accumulation.  A block
    with no position in ``(last - window, last]`` is skipped whole (an idle
    row, ``last == -1``, skips every block).  In a live block, a page spec
    past the row's end or before its window holds another live page of the
    row (`live_page`), whose positions the mask removes.

    Every value stays a 2-D [rows, lanes] tile: the G query heads of KV head
    h are the leading-index slice ``q_ref[0, h]`` ([G, hd]), never a reshape
    of the [H, hd] row — Mosaic cannot re-tile that reshape when G or hd is
    not a multiple of the (sublane, lane) tiling (G = 7, hd = 64 for
    qwen2-0.5b) — and a page arrives as one lane-dense [ps, bkv·hd] tile
    whose heads are static lane slices."""
    k_refs = refs[:pp]
    v_refs = refs[pp:2 * pp]
    i = 2 * pp
    if quant:
        ks_refs = refs[i:i + pp]
        vs_refs = refs[i + pp:i + 2 * pp]
        i += 2 * pp
    o_ref, acc_ref, m_ref, l_ref = refs[i:i + 4]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = j * pp * ps
    live = start <= last                           # False for idle rows
    if window:
        live &= start + pp * ps > last - window + 1

    @pl.when(live)
    def _block():
        q = tuple(q_ref[0, h] for h in range(bkv))
        state = tuple(tuple(r[h] for h in range(bkv))
                      for r in (m_ref, l_ref, acc_ref))
        for u in range(pp):                        # static unroll: pages
            state = _attend_page(
                q, k_refs[u][0, 0], v_refs[u][0, 0],
                ks_refs[u][0, 0] if quant else None,
                vs_refs[u][0, 0] if quant else None,
                start + u * ps, last, state, hd=hd, window=window,
                scale=scale)
        for r, values in zip((m_ref, l_ref, acc_ref), state):
            for h in range(bkv):
                r[h] = values[h]

    @pl.when(j == nj - 1)
    def _emit():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)  # masked row -> 0
        o_ref[0] = out.astype(o_ref.dtype)


def _decode_kernel(tbl_ref, lp_ref, layer_ref, q_ref, *refs, **kw):
    del tbl_ref, layer_ref         # consumed by the BlockSpec index_maps
    _walk_pages(lp_ref[pl.program_id(0)], pl.program_id(2), q_ref, refs, **kw)


def pool_heads(pool, hd: int) -> int:
    """KV heads of a lane-dense pool [L, P, ps, KV·hd] (uint8 packs two
    int4 values a byte: [L, P, ps, KV·hd/2])."""
    return pool.shape[-1] // (hd // 2 if pool.dtype == jnp.uint8 else hd)


def head_tile(KV: int, width: int, bkv: int, quant: bool) -> int:
    """The KV heads a program attends: a divisor of KV, all of them when
    asked for none (0), and all of them where a tile of `bkv` heads of
    `width` lanes each would not be a whole number of 128-lane tiles, or
    where the pool is quantized (a page's scales arrive as one [ps, KV]
    tile)."""
    bkv = _largest_divisor(KV, bkv if bkv > 0 else KV)
    if quant or (bkv * width) % 128:
        return KV
    return bkv


def page_walk_call(kernel, scalars, q, k_pool, v_pool, layer, k_scale,
                   v_scale, *, page_index, pp: int, bkv: int, nj: int,
                   window: int, interpret: bool, name: str) -> jnp.ndarray:
    """The pallas_call both page-walking kernels share: grid (query row,
    KV-head tile, page block) over q [R, H, hd], with `scalars` and then
    the layer index riding in as scalar-prefetch operands.  `page_index(u)`
    reads the scalars to find the u-th page of a program in the pool:
    ``(r, j, *scalar_refs) -> physical page``.

    The pools are the layer-stacked, lane-dense [L, P, ps, KV·hd]: each
    page spec fetches the (1, 1, ps, bkv·hd) block at (layer, page, 0,
    head tile), so a layer's pages are read where they live in the stack
    and no slice of the pool is ever copied.  The layer is a traced
    operand, so one compiled kernel serves every layer.

    q enters as [R, KV, G, hd] (a free reshape here, outside the kernel),
    so a program's query heads arrive grouped by KV head.  `name` names
    the kernel in the compiled program and so in the device trace."""
    R, H, hd = q.shape
    ps = k_pool.shape[2]
    KV = pool_heads(k_pool, hd)
    assert H % KV == 0, (H, KV)           # query heads tile evenly over KV heads
    G = H // KV
    quant = k_scale is not None
    width = k_pool.shape[-1] // KV                 # hd, or hd//2 packed
    bkv = head_tile(KV, width, bkv, quant)

    def row_index(r, h, j, *_):
        return (r, h, 0, 0)

    def pool_index(u):
        page = page_index(u)

        def index(r, h, j, *refs):
            return (refs[-1][0], page(r, j, *refs[:-1]), 0, h)
        return index

    pool_specs = [pl.BlockSpec((1, 1, ps, bkv * width), pool_index(u))
                  for u in range(pp)]
    in_specs = [pl.BlockSpec((1, bkv, G, hd), row_index),
                *pool_specs, *pool_specs]
    args = [q.reshape(R, KV, G, hd), *([k_pool] * pp), *([v_pool] * pp)]
    if quant:
        # bkv == KV: the head-tile index is always 0, so the pool's index
        # maps address the [L, P, ps, KV] scales too
        scale_specs = [pl.BlockSpec((1, 1, ps, KV), pool_index(u))
                       for u in range(pp)]
        in_specs += [*scale_specs, *scale_specs]
        args += [*([k_scale] * pp), *([v_scale] * pp)]

    body = functools.partial(
        kernel, pp=pp, ps=ps, nj=nj, bkv=bkv, hd=hd, window=window,
        quant=quant, scale=1.0 / math.sqrt(hd))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    out = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars) + 1,
            grid=(R, KV // bkv, nj),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bkv, G, hd), row_index),
            scratch_shapes=[
                pltpu.VMEM((bkv, G, hd), jnp.float32),
                pltpu.VMEM((bkv, G, 1), jnp.float32),
                pltpu.VMEM((bkv, G, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, KV, G, hd), q.dtype),
        interpret=interpret,
        name=name,
    )(*scalars, layer, *args)
    return out.reshape(R, H, hd)


@functools.partial(
    jax.jit, static_argnames=("window", "pp", "bkv", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,            # [B, H, hd]
    k_pool: jnp.ndarray,       # [L, P, ps, KV·hd]  (uint8: KV·hd/2)
    v_pool: jnp.ndarray,
    tbl: jnp.ndarray,          # [B, pages_per_seq] int32
    last_pos: jnp.ndarray,     # [B] int32, newest valid position (-1 = idle)
    layer: jnp.ndarray,        # int32 scalar: the layer of the stack read
    k_scale: jnp.ndarray = None,   # [L, P, ps, KV] f32 when quantized
    v_scale: jnp.ndarray = None,
    window: int = 0,
    pp: int = 4,               # pages per program (autotuned: attn.paged_decode)
    bkv: int = 0,              # KV-head tile, 0 = all heads
    interpret: bool = None,
) -> jnp.ndarray:
    P, ps = k_pool.shape[1:3]
    pps = tbl.shape[1]
    pp = max(1, min(pp, pps))

    def page_index(u):
        # the scalar-prefetched block table turns the logical page into a
        # physical pool index right in the index_map: the pipeline DMAs the
        # page from wherever it lives, no gather ever materializes.  Past
        # the row's last live page the index stops changing (`live_page`),
        # so dead pages are never copied.  An idle row's table slot may
        # carry the out-of-bounds sentinel (== P); clamp so the DMA stays
        # in bounds — the kernel skips that page anyway.
        def index(b, j, tbl_ref, lp_ref):
            logical = live_page(j, u, lp_ref[b], pp=pp, ps=ps, window=window)
            return jnp.minimum(tbl_ref[b, logical], P - 1)
        return index

    return page_walk_call(
        _decode_kernel, (tbl.astype(jnp.int32), last_pos.astype(jnp.int32)),
        q, k_pool, v_pool, layer, k_scale, v_scale, page_index=page_index,
        pp=pp, bkv=bkv, nj=-(-pps // pp), window=window,
        interpret=default_interpret(interpret), name="paged_decode_attention")


@functools.partial(jax.jit, static_argnames=("window", "pp"))
def paged_decode_attention_xla(
    q, k_pool, v_pool, tbl, last_pos, layer, k_scale=None, v_scale=None,
    window: int = 0, pp: int = 4,
) -> jnp.ndarray:
    """Pure-XLA twin, *bit-identical to the gather reference* by
    construction: two dynamic-trip-count passes over page blocks —

      1. blocked QK into a [B, KV, G, max_ctx] f32 score buffer (scores are
         tiny: no hd factor, ~1/2*hd the bytes of the dense KV gather),
      2. the exact softmax + probs->compute-dtype cast the dense path runs
         on its materialized scores,
      3. blocked PV with f32 partial accumulation.

    Both loops stop at the last *active* page in the batch, so per-step
    work scales with the actual context, not the pool bound, and the dense
    [B, max_ctx, KV, hd] K/V buffers never exist.  Each block gathers its
    pages of `layer` straight out of the layer-stacked pool.  This is what
    keeps the `--layout compare` harness token-identical across contiguous,
    paged-gather, and paged-fused on CPU hosts."""
    B, H, hd = q.shape
    P, ps = k_pool.shape[1:3]
    KV = pool_heads(k_pool, hd)
    pps = tbl.shape[1]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    cd = q.dtype
    quant = k_scale is not None

    pp = max(1, min(pp, pps))
    nj = -(-pps // pp)
    tokens = pp * ps
    S = nj * tokens
    # pad the table so each block slices pp whole columns; padded columns
    # carry the out-of-bounds sentinel like dead slots do — their positions
    # are past last_pos, so their (clamped-gather) data masks away through
    # zero probs in the PV loop
    tbl_p = jnp.pad(tbl.astype(jnp.int32), ((0, 0), (0, nj * pp - pps)),
                    constant_values=P)
    last_pos = last_pos.astype(jnp.int32)
    q4 = q.reshape(B, KV, G, hd)
    steps = jnp.clip((jnp.max(last_pos) + tokens) // tokens, 1, nj)

    def block(pool, scales, j):
        """Block j's pages of every row: [B, tokens, KV, hd]."""
        cols = jax.lax.dynamic_slice_in_dim(tbl_p, j * pp, pp, 1)  # [B, pp]
        kb = pool_values(pool[layer, cols], quant).reshape(B, tokens, KV, -1)
        if quant:
            kb = _dequant_slab(
                kb, scales[layer, cols].reshape(B, tokens, KV, 1), hd)
        return kb

    def qk_body(carry):
        j, sbuf = carry
        kb = block(k_pool, k_scale, j)
        s = jnp.einsum("bkgh,btkh->bkgt", q4, kb.astype(cd))
        s = _round_scores(s, cd) * scale           # [B, KV, G, tokens]
        return j + 1, jax.lax.dynamic_update_slice(
            sbuf, s, (0, 0, 0, j * tokens))

    _, sbuf = jax.lax.while_loop(
        lambda c: c[0] < steps, qk_body,
        (jnp.zeros((), jnp.int32),
         jnp.full((B, KV, G, S), NEG_INF, jnp.float32)))

    pos = jnp.arange(S, dtype=jnp.int32)
    mask = (pos[None, :] <= last_pos[:, None]) & (last_pos >= 0)[:, None]
    if window:
        mask &= (last_pos[:, None] - pos[None, :]) < window
    sbuf = jnp.where(mask[:, None, None, :], sbuf, NEG_INF)
    probs = jax.nn.softmax(sbuf, axis=-1).astype(
        jnp.bfloat16 if quant else FLOAT_OF_BITS[v_pool.dtype])

    def pv_body(carry):
        j, acc = carry
        # dead table slots hold the out-of-bounds sentinel (== P); the
        # gather clamps them to the last physical page, whose masked
        # positions contribute exactly 0 via zero probs.  (Finite-garbage
        # safe, like the pre-sentinel code; the NaN-proof zero-fill lives
        # in paged_read — zeroing V per block here costs 10-25% of the
        # decode step for a hazard only a NaN-poisoned pool can hit.)
        vb = block(v_pool, v_scale, j)
        p = jax.lax.dynamic_slice_in_dim(probs, j * tokens, tokens, 3)
        pv = jnp.einsum("bkgt,btkh->bkgh", p, vb,
                        preferred_element_type=jnp.float32)
        return j + 1, acc + pv

    _, acc = jax.lax.while_loop(
        lambda c: c[0] < steps, pv_body,
        (jnp.zeros((), jnp.int32), jnp.zeros((B, KV, G, hd), jnp.float32)))
    # fully-masked rows see a uniform softmax over NEG_INF scores; zero them
    # explicitly (the kernel's l>0 guard does the same) — their output is
    # discarded but must stay finite and deterministic
    acc *= (last_pos >= 0)[:, None, None, None]
    return acc.reshape(B, H, hd).astype(q.dtype)


# ------------------------------------------------------- prefill (flash) ----
def _prefill_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, nk: int, G: int, bkv: int,
                    window: int, scale: float):
    """Head-major tiles: q [bkv*G, bq, hd], k/v [bkv, bk, hd], so each
    query head is a leading-index [bq, hd] slice and every dot is a plain
    2-D MXU matmul — no in-kernel reshape or transpose for Mosaic to
    re-tile."""
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cd = q_ref.dtype
    qp, kp = qp_ref[0], kp_ref[0]                  # [bq, 1], [1, bk]
    mask = (qp >= kp) & (kp >= 0)
    if window:
        mask &= (qp - kp) < window

    for h in range(bkv):                           # static unroll: KV heads
        kb = k_ref[0, h].astype(cd)                # [bk, hd]
        vb = v_ref[0, h].astype(jnp.float32)
        for g in range(G):                         # ... and their q heads
            r = h * G + g
            s = jax.lax.dot_general(
                q_ref[0, r], kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # [bq, bk]
            s = jnp.where(mask, _round_scores(s, cd) * scale, NEG_INF)
            m_prev = m_ref[r]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_ref[r] = l_ref[r] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[r] = acc_ref[r] * alpha + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)    # [bq, hd]
            m_ref[r] = m_new

    @pl.when(kk == nk - 1)
    def _emit():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "bq", "bk", "bkv", "interpret"))
def flash_prefill(
    q: jnp.ndarray,            # [B, Sq, H, hd]
    k: jnp.ndarray,            # [B, Skv, KV, hd]
    v: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, Sq] int32 (-1 = pad)
    k_positions: jnp.ndarray,  # [B, Skv] int32 (-1 = pad)
    window: int = 0,
    bq: int = 128,
    bk: int = 128,
    bkv: int = 0,
    interpret: bool = None,
) -> jnp.ndarray:
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    assert H % KV == 0, (H, KV)           # query heads tile evenly over KV heads
    G = H // KV
    bq = min(bq, max(8, Sq))
    bk = min(bk, max(8, k.shape[1]))
    bkv = _largest_divisor(KV, bkv if bkv > 0 else KV)
    assert KV % bkv == 0, (KV, bkv)       # _largest_divisor contract
    interpret = default_interpret(interpret)

    def padq(x, value=0):
        pad = (-x.shape[1]) % bq
        widths = [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)
        return jnp.pad(x, widths, constant_values=value) if pad else x

    def padk(x, value=0):
        pad = (-x.shape[1]) % bk
        widths = [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2)
        return jnp.pad(x, widths, constant_values=value) if pad else x

    # head-major layouts for the kernel (XLA transposes, outside it):
    # q/out [B, H, S, hd], k/v [B, KV, S, hd]; positions as a q column
    # [B, S, 1] and a k row [B, 1, S] so the mask is a 2-D broadcast
    head_major = functools.partial(jnp.transpose, axes=(0, 2, 1, 3))
    qp = head_major(padq(q))
    kp_, vp_ = head_major(padk(k)), head_major(padk(v))
    qpos = padq(q_positions.astype(jnp.int32), value=-1)[:, :, None]
    kpos = padk(k_positions.astype(jnp.int32), value=-1)[:, None, :]
    nq, nk = qp.shape[2] // bq, kp_.shape[2] // bk
    nh = KV // bkv

    kernel = functools.partial(
        _prefill_kernel, nk=nk, G=G, bkv=bkv, window=window,
        scale=1.0 / math.sqrt(hd))
    out = pl.pallas_call(
        kernel,
        grid=(B, nh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bkv * G, bq, hd),
                         lambda b, h, i, kk: (b, h, i, 0)),
            pl.BlockSpec((1, bkv, bk, hd), lambda b, h, i, kk: (b, h, kk, 0)),
            pl.BlockSpec((1, bkv, bk, hd), lambda b, h, i, kk: (b, h, kk, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, h, i, kk: (b, i, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, kk: (b, 0, kk)),
        ],
        out_specs=pl.BlockSpec((1, bkv * G, bq, hd),
                               lambda b, h, i, kk: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bkv * G, bq, hd), jnp.float32),
            pltpu.VMEM((bkv * G, bq, 1), jnp.float32),
            pltpu.VMEM((bkv * G, bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_prefill",
    )(qp, kp_, vp_, qpos, kpos)
    return head_major(out)[:, :Sq]


@functools.partial(jax.jit, static_argnames=("window", "bk"))
def flash_prefill_xla(
    q, k, v, q_positions, k_positions, window: int = 0, bk: int = 128,
) -> jnp.ndarray:
    """Pure-XLA twin: lax.scan over kv tiles with the same online-softmax
    carry — peak score memory is [B, KV, G, Sq, bk], never [Sq, Skv]."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    cd = q.dtype
    bk = min(bk, max(8, k.shape[1]))

    pad = (-k.shape[1]) % bk
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)),
                              constant_values=-1)
    nk = k.shape[1] // bk
    qg = q.reshape(B, Sq, KV, G, hd)
    qpos = q_positions.astype(jnp.int32)

    def tiles(x):
        return jnp.moveaxis(
            x.reshape(B, nk, bk, *x.shape[2:]), 1, 0)  # [nk, B, bk, ...]

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, kposb = xs
        s = jnp.einsum("bqkgh,btkh->bkgqt", qg, kb.astype(cd))
        s = _round_scores(s, cd) * scale
        mask = (qpos[:, :, None] >= kposb[:, None, :]) \
            & (kposb[:, None, :] >= 0)
        if window:
            mask &= (qpos[:, :, None] - kposb[:, None, :]) < window
        mask = mask[:, None, None]                 # [B, 1, 1, Sq, bk]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bkgqt,btkh->bkgqh", p, vb.astype(jnp.float32))
        return (m_new, l, alpha * acc + pv), None

    init = (jnp.full((B, KV, G, Sq, 1), NEG_INF, jnp.float32),
            jnp.zeros((B, KV, G, Sq, 1), jnp.float32),
            jnp.zeros((B, KV, G, Sq, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        step, init, (tiles(k), tiles(v), tiles(k_positions.astype(jnp.int32))))
    out = acc / jnp.where(l > 0, l, 1.0)           # [B, KV, G, Sq, hd]
    return (out.transpose(0, 3, 1, 2, 4)
            .reshape(B, Sq, H, hd).astype(q.dtype))

"""GQA attention with RoPE/M-RoPE, qk-norm, sliding windows, quantizable
KV cache, and two interchangeable implementations:

  * ``full``    -- materialized scores (cost-probe variant; exact HLO FLOPs)
  * ``chunked`` -- lax.map over query chunks against the full K/V (memory-
                   bounded for 32k prefill; production variant)

All projections route through the paper's QuantizedLinear (`qdense`).

KV caches are ring buffers of `min(seq, window)` slots carrying an absolute-
position tensor `kpos` [B, size] (-1 = empty), which makes causal/window/
validity masking uniform across full and sliding-window caches and across
prefill/decode.  `cache_dtype="int8"` stores quantized K/V with per-token
scales (a §Perf memory-term lever: ~2x less decode traffic than bf16).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, Runtime
from repro.core.qlinear import qdense
from repro.core.quant_plan import join_site
from repro.distributed.sharding import dp_axes, shard, shard_spec, tp_size
from .common import apply_mrope, apply_rope, normal_init, rms_norm

NEG_INF = -1e30

#: the leaves of a paged attention cache that hold the layer-stacked pool
#: (serving.kv_pages); the layer loop passes them through whole
POOL_LEAVES = ("k", "v", "k_scale", "v_scale")


def _attn_strategy(n_units: int, seq: int) -> str:
    """How to use the TP axis inside the attention core:
      head -- units divide TP: classic Megatron head sharding;
      seq  -- units don't divide but the (chunk) sequence does: shard query
              positions on TP, replicate K/V (context parallelism; the k/v
              replication traffic is tiny vs replicating score FLOPs 16x);
      none -- decode / tiny shapes: replicate heads.
    Never let GSPMD partial-shard `hd` — that turns the attention backward
    into giant score all-reduces (measured in EXPERIMENTS.md §Perf)."""
    tp = tp_size()
    if tp <= 1:
        return "none"
    if n_units % tp == 0:
        return "head"
    if seq > 1 and seq % tp == 0:
        return "seq"
    return "none"


def _constrain(t: jnp.ndarray, strategy: str, batch_sharded: bool,
               *, unit_axis: int = 2, seq_axis: int = 1,
               kv_in_seq: bool = False):
    if tp_size() <= 1:
        return t
    dpa = dp_axes()
    dspec = (dpa if len(dpa) > 1 else (dpa[0] if dpa else None)) \
        if batch_sharded else None
    ax = [None] * t.ndim
    ax[0] = dspec
    if strategy == "head":
        ax[unit_axis] = "model"
    elif strategy == "seq" and not kv_in_seq:
        ax[seq_axis] = "model"
    # strategy none / kv under seq-sharding: replicated over model
    return shard_spec(t, P(*ax))


def init_attention(key, cfg: ArchConfig) -> Dict:
    hd, H, KV, D = cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": normal_init(ks[0], (D, H * hd)),
        "wk": normal_init(ks[1], (D, KV * hd)),
        "wv": normal_init(ks[2], (D, KV * hd)),
        "wo": normal_init(ks[3], (H * hd, D), fan_in=H * hd),
    }
    if cfg.qkv_bias:
        p["wq_bias"] = jnp.zeros((H * hd,))
        p["wk_bias"] = jnp.zeros((KV * hd,))
        p["wv_bias"] = jnp.zeros((KV * hd,))
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,))
        p["k_norm"] = jnp.ones((hd,))
    return p


# ----------------------------------------------------------- KV cache ------
def init_attn_cache(cfg: ArchConfig, rt: Runtime, batch: int, seq: int) -> Dict:
    """Cache for one attention layer. `seq` = max context length."""
    size = min(seq, cfg.local_window) if cfg.local_window else seq
    kv, hd = cfg.n_kv_heads, cfg.hd
    cache = {
        "pos": jnp.zeros((batch,), jnp.int32),
        "kpos": jnp.full((batch, size), -1, jnp.int32),
    }
    if rt.cache_dtype == "int8":
        z = jnp.zeros((batch, size, kv, hd), jnp.int8)
        s = jnp.zeros((batch, size, kv, 1), jnp.float32)
        cache.update({"k": z, "v": z, "k_scale": s, "v_scale": s})
    elif rt.cache_dtype == "int4":
        # the paper's 4-bit format applied to the KV cache: packed nibble
        # pairs + per-(token, head) scales — 4x fewer cache bytes than bf16
        z = jnp.zeros((batch, size, kv, hd // 2), jnp.uint8)
        s = jnp.zeros((batch, size, kv, 1), jnp.float32)
        cache.update({"k": z, "v": z, "k_scale": s, "v_scale": s})
    else:
        dt = jnp.bfloat16 if rt.cache_dtype == "bfloat16" else jnp.float32
        z = jnp.zeros((batch, size, kv, hd), dt)
        cache.update({"k": z, "v": z})
    return cache


def quantize_kv(val, int4: bool):
    """Per-(token, head) absmax quantization of K/V slabs [..., hd].
    Shared by the contiguous ring cache and the paged pool (kv_pages) so the
    two layouts stay bit-identical."""
    qmax = 7.0 if int4 else 127.0
    scale = jnp.max(jnp.abs(val), axis=-1, keepdims=True) / qmax + 1e-8
    q = jnp.clip(jnp.round(val / scale), -qmax, qmax).astype(jnp.int8)
    if int4:
        from repro.core.quant import pack_int4

        q = pack_int4(q, axis=-1)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q, scale):
    """Inverse of quantize_kv (uint8 => packed nibbles)."""
    if q.dtype == jnp.uint8:
        from repro.core.quant import unpack_int4

        q = unpack_int4(q, axis=-1)
    return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def _scatter_time(buf, val, slots):
    """buf [B, size, ...] <- val [B, n, ...] at slot indices slots [B, n].
    Out-of-range slots (the drop sentinel for pad/invalid positions) are
    silently discarded."""
    bidx = jnp.arange(buf.shape[0])[:, None] * jnp.ones_like(slots)
    return buf.at[bidx, slots].set(val.astype(buf.dtype), mode="drop")


def _dus_time(buf, val, start):
    """buf [B, size, ...] <- val [B, n, ...] at contiguous slots from scalar
    `start`.  dynamic-update-slice instead of scatter: 5x cheaper in the XLA
    cost model and genuinely faster on TPU (no index vector materialized)."""
    idx = (0, start) + (0,) * (buf.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, val.astype(buf.dtype), idx)


def _cache_write(cache: Dict, k, v, abs_pos, aligned: bool = False) -> Dict:
    """Write k/v [B, n, KV, hd] whose absolute positions are abs_pos [B, n].

    `aligned=True` asserts every batch row writes the same positions
    (step-aligned serving): contiguous DUS writes (positions must not wrap
    mid-range — callers pass n=1 or a non-wrapping prefill range).  In the
    scatter path, negative positions (left-pad / inactive serving rows) are
    routed out of bounds and dropped.
    """
    size = cache["k"].shape[1]
    slots = jnp.where(abs_pos >= 0, abs_pos % size, size)   # size => dropped
    out = dict(cache)
    write = ((lambda buf, val: _dus_time(buf, val, slots[0, 0]))
             if aligned else (lambda buf, val: _scatter_time(buf, val, slots)))
    if "k_scale" in cache:
        int4 = cache["k"].dtype == jnp.uint8        # packed-nibble cache
        for name, val in (("k", k), ("v", v)):
            q, scale = quantize_kv(val, int4)
            out[name] = write(cache[name], q)
            out[name + "_scale"] = write(cache[name + "_scale"], scale)
    else:
        out["k"] = write(cache["k"], k)
        out["v"] = write(cache["v"], v)
    out["kpos"] = write(cache["kpos"], abs_pos)
    return out


def _cache_read(cache: Dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if "k_scale" in cache:
        return (dequantize_kv(cache["k"], cache["k_scale"]),
                dequantize_kv(cache["v"], cache["v_scale"]))
    return cache["k"], cache["v"]


# ------------------------------------------------------------ core ---------
def _gqa_block(q, k, v, mask, batch_sharded=True):
    """q [B,n,KV,G,hd]; k/v [B,Skv,KV,hd]; mask [B,n,Skv] bool."""
    strategy = _attn_strategy(k.shape[2], q.shape[1])
    q = _constrain(q, strategy, batch_sharded)
    k = _constrain(k, strategy, batch_sharded, kv_in_seq=True)
    v = _constrain(v, strategy, batch_sharded, kv_in_seq=True)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqkgh,btkh->bkgqt", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqt,btkh->bqkgh", probs.astype(v.dtype), v)
    return _constrain(out, strategy, batch_sharded)


def attention_core(
    q: jnp.ndarray,                 # [B, Sq, H, hd]
    k: jnp.ndarray,                 # [B, Skv, KV, hd]
    v: jnp.ndarray,
    *,
    q_positions: jnp.ndarray,       # [B, Sq]
    k_positions: jnp.ndarray,       # [B, Skv]
    window: int,
    impl: str,
    chunk_q: int,
    tag: str = "",
) -> jnp.ndarray:
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    tp = tp_size()
    batch_sharded = B > 1
    if impl == "flash" and tp <= 1:
        # tiled online-softmax prefill (kernels.paged_attention): scores
        # only ever exist as [bq, bk] tiles.  TP runs keep the sharded
        # chunked path — the flash kernel carries no partition constraints.
        from repro.kernels import ops

        return ops.flash_prefill(q, k, v, q_positions, k_positions,
                                 window=window, tag=tag)
    if impl == "flash":
        impl = "chunked"
    if tp > 1 and KV % tp != 0 and H % tp == 0:
        # Megatron-style KV-head duplication: q-heads shard on TP, each
        # shard holds copies of the KV heads it needs (no cross-shard math).
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        KV = H
    qg = q.reshape(B, Sq, KV, H // KV, hd)

    def mask3(qpos):                         # [B, n, Skv]
        m = (qpos[:, :, None] >= k_positions[:, None, :]) \
            & (k_positions[:, None, :] >= 0)
        if window:
            m &= (qpos[:, :, None] - k_positions[:, None, :]) < window
        return m

    if impl == "full" or Sq <= chunk_q:
        out = _gqa_block(qg, k, v, mask3(q_positions), batch_sharded)
        return out.reshape(B, Sq, H, hd)

    nq = -(-Sq // chunk_q)
    pad = nq * chunk_q - Sq
    qg_p = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qpos_p = jnp.pad(q_positions, ((0, 0), (0, pad)), constant_values=-1)
    qg_b = qg_p.reshape(B, nq, chunk_q, KV, H // KV, hd).swapaxes(0, 1)
    qpos_b = qpos_p.reshape(B, nq, chunk_q).swapaxes(0, 1)

    out = jax.lax.map(
        lambda args: _gqa_block(args[0], k, v, mask3(args[1]), batch_sharded),
        (qg_b, qpos_b)
    )
    out = out.swapaxes(0, 1).reshape(B, nq * chunk_q, KV, H // KV, hd)[:, :Sq]
    return out.reshape(B, Sq, H, hd)


# ------------------------------------------------------------ module -------
def apply_attention(
    params: Dict,
    x: jnp.ndarray,                  # [B, S, D]
    cfg: ArchConfig,
    rt: Runtime,
    positions: jnp.ndarray,          # [B, S] (or [3, B, S] for mrope)
    cache: Optional[Dict] = None,
    update_cache: bool = False,
    site: str = "",
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    # one site string keys both the plan's backend choice and per-call-site
    # tile tuning in kernels.autotune (QKV share a GEMM shape per config so
    # they share a site; wo differs)
    qkv_site = join_site(site, "attn.qkv")
    wo_site = join_site(site, "attn.wo")
    qc = rt.quant_cfg(cfg, qkv_site)
    q = qdense(params["wq"], x, qc, params.get("wq_bias"), tag=qkv_site)
    k = qdense(params["wk"], x, qc, params.get("wk_bias"), tag=qkv_site)
    v = qdense(params["wv"], x, qc, params.get("wv_bias"), tag=qkv_site)
    q = shard(q, "act_bthd")
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)

    tpos = positions if positions.ndim == 2 else positions[0]  # temporal
    if cfg.rope == "rope":
        q = apply_rope(q, tpos, cfg.rope_theta)
        k = apply_rope(k, tpos, cfg.rope_theta)
    elif cfg.rope == "mrope":
        mp = positions if positions.ndim == 3 else jnp.stack([tpos] * 3)
        q = apply_mrope(q, mp, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mp, cfg.rope_theta, cfg.mrope_sections)

    new_cache = None
    if cache is not None and "slots" in cache:
        # ---- ragged token-major step: B == 1, S == packed token rows ------
        # Every row (prefill-chunk slice or decode token) routes through the
        # block-table row cache["slots"] names.  Write-then-attend makes one
        # mask rule exact for both: the chunk's K/V lands in the pool first,
        # so pos <= token_pos is causal for prefill rows and last-token for
        # decode rows (see kernels.ragged_attention).
        from repro.kernels import ops
        from repro.serving.kv_pages import ragged_paged_write

        new_cache = ragged_paged_write(cache, k, v, tpos)
        out = ops.ragged_paged_attention(
            q[0], new_cache["k"], new_cache["v"], new_cache["tbl"],
            cache["slots"], tpos[0], cache["layer"],
            new_cache.get("k_scale"), new_cache.get("v_scale"),
            window=cfg.local_window,
            tag=join_site(site, "attn.ragged"),
        )[None]
    elif cache is not None and "tbl" in cache:
        # ---- paged KV (serving): block table + the layer-stacked pool,
        # read and written at cache["layer"] (serving/kv_pages) ------------
        from repro.serving.kv_pages import paged_read, paged_write

        if S == 1:
            new_cache = paged_write(cache, k, v, tpos)
            if rt.paged_attn == "fused" and tp_size() <= 1:
                # decode: consume the pages where they live — the fused
                # kernel walks the block table with online-softmax
                # accumulation; no paged_read, no dense KV materialization
                from repro.kernels import ops

                out = ops.paged_decode_attention(
                    q[:, 0], new_cache["k"], new_cache["v"],
                    new_cache["tbl"], tpos[:, -1], cache["layer"],
                    new_cache.get("k_scale"), new_cache.get("v_scale"),
                    window=cfg.local_window,
                    tag=join_site(site, "attn.paged_decode"),
                )[:, None]
            else:
                # gather baseline (and TP fallback): reconstruct the dense
                # layout, attend over it — the bit-exactness reference
                kf, vf, kpos = paged_read(new_cache, tpos[:, -1], KV)
                out = attention_core(
                    q, kf, vf,
                    q_positions=tpos, k_positions=kpos,
                    window=cfg.local_window, impl="full",
                    chunk_q=rt.attn_chunk_q,
                )
        elif rt.prefill_over_cache:
            # tail prefill (prefix-cache hit): the query covers only the
            # uncached suffix; its keys join the prefix K/V already living
            # in shared pages, so write the suffix first and attend over
            # the gathered pool — the same dense layout the decode gather
            # baseline reconstructs, with kpos masking the empty slots.
            new_cache = paged_write(cache, k, v, tpos) if update_cache \
                else cache
            kf, vf, kpos = paged_read(new_cache, tpos[:, -1], KV)
            out = attention_core(
                q, kf, vf,
                q_positions=tpos, k_positions=kpos,
                window=cfg.local_window, impl=rt.attn_impl,
                chunk_q=rt.attn_chunk_q, tag=join_site(site, "attn.prefill"),
            )
        else:
            # prefill: the prompt is the whole context — attend in-flight,
            # write it into the pages for later decode steps
            out = attention_core(
                q, k, v,
                q_positions=tpos, k_positions=tpos,
                window=cfg.local_window, impl=rt.attn_impl,
                chunk_q=rt.attn_chunk_q, tag=join_site(site, "attn.prefill"),
            )
            if update_cache:
                new_cache = paged_write(cache, k, v, tpos)
    elif cache is not None and S == 1:
        # ---- decode: append one token, attend over the cache --------------
        new_cache = _cache_write(cache, k, v, tpos, aligned=rt.aligned_decode)
        new_cache["pos"] = cache["pos"] + 1
        kf, vf = _cache_read(new_cache)
        out = attention_core(
            q, kf, vf,
            q_positions=tpos, k_positions=new_cache["kpos"],
            window=cfg.local_window, impl="full", chunk_q=rt.attn_chunk_q,
        )
    else:
        # ---- train / prefill ----------------------------------------------
        out = attention_core(
            q, k, v,
            q_positions=tpos, k_positions=tpos,
            window=cfg.local_window, impl=rt.attn_impl, chunk_q=rt.attn_chunk_q,
            tag=join_site(site, "attn.prefill"),
        )
        if update_cache and cache is not None:
            size = cache["k"].shape[1]
            take = min(S, size)
            # prefill fills a contiguous, non-wrapping range: DUS-safe when
            # batch-aligned (ring wrap only matters once pos > size, i.e.
            # decode, which writes single slots)
            wpos = tpos[:, -take:]
            if not rt.aligned_decode:
                # chunked prefill can re-present already-cached positions
                # (a resume landing mid-way through a partial page): write
                # only the uncovered suffix — covered slots are routed to
                # the drop sentinel instead of re-scattered.  The aligned
                # path keeps its single DUS (a -1 would skew its start slot
                # and clamp the write onto the ring tail).
                wpos = jnp.where(wpos >= cache["pos"][:, None], wpos, -1)
            new_cache = _cache_write(
                cache, k[:, -take:], v[:, -take:], wpos,
                aligned=rt.aligned_decode,
            )
            new_cache["pos"] = cache["pos"] + S

    out = out.reshape(B, S, H * hd)
    y = qdense(params["wo"], out, rt.quant_cfg(cfg, wo_site), tag=wo_site)
    return shard(y, "act_btd"), new_cache

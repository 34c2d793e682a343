"""Public entry points for the kernels: block-size lookup and backend
dispatch in one place.

Dispatch (the `interpret` argument):

  True   -- Pallas kernel through the interpreter (kernel-body tests on CPU)
  False  -- Pallas kernel compiled through Mosaic (TPU)
  None   -- ``REPRO_PALLAS_INTERPRET`` env if set ("0" => compile,
            anything else => interpret); otherwise Pallas-Mosaic on TPU and
            the pure-XLA twin elsewhere.  The interpreter is a debugging
            tool, not an execution path: on CPU/GPU the XLA twin is the
            same math at full XLA speed.

Weight layout: every GEMM takes its int4 weight in the one packed layout,
planar K-major ``[K'/2, N]`` uint8 (``packing.pack_kmajor``; K' is K
rounded up to whole planar halves, to a multiple of 2G for grouped
scales).  The serving packer (``core.qlinear.pack_weight_nd``) writes that
layout once; nothing here converts.

Block sizes: resolved per GEMM shape through ``kernels.autotune`` unless
explicitly overridden (bm=/bn=/bk= kwargs).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.observability.metrics import global_registry

from . import autotune, paged_attention, ragged_attention, ref
from .dispatch import env_interpret
from .int4_matmul import int4_matmul as _int4_matmul
from .int4_matmul import int4_matmul_fused as _int4_matmul_fused
from .lut4_matmul import lut4_matmul as _lut4_matmul
from .lut_mul4 import lut_mul4 as _lut_mul4
from .w4a16_matmul import w4a16_matmul as _w4a16_matmul

_PALLAS, _INTERPRET, _XLA = "pallas", "interpret", "xla"


def _mode(interpret: Optional[bool]) -> str:
    if interpret is True:
        return _INTERPRET
    if interpret is False:
        return _PALLAS
    env = env_interpret()
    if env is not None:
        return _INTERPRET if env else _PALLAS
    return _PALLAS if jax.default_backend() == "tpu" else _XLA


def _count_dispatch(op: str, mode: str) -> None:
    """Record a backend-dispatch decision in the process-global registry
    (these wrappers are module-level, with no engine to hang off).  Fires
    at trace time, so the count is per *compiled program* that uses the op
    — a steady-state serving run shows one bump per (op, jit signature),
    not one per step; a climbing count during steady state is the same
    smell JitWatch flags."""
    global_registry().counter(
        "kernel_dispatch_total",
        "kernel backend-dispatch decisions (counted at trace time)",
        op=op, mode=mode).inc()


def use_pallas(interpret: Optional[bool] = None) -> bool:
    """True when the Pallas kernels (compiled or interpreted) would run."""
    return _mode(interpret) != _XLA


def _blocks(op: str, M: int, K: int, N: int, dtype, group_size: int,
            tag: str, overrides: dict) -> dict:
    b = autotune.get_blocks(op, M, K, N, jnp.dtype(dtype).name,
                            group_size=group_size, tag=tag)
    b.update({k: v for k, v in overrides.items() if v is not None})
    return b


def mul4(a_q, b_q, strategy: str = "onehot",
         interpret: Optional[bool] = None):
    """Elementwise exact int4 product."""
    m = _mode(interpret)
    _count_dispatch("mul4", m)
    if m == _XLA:
        return ref.mul4_ref(a_q, b_q)
    return _lut_mul4(a_q, b_q, strategy=strategy,
                     interpret=m == _INTERPRET)


def int4_matmul(a_q, a_scale, w_packed, w_scale,
                interpret: Optional[bool] = None, tag: str = "",
                bm=None, bn=None, bk=None):
    """W4A4 matmul with fused dequant epilogue; `w_packed` planar K-major
    [ceil(K/2), N] uint8."""
    m = _mode(interpret)
    _count_dispatch("int4_matmul", m)
    if m == _XLA:
        return ref.int4_matmul_ref(a_q, a_scale, w_packed, w_scale)
    M, K = a_q.shape
    b = _blocks("int4_matmul", M, K, w_packed.shape[1], a_q.dtype, 0, tag,
                {"bm": bm, "bn": bn, "bk": bk})
    return _int4_matmul(a_q, a_scale, w_packed, w_scale,
                        interpret=m == _INTERPRET, **b)


def lut4_matmul(a_q, a_scale, w_packed, w_scale,
                interpret: Optional[bool] = None, tag: str = "",
                bm=None, bn=None, bk=None):
    """Table-lookup W4A4 matmul (`kernels/lut4_matmul.py`); `w_packed`
    planar K-major [ceil(K/2), N] uint8.

    Block sizes resolve through ``kernels.autotune`` op ``gemm.lut4``, which
    carries its own candidate set (cost scales with the per-tile lookup loop
    over bk/2 packed rows, so it favors smaller bk than the MXU kernels).
    """
    m = _mode(interpret)
    _count_dispatch("lut4_matmul", m)
    if m == _XLA:
        # XLA twin: the exact product table is rank-1 (T[a, w] = a*w), so
        # the lookup-sum collapses to the int8 dot — bit-identical because
        # integer accumulation is exact (see ref.lut4_matmul_ref).
        return ref.int4_matmul_ref(a_q, a_scale, w_packed, w_scale)
    M, K = a_q.shape
    b = _blocks("gemm.lut4", M, K, w_packed.shape[1], a_q.dtype, 0, tag,
                {"bm": bm, "bn": bn, "bk": bk})
    return _lut4_matmul(a_q, a_scale, w_packed, w_scale,
                        interpret=m == _INTERPRET, **b)


def int4_matmul_fused(x, w_packed, w_scale,
                      interpret: Optional[bool] = None, tag: str = "",
                      bm=None, bn=None, bk=None):
    """Fused activation-quantize W4A4: float x in, quantize + matmul +
    dequant in one pallas_call (A4 activations never round-trip HBM);
    `w_packed` planar K-major [ceil(K/2), N] uint8."""
    m = _mode(interpret)
    _count_dispatch("int4_matmul_fused", m)
    if m == _XLA:
        return ref.int4_matmul_fused_ref(x, w_packed, w_scale)
    M, K = x.shape
    b = _blocks("int4_matmul_fused", M, K, w_packed.shape[1], x.dtype, 0,
                tag, {"bm": bm, "bn": bn, "bk": bk})
    return _int4_matmul_fused(x, w_packed, w_scale,
                              interpret=m == _INTERPRET, **b)


def w4a16_matmul(x, w_packed, w_scale, group_size: int,
                 interpret: Optional[bool] = None, tag: str = "",
                 bm=None, bn=None, bk=None):
    """Weight-only int4 matmul with per-group dequant; `w_packed` planar
    K-major [K'/2, N] uint8, K' a multiple of 2·group_size when the scales
    are grouped [K/G, 1, N] (each planar half covers whole groups)."""
    m = _mode(interpret)
    _count_dispatch("w4a16_matmul", m)
    if m == _XLA:
        return ref.w4a16_matmul_ref(x, w_packed, w_scale, group_size)
    M, K = x.shape
    g = 0 if w_scale.ndim == 2 else group_size
    b = _blocks("w4a16_matmul", M, K, w_packed.shape[1], x.dtype, g, tag,
                {"bm": bm, "bn": bn, "bk": bk})
    return _w4a16_matmul(x, w_packed, w_scale, group_size,
                         interpret=m == _INTERPRET, **b)


def _pool_dtype_name(pool) -> str:
    """A pool's autotune dtype: its values' (a float pool holds bits)."""
    return paged_attention.FLOAT_OF_BITS.get(pool.dtype, pool.dtype).name


def paged_decode_attention(q, k_pool, v_pool, tbl, last_pos, layer,
                           k_scale=None, v_scale=None, *, window: int = 0,
                           interpret: Optional[bool] = None, tag: str = ""):
    """Fused decode attention over the KV page pool (no gather, no dense
    [B, max_ctx] KV materialization).

    q [B, H, hd]; layer-stacked lane-dense pools [L, P, ps, KV·hd] (uint8:
    KV·hd/2; + per-token scales [L, P, ps, KV] when the cache is
    int8/int4), read at `layer` (int32 scalar); tbl [B, pages_per_seq];
    last_pos [B] (-1 = inactive row, masked to a zero output).  Tiles
    resolve through ``kernels.autotune`` op ``attn.paged_decode`` — page
    size rides in the key's group_size slot, ``bk`` is kv tokens per
    program, ``bn`` the KV-head tile.
    """
    m = _mode(interpret)
    _count_dispatch("paged_decode_attention", m)
    B, H, hd = q.shape
    ps = k_pool.shape[2]
    max_ctx = tbl.shape[1] * ps
    b = autotune.get_blocks("attn.paged_decode", B, max_ctx, H * hd,
                            _pool_dtype_name(k_pool), group_size=ps,
                            tag=tag)
    pp = max(1, b["bk"] // ps)
    if m == _XLA:
        return paged_attention.paged_decode_attention_xla(
            q, k_pool, v_pool, tbl, last_pos, layer, k_scale, v_scale,
            window=window, pp=pp)
    return paged_attention.paged_decode_attention(
        q, k_pool, v_pool, tbl, last_pos, layer, k_scale, v_scale,
        window=window, pp=pp, bkv=b["bn"], interpret=m == _INTERPRET)


def ragged_paged_attention(q, k_pool, v_pool, tbl, token_slot, token_pos,
                           layer, k_scale=None, v_scale=None, *,
                           window: int = 0,
                           interpret: Optional[bool] = None, tag: str = ""):
    """Ragged token-major attention over the KV page pool: one launch for a
    flat ``[T, H, hd]`` pack of mixed prefill-chunk and decode rows.

    q [T, H, hd]; layer-stacked pools as for ``paged_decode_attention``,
    read at `layer`; tbl [max_batch, pages_per_seq]; token_slot /
    token_pos [T] (-1 = padding row, masked to a zero output).  Tiles
    resolve through ``kernels.autotune`` op ``attn.ragged`` with the same
    entry semantics as ``attn.paged_decode``."""
    m = _mode(interpret)
    _count_dispatch("ragged_paged_attention", m)
    T, H, hd = q.shape
    ps = k_pool.shape[2]
    max_ctx = tbl.shape[1] * ps
    b = autotune.get_blocks("attn.ragged", T, max_ctx, H * hd,
                            _pool_dtype_name(k_pool), group_size=ps,
                            tag=tag)
    pp = max(1, b["bk"] // ps)
    if m == _XLA:
        return ragged_attention.ragged_attention_xla(
            q, k_pool, v_pool, tbl, token_slot, token_pos, layer, k_scale,
            v_scale, window=window, pp=pp)
    return ragged_attention.ragged_decode_attention(
        q, k_pool, v_pool, tbl, token_slot, token_pos, layer, k_scale,
        v_scale, window=window, pp=pp, bkv=b["bn"],
        interpret=m == _INTERPRET)


def flash_prefill(q, k, v, q_positions, k_positions, *, window: int = 0,
                  interpret: Optional[bool] = None, tag: str = ""):
    """Tiled flash prefill with causal/validity masking: scores only exist
    as [bq, bk] tiles (online softmax), never as the [S, S] matrix.

    q [B, Sq, H, hd]; k/v [B, Skv, KV, hd]; positions [B, S] (-1 = pad).
    Tiles resolve through ``kernels.autotune`` op ``attn.prefill``.
    """
    m = _mode(interpret)
    _count_dispatch("flash_prefill", m)
    B, Sq, H, hd = q.shape
    b = autotune.get_blocks("attn.prefill", Sq, k.shape[1], H * hd,
                            jnp.dtype(q.dtype).name, tag=tag)
    if m == _XLA:
        return paged_attention.flash_prefill_xla(
            q, k, v, q_positions, k_positions, window=window, bk=b["bk"])
    return paged_attention.flash_prefill(
        q, k, v, q_positions, k_positions, window=window,
        bq=b["bm"], bk=b["bk"], bkv=b["bn"], interpret=m == _INTERPRET)

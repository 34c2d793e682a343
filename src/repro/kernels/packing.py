"""Shared nibble pack/unpack layer for the quantized-GEMM kernels.

Two storage layouts for int4 tensors (two values per uint8 byte):

  * interleaved N-packed (``core.quant.pack_int4``): adjacent *columns*
    share a byte.  This is the serialization format (quantized checkpoints,
    ``plan_pack_tree`` serving weights) — compact and axis-generic, but the
    in-kernel unpack needs a stack+reshape interleave, which Mosaic lowers
    as a lane-axis relayout on the matmul critical path.
  * planar K-major (``pack_kmajor``): contraction rows ``k`` and
    ``k + K/2`` share a byte.  The low nibbles of a ``[K/2, N]`` tile *are*
    rows ``[0, K/2)`` and the high nibbles *are* rows ``[K/2, K)`` — the
    in-kernel unpack is a shift/mask with **no relayout**, and the two
    planar halves feed two MXU dots that accumulate into the same tile.

``prepack_kmajor`` converts serialized weights to the kernel layout once
per concrete array (cache keyed by ``id()``, weakref-evicted), so a serving
loop that calls the kernels every step with the same weight pays the
relayout exactly once instead of per call.

This module is self-contained (no repro imports): it is the single home of
the sign-extend / shift-mask helpers that used to be copy-pasted between
``int4_matmul.py`` and ``w4a16_matmul.py``.
"""

from __future__ import annotations

import functools
import weakref
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def pad_to(x: jnp.ndarray, mult: int, axis: int, value=0) -> jnp.ndarray:
    """Zero-pad (or `value`-pad) `axis` of x up to the next multiple of `mult`."""
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def unpack_nibbles(p: jnp.ndarray, dtype=jnp.int8
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """uint8 -> (lo, hi) sign-extended nibbles in [-8, 7] as `dtype`, each
    the same shape as `p`.

    The shift primitive shared by every kernel; what the nibbles *mean*
    (adjacent columns vs planar row halves) is the caller's layout contract.
    Sign extension runs in int32 — shift the nibble to the top of the word,
    then shift it back arithmetically — because the TPU vector unit has no
    int8 arithmetic (Mosaic refuses an int8 subtract).  The one cast at the
    end goes straight to the consumer's dtype (int8 for the MXU int8 dot,
    the activation dtype for W4A16)."""
    w = p.astype(jnp.int32)
    return ((w << 28) >> 28).astype(dtype), ((w << 24) >> 28).astype(dtype)


def unpack_interleaved(p: jnp.ndarray) -> jnp.ndarray:
    """Interleaved N-packed [..., K, N//2] uint8 -> [..., K, N] int8."""
    lo, hi = unpack_nibbles(p)
    return jnp.stack([lo, hi], axis=-1).reshape(
        *p.shape[:-1], p.shape[-1] * 2)


# ------------------------------------------------------- planar K-major ----
def pack_kmajor(q: jnp.ndarray, row_mult: int = 2) -> jnp.ndarray:
    """[..., K, N] int8 (int4 values) -> [..., K'/2, N] uint8, planar
    (K' = K rounded up to a multiple of `row_mult`, at least even).

    Row r of the packed array holds original row r in its low nibble and
    row r + K'/2 in its high nibble.  Padding rows are zero int4 values and
    contribute nothing to a contraction.  Grouped-scale consumers pass
    ``row_mult=2*group_size`` so each planar half covers whole groups.
    """
    q = pad_to(q, max(2, row_mult), -2)
    half = q.shape[-2] // 2
    lo = q[..., :half, :] & 0xF
    hi = q[..., half:, :] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_kmajor(p: jnp.ndarray) -> jnp.ndarray:
    """Inverse of pack_kmajor: [..., K/2, N] uint8 -> [..., K, N] int8."""
    lo, hi = unpack_nibbles(p)
    return jnp.concatenate([lo, hi], axis=-2)


@functools.partial(jax.jit, static_argnames="row_mult")
def nmajor_to_kmajor(w_packed: jnp.ndarray, row_mult: int = 2) -> jnp.ndarray:
    """Serialized interleaved [..., K, N//2] -> kernel planar [..., K'/2, N]
    (K' = K rounded up to a multiple of `row_mult`, at least even)."""
    return pack_kmajor(unpack_interleaved(w_packed), row_mult)


# ------------------------------------------- per-nibble product tables -----
@functools.lru_cache(maxsize=None)
def nibble_product_tables() -> Tuple[np.ndarray, np.ndarray]:
    """The paper's exact 4x4-bit product table, tiled for GEMM lookup.

    Returns ``(t_lo, t_hi)``, each ``[16, 256]`` int8 host arrays:

        t_lo[a, byte] = sext4(a) * sext4(byte & 0xF)
        t_hi[a, byte] = sext4(a) * sext4(byte >> 4)

    Row index = activation nibble (unsigned 2's-complement code), column
    index = a *packed K-major weight byte* — so a kernel holding packed
    weights never unpacks them: one row-select per activation nibble plus
    one lane-dim take per weight byte reads the sign-extended product
    directly.  Products of int4 values fit int8 (|p| <= 64).  8 KiB total,
    built once per process and shared by every weight tensor.
    """
    s = ((np.arange(16, dtype=np.int32) ^ 8) - 8)          # sext4 of 0..15
    byte = np.arange(256, dtype=np.int32)
    t_lo = s[:, None] * s[byte & 0xF][None, :]
    t_hi = s[:, None] * s[byte >> 4][None, :]
    return t_lo.astype(np.int8), t_hi.astype(np.int8)


@functools.lru_cache(maxsize=None)
def lut4_tables() -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Device-resident ``nibble_product_tables()`` (committed once, cached
    for the life of the process — the \"prepack\" of the LUT backend).

    ``ensure_compile_time_eval`` keeps the cached values concrete even when
    the first call happens under an outer trace (a tracer must never be
    memoized past its trace's lifetime)."""
    t_lo, t_hi = nibble_product_tables()
    with jax.ensure_compile_time_eval():
        return (jax.block_until_ready(jnp.asarray(t_lo)),
                jax.block_until_ready(jnp.asarray(t_hi)))


def table_take(table: jnp.ndarray, rows: jnp.ndarray,
               lanes: jnp.ndarray) -> jnp.ndarray:
    """Two-level vectorized table lookup: ``table[rows[i], lanes[i, j]]``.

    ``rows`` ``[m]`` selects one table row per output row (activation
    nibble); ``lanes`` ``[m, n]`` then takes along the lane dimension
    (packed weight byte).  Both steps are full-width vector ops — no
    per-element one-hot expansion, no scalar gather loop.
    """
    sel = jnp.take(table, rows, axis=0)          # [m, 256]
    return jnp.take_along_axis(sel, lanes, axis=-1)


# ------------------------------------------------- prepacked-weight cache --
# (id(src), row_mult) -> (weakref to src, kmajor-packed array).  The weakref
# callback evicts the entry when the source weight is garbage-collected, so
# the cache never outlives (or pins) the arrays it mirrors.
_PREPACKED: Dict[Tuple[int, int], Tuple[weakref.ref, jnp.ndarray]] = {}


def prepack_kmajor(w_packed: jnp.ndarray, row_mult: int = 2) -> jnp.ndarray:
    """`nmajor_to_kmajor`, cached by array identity for concrete arrays.

    Tracers (calls under an outer jit) convert inline — XLA sees the repack
    as part of the traced graph and CSEs/hoists what it can; concrete
    arrays (eager serving / benchmarks) repack exactly once per weight.
    """
    if isinstance(w_packed, jax.core.Tracer):
        return nmajor_to_kmajor(w_packed, row_mult)
    key = (id(w_packed), row_mult)
    hit = _PREPACKED.get(key)
    if hit is not None and hit[0]() is w_packed:
        return hit[1]
    out = jax.block_until_ready(nmajor_to_kmajor(w_packed, row_mult))
    try:
        ref = weakref.ref(w_packed, lambda _r, _k=key: _PREPACKED.pop(_k, None))
    except TypeError:                      # not weakref-able: skip caching
        return out
    _PREPACKED[key] = (ref, out)
    return out


def prepack_cache_size() -> int:
    return len(_PREPACKED)


def clear_prepack_cache() -> None:
    _PREPACKED.clear()


# ------------------------------------------------------- tile flattening ---
def flatten_to_tiles(x: jnp.ndarray, rows_mult: int, cols: int
                     ) -> Tuple[jnp.ndarray, int]:
    """Flatten any-shape x into a [rows, cols] tile grid, rows padded to a
    multiple of `rows_mult` (single jnp.pad — no O(n) scatter copy).

    Returns (tiles, n) where n is the original element count; undo with
    ``tiles.reshape(-1)[:n].reshape(orig_shape)``.
    """
    n = x.size
    rows = -(-n // cols)
    rows_padded = -(-rows // rows_mult) * rows_mult
    flat = jnp.pad(x.reshape(-1), (0, rows_padded * cols - n))
    return flat.reshape(rows_padded, cols), n

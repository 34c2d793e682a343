"""The int4 weight layout of the quantized GEMMs, and its nibble helpers.

One layout, planar K-major (``pack_kmajor``): a ``[..., K, N]`` int4
weight is stored as ``[..., K'/2, N]`` uint8, where contraction rows ``k``
and ``k + K'/2`` share a byte (K' is K rounded up to whole planar halves).
The packer (``core.qlinear.pack_weight_nd``) writes it once, quantized
checkpoints store it, and every GEMM entry point in ``kernels.ops`` reads
it as is.

Why planar: the low nibbles of a ``[K/2, N]`` tile *are* rows
``[0, K/2)`` and the high nibbles *are* rows ``[K/2, K)``, so the
in-kernel unpack is a shift/mask with **no relayout**, and the two planar
halves feed two MXU dots that accumulate into the same tile.  Packing two
adjacent output columns per byte instead would need a stack+reshape
interleave, which Mosaic lowers as a lane-axis relayout on the matmul's
critical path.  The N axis stays whole, so a column-sharded weight shards
its packed bytes the same way.

(The int4 KV cache packs along its last axis with ``core.quant.pack_int4``;
that is a cache format, not a weight layout.)

This module is self-contained (no repro imports): it is the single home of
the sign-extend / shift-mask helpers shared by ``int4_matmul.py``,
``w4a16_matmul.py`` and ``lut4_matmul.py``.
"""

from __future__ import annotations

import functools
from typing import Tuple

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
    (planar row halves of a weight, adjacent lanes of a KV row) is the
    caller's layout contract.
    Sign extension runs in int32 — shift the nibble to the top of the word,
    then shift it back arithmetically — because the TPU vector unit has no
    int8 arithmetic (Mosaic refuses an int8 subtract).  The one cast at the
    end goes straight to the consumer's dtype (int8 for the MXU int8 dot,
    the activation dtype for W4A16)."""
    w = p.astype(jnp.int32)
    return ((w << 28) >> 28).astype(dtype), ((w << 24) >> 28).astype(dtype)


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
    """Device-resident ``nibble_product_tables()``, committed on first use
    (when the first ``lut4`` GEMM is traced) and cached for the life of the
    process.

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

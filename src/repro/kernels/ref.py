"""Pure-jnp oracles for every kernel in `repro.kernels`.

These are the ground-truth semantics the Pallas kernels (and the FPGA netlist
simulation) are tested against with `assert_allclose` across shape/dtype
sweeps.  All integer paths are exact, so integer comparisons use equality.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .packing import nibble_product_tables, pad_to, unpack_kmajor


def make_product_lut() -> np.ndarray:
    """256-entry signed-int4 product table: LUT[(a&0xF)<<4 | (b&0xF)] = a*b.

    This is the TPU re-homing of the paper's LUT-based multiplier: the full
    4x4-bit product space precomputed into a table small enough to live in
    VMEM (256 bytes), indexed instead of recomputed.  A view of the GEMM
    tables: t_lo[a, byte] for byte < 16 has a zero high nibble, so its first
    16 columns are exactly sext4(a) * sext4(b).
    """
    t_lo, _ = nibble_product_tables()
    return np.ascontiguousarray(t_lo[:, :16]).reshape(256)


def mul4_ref(a_q: jnp.ndarray, b_q: jnp.ndarray) -> jnp.ndarray:
    """Elementwise exact int4*int4 -> int8 product (values in [-56, 64])."""
    return (a_q.astype(jnp.int32) * b_q.astype(jnp.int32)).astype(jnp.int8)


def int4_matmul_ref(
    a_q: jnp.ndarray,          # [M, K] int8 holding int4 values
    a_scale: jnp.ndarray,      # [M, 1] f32
    w_packed: jnp.ndarray,     # [ceil(K/2), N] uint8, planar K-major
    w_scale: jnp.ndarray,      # [1, N] f32
) -> jnp.ndarray:
    """W4A4 matmul: integer dot + per-row/per-col scale epilogue -> f32."""
    w_q = unpack_kmajor(w_packed)[: a_q.shape[1]]            # [K, N] int8
    acc = jnp.dot(
        a_q.astype(jnp.int8), w_q, preferred_element_type=jnp.int32
    )
    return acc.astype(jnp.float32) * a_scale * w_scale


def lut4_matmul_ref(
    a_q: jnp.ndarray,          # [M, K] int8 holding int4 values
    a_scale: jnp.ndarray,      # [M, 1] f32
    w_packed: jnp.ndarray,     # [ceil(K/2), N] uint8, planar K-major
    w_scale: jnp.ndarray,      # [1, N] f32
) -> jnp.ndarray:
    """Table-formulation W4A4 oracle: every partial product is *read* from
    the 16x256 per-nibble tables (never multiplied), then summed in int32.

    Materializes the [M, K/2, N] partial-product cube, so test shapes only.
    Bitwise equal to `int4_matmul_ref` because the exact product table is
    rank-1 (T[a, w] = a*w) and integer sums are exact — that identity is
    what makes the XLA twin of the `lut4` backend an int8 dot.
    """
    t_lo, t_hi = (jnp.asarray(t) for t in nibble_product_tables())
    wb = w_packed.astype(jnp.int32)                          # [Kh, N]
    kh = wb.shape[0]
    a = pad_to(a_q, 2 * kh, 1)
    u_lo = (a[:, :kh] & 0xF).astype(jnp.int32)               # [M, Kh]
    u_hi = (a[:, kh:] & 0xF).astype(jnp.int32)
    pp = (t_lo[u_lo[:, :, None], wb[None, :, :]].astype(jnp.int32)
          + t_hi[u_hi[:, :, None], wb[None, :, :]])          # [M, Kh, N]
    acc = jnp.sum(pp, axis=1, dtype=jnp.int32)
    return acc.astype(jnp.float32) * a_scale * w_scale


def int4_matmul_fused_ref(
    x: jnp.ndarray,            # [M, K] float activations
    w_packed: jnp.ndarray,     # [ceil(K/2), N] uint8, planar K-major
    w_scale: jnp.ndarray,      # [1, N] f32
) -> jnp.ndarray:
    """Oracle for the fused activation-quantize A4 path: dynamic per-row
    int4 quantization (same round/clip as core.quant.quantize) + W4A4."""
    from repro.core.quant import quant_scale, quantize

    x32 = x.astype(jnp.float32)
    a_scale = quant_scale(x32, axis=1, bits=4)
    a_q = quantize(x32, a_scale, bits=4)
    return int4_matmul_ref(a_q, a_scale, w_packed, w_scale)


def w4a16_matmul_ref(
    x: jnp.ndarray,            # [M, K] bf16/f32
    w_packed: jnp.ndarray,     # [K'/2, N] uint8, planar K-major
    w_scale: jnp.ndarray,      # [K//G, 1, N] f32 (or [1, N] per-channel)
    group_size: int,
) -> jnp.ndarray:
    """Weight-only int4 serving matmul: dequantize then bf16 GEMM -> f32."""
    w_q = unpack_kmajor(w_packed)[: x.shape[1]]              # [K, N] int8
    K, N = w_q.shape
    if w_scale.ndim == 2:
        w = w_q.astype(jnp.float32) * w_scale
    else:
        wg = w_q.reshape(K // group_size, group_size, N).astype(jnp.float32)
        w = (wg * w_scale).reshape(K, N)
    return jnp.dot(x.astype(jnp.float32), w, preferred_element_type=jnp.float32)

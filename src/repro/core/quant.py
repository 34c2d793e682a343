"""int4 quantization stack — the framework-level embodiment of the paper's
"dense arrays of 4-bit multipliers for edge inference" motivation (§I).

Symmetric signed-int4 quantization (q in [-8, 7], scale = amax/7) with
per-tensor / per-channel / per-group granularity, straight-through-estimator
fake-quant for QAT, and nibble packing (two int4 lanes per uint8 byte) for the
serving path consumed by ``repro.kernels``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

INT4_MIN, INT4_MAX = -8, 7


def _qrange(bits: int) -> Tuple[int, int]:
    return -(2 ** (bits - 1)), 2 ** (bits - 1) - 1


def quant_scale(
    x: jnp.ndarray, axis: Optional[int] = None, bits: int = 4, eps: float = 1e-8
) -> jnp.ndarray:
    """Symmetric scale; `axis=None` -> per-tensor, else reduce over `axis`."""
    _, qmax = _qrange(bits)
    if axis is None:
        amax = jnp.max(jnp.abs(x))
    else:
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    return jnp.maximum(amax, eps) / qmax


def quantize(x: jnp.ndarray, scale: jnp.ndarray, bits: int = 4) -> jnp.ndarray:
    qmin, qmax = _qrange(bits)
    q = jnp.clip(jnp.round(x / scale), qmin, qmax)
    return q.astype(jnp.int8)


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(scale.dtype) * scale


def fake_quant(
    x: jnp.ndarray, axis: Optional[int] = None, bits: int = 4
) -> jnp.ndarray:
    """Quantize-dequantize with a straight-through-estimator gradient (QAT).

    Scale/grid math runs in fp32 but the result keeps x.dtype, so bf16
    activations stay bf16 through the STE (otherwise every TP all-reduce in
    the backward doubles to fp32 width — a measured §Perf regression).
    """
    x32 = x.astype(jnp.float32)
    scale = quant_scale(x32, axis=axis, bits=bits)
    xq = dequantize(quantize(x32, scale, bits=bits), scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(xq - x)


def group_quantize(
    w: jnp.ndarray, group_size: int, bits: int = 4
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-group quantization along the *first* (reduction) axis of w [K, N].

    Returns (q [K, N] int8-with-int4-values, scales [K//G, 1, N]).
    """
    K, N = w.shape
    if group_size <= 0 or group_size >= K:
        scale = quant_scale(w, axis=0, bits=bits)          # per-output-channel
        return quantize(w, scale, bits=bits), scale
    assert K % group_size == 0, (K, group_size)
    wg = w.reshape(K // group_size, group_size, N)
    scale = quant_scale(wg, axis=1, bits=bits)
    q = quantize(wg, scale, bits=bits).reshape(K, N)
    return q, scale


def group_dequantize(
    q: jnp.ndarray, scale: jnp.ndarray, group_size: int
) -> jnp.ndarray:
    K, N = q.shape
    if scale.ndim == 2:                                    # per-channel
        return dequantize(q, scale)
    qg = q.reshape(K // group_size, group_size, N)
    return dequantize(qg, scale).reshape(K, N)


# ---------------------------------------------------------------------------
# Nibble packing along an axis: two signed int4 values per uint8 byte (the
# axis must have even length).  This is the int4 KV cache's format
# (models/attention.py); weights use the planar K-major layout of
# kernels/packing.py.  Either way it is the TPU analogue of the paper's area
# argument: 4-bit packing halves bytes vs int8 and quarters them vs bf16,
# directly scaling the achievable "multiplier array" per unit of HBM
# bandwidth.
# ---------------------------------------------------------------------------

def pack_int4(q: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Pack int8 tensor holding int4 values in [-8,7] into uint8 nibbles."""
    q = jnp.moveaxis(q, axis, -1)
    assert q.shape[-1] % 2 == 0, q.shape
    lo = q[..., 0::2] & 0xF
    hi = q[..., 1::2] & 0xF
    packed = (lo | (hi << 4)).astype(jnp.uint8)
    return jnp.moveaxis(packed, -1, axis)


def unpack_int4(p: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Inverse of pack_int4: uint8 nibbles -> int8 tensor of int4 values."""
    p = jnp.moveaxis(p, axis, -1)
    lo = (p & 0xF).astype(jnp.int8)
    hi = ((p >> 4) & 0xF).astype(jnp.int8)
    # sign-extend 4-bit two's complement: (n ^ 8) - 8
    lo = ((lo ^ 8) - 8).astype(jnp.int8)
    hi = ((hi ^ 8) - 8).astype(jnp.int8)
    out = jnp.stack([lo, hi], axis=-1).reshape(*p.shape[:-1], p.shape[-1] * 2)
    return jnp.moveaxis(out, -1, axis)


def to_unsigned_mag(q: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Split signed int4 values into (|q| in [0,8], sign in {-1,+1}).

    |q| <= 8 fits the unsigned 4-bit domain of the paper's multiplier, so the
    netlist computes |a|*|b| exactly and the sign is applied afterwards.
    """
    sign = jnp.where(q < 0, jnp.int32(-1), jnp.int32(1))
    return jnp.abs(q.astype(jnp.int32)).astype(jnp.uint8), sign

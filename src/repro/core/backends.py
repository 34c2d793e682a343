"""Quantized-linear backend registry.

``qdense`` used to be a monolithic if/elif chain; each backend is now a
registered function so plan resolution (core.quant_plan) can pick a backend
*per call site* and new backends are additions, not edits:

    @register_backend("my_backend")
    def _my_backend(w, x2, cfg, tag):    # w [K, N] float master, x2 [M, K]
        return ...                       # y2 [M, N]

The shared wrapper in ``qdense`` owns the batch flattening, reshape
epilogue, bias add and output-dtype cast that every backend used to
duplicate — a backend only computes the 2-D GEMM.  ``tag`` is the site
string: it keys per-call-site (bm, bn, bk) tile tuning in
``kernels.autotune`` (the same string keys the quant choice in the plan).
"""

from __future__ import annotations

from typing import Callable, Dict

import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.packing import pack_kmajor

from .quant import (
    fake_quant,
    group_dequantize,
    group_quantize,
    quant_scale,
    quantize,
    to_unsigned_mag,
)

BACKENDS: Dict[str, Callable] = {}


def register_backend(name: str):
    """Register ``fn(w, x2, cfg, tag) -> y2`` under ``name``."""
    def deco(fn):
        BACKENDS[name] = fn
        return fn
    return deco


def get_backend(name: str) -> Callable:
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown quant backend {name!r}; registered: "
            f"{sorted(BACKENDS)}") from None


@register_backend("float")
def _float_backend(w, x2, cfg, tag):
    """Plain GEMM in the activation dtype (reference / ablation baseline)."""
    return jnp.dot(x2, w.astype(x2.dtype))


@register_backend("fake_quant")
def _fake_quant_backend(w, x2, cfg, tag):
    """QAT: STE fake-quant on weights (per-out-channel) and activations
    (per-token dynamic); float GEMM.  Training mode."""
    wq = fake_quant(w, axis=0, bits=cfg.w_bits)
    xq = fake_quant(x2, axis=-1, bits=cfg.a_bits)       # stays x dtype
    return jnp.dot(xq, wq.astype(x2.dtype))


def _int4_backend(w, x2, cfg, tag):
    """W4A4 integer GEMM: int8 dot, int32 accum, dequant epilogue.

    ``int_sim`` keeps the pure-XLA path (identical math to
    kernels/int4_matmul.py, usable inside multi-device pjit graphs);
    ``pallas_int4`` runs quantize + int8-MXU matmul + dequant in one
    pallas_call on TPU (XLA twin math elsewhere — see kernels.ops)."""
    xf = x2.astype(jnp.float32)
    w_scale = quant_scale(w, axis=0, bits=cfg.w_bits)    # [1, N]
    w_q = quantize(w, w_scale, bits=cfg.w_bits)
    # the Pallas kernels are int4-specific; other bit widths keep the XLA
    # path so cfg.a_bits/w_bits are honored on every backend
    if cfg.backend == "pallas_int4" and ops.use_pallas() \
            and cfg.a_bits == 4 and cfg.w_bits == 4:
        # quantize + matmul + dequant in one pallas_call; the weight is
        # packed K-major directly from the quantized master
        return ops.int4_matmul_fused(xf, pack_kmajor(w_q), w_scale, tag=tag)
    a_scale = quant_scale(xf, axis=1, bits=cfg.a_bits)   # per-row
    a_q = quantize(xf, a_scale, bits=cfg.a_bits)
    acc = jnp.dot(a_q, w_q, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * a_scale * w_scale


register_backend("int_sim")(_int4_backend)
register_backend("pallas_int4")(_int4_backend)


@register_backend("lut4")
def _lut4_backend(w, x2, cfg, tag):
    """W4A4 through the paper's LUT multiplier, amortized across a GEMM tile
    (kernels/lut4_matmul.py): every partial product is *read* out of the
    16x256 per-nibble tables with a lane-dim take and accumulated in int32
    on the VPU — no MXU dot, weights stay nibble-packed in-kernel.

    The exact product table is rank-1 (T[a, w] = a*w), so the XLA twin is
    the same int8 dot as ``int_sim`` — bit-identical logits/tokens between
    a ``lut4`` plan and an ``int_sim`` plan off-TPU, and between the kernel
    and its twin on-TPU (integer accumulation is exact)."""
    xf = x2.astype(jnp.float32)
    w_scale = quant_scale(w, axis=0, bits=cfg.w_bits)    # [1, N]
    w_q = quantize(w, w_scale, bits=cfg.w_bits)
    a_scale = quant_scale(xf, axis=1, bits=cfg.a_bits)   # per-row
    a_q = quantize(xf, a_scale, bits=cfg.a_bits)
    # the table kernel is int4-specific; other bit widths keep the XLA path
    if ops.use_pallas() and cfg.a_bits == 4 and cfg.w_bits == 4:
        return ops.lut4_matmul(a_q, a_scale, pack_kmajor(w_q), w_scale,
                               tag=tag)
    acc = jnp.dot(a_q, w_q, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * a_scale * w_scale


@register_backend("w4a16")
def _w4a16_backend(w, x2, cfg, tag):
    """Weight-only serving: activation-dtype MXU contraction with scales in
    the epilogue (kernels.ops.w4a16_matmul on TPU, XLA twin elsewhere)."""
    g = cfg.group_size if cfg.group_size else w.shape[0]
    w_q, w_scale = group_quantize(w, g, bits=cfg.w_bits)
    if ops.use_pallas() and cfg.w_bits == 4:
        rm = 2 * g if w_scale.ndim == 3 else 2
        return ops.w4a16_matmul(x2, pack_kmajor(w_q, rm), w_scale, g,
                                tag=tag)
    wf = group_dequantize(w_q, w_scale, g)
    return jnp.dot(x2.astype(jnp.float32), wf,
                   preferred_element_type=jnp.float32)


@register_backend("netlist")
def _netlist_backend(w, x2, cfg, tag):
    """End-to-end oracle: every 4-bit product through the simulated FPGA
    circuit (the paper's netlist).  O(bits) slower; tests / tiny shapes."""
    from .mult4_proposed import build_proposed_mult4

    nl = build_proposed_mult4()
    xf = x2.astype(jnp.float32)
    a_scale = quant_scale(xf, axis=1, bits=cfg.a_bits)
    a_q = quantize(xf, a_scale, bits=cfg.a_bits)             # [M, K]
    w_scale = quant_scale(w, axis=0, bits=cfg.w_bits)
    w_q = quantize(w, w_scale, bits=cfg.w_bits)              # [K, N]
    mag_a, sign_a = to_unsigned_mag(a_q)
    mag_w, sign_w = to_unsigned_mag(w_q)
    # products [M, K, N] through the netlist (vectorized over all pairs)
    prod = nl(mag_a[:, :, None], mag_w[None, :, :]).astype(jnp.int32)
    prod = prod * sign_a[:, :, None] * sign_w[None, :, :]
    acc = jnp.sum(prod, axis=1).astype(jnp.float32)
    return acc * a_scale * w_scale

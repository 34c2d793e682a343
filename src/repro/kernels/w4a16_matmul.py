"""Pallas TPU kernel: weight-only int4 serving matmul (W4A16).

bf16 activations x planar-K-major-packed int4 weights.  This is the
AWQ/GPTQ-shaped deployment mode of the paper's technique: weight bytes drop
4x (the "more multipliers per unit area" argument) while activation precision
is preserved.

The seed kernel dequantized the weight tile to f32 (scale multiply on every
[bk, bn] element) and contracted in f32 — off the fast MXU path.  This
version contracts in the *activation* dtype: int4 values in [-8, 7] are
exactly representable in bf16, so casting the unpacked nibbles to bf16 and
contracting on the bf16 MXU (f32 accumulation) loses nothing, and the scale
multiply moves off the weight tile into the epilogue:

  * per-channel scales [1, N]: one multiply per *output* element, applied
    once at k == nk-1 (a true epilogue — bk x fewer multiplies than
    scaling the weight tile every k-step);
  * per-group scales [K/G, 1, N]: each planar half of a k-step covers whole
    groups (bk % 2G == 0), contracted one group at a time and scaled on the
    [bm, bn] partial product — still O(bm*bn) per group instead of
    O(G*bn) on the weights.

Weights use the planar K-major nibble layout (kernels/packing.py): unpack is
shift/mask only, no relayout; the activation tile is split at K/2 to match.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import default_interpret
from .packing import pad_to, unpack_nibbles


def _pad_rows(s: jnp.ndarray, rows: int) -> jnp.ndarray:
    """Pad a [g, 1, N] scale slab with zero rows up to exactly `rows`
    (padded K rows hold zero int4 values, so their scale is irrelevant)."""
    return jnp.pad(s, [(0, rows - s.shape[0])] + [(0, 0)] * (s.ndim - 1))


def _dot(x, w):
    return jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _kernel_per_channel(xlo_ref, xhi_ref, w_ref, ws_ref, o_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # planar [bk/2, bn] nibbles, already in the activation dtype
    lo, hi = unpack_nibbles(w_ref[...], xlo_ref.dtype)
    o_ref[...] += _dot(xlo_ref[...], lo) + _dot(xhi_ref[...], hi)

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = o_ref[...] * ws_ref[...]    # [1, bn] per-channel scale


def _kernel_grouped(xlo_ref, xhi_ref, w_ref, slo_ref, shi_ref, o_ref, *,
                    nk: int, gpbh: int, gsize: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x_lo, x_hi = xlo_ref[...], xhi_ref[...]
    lo, hi = unpack_nibbles(w_ref[...], x_lo.dtype)   # planar [bk/2, bn]
    acc = jnp.zeros_like(o_ref)
    for g in range(gpbh):                        # static unroll: whole groups
        rows = slice(g * gsize, (g + 1) * gsize)
        acc += _dot(x_lo[:, rows], lo[rows]) * slo_ref[g]
        acc += _dot(x_hi[:, rows], hi[rows]) * shi_ref[g]
    o_ref[...] += acc


@functools.partial(
    jax.jit, static_argnames=("group_size", "bm", "bn", "bk", "interpret")
)
def w4a16_matmul(
    x: jnp.ndarray,            # [M, K] bf16/f32
    w_kmajor: jnp.ndarray,     # [ceil(K/2), N] uint8, planar K-major
    w_scale: jnp.ndarray,      # [K//G, 1, N] f32 (or [1, N] per-channel)
    group_size: int,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = None,
) -> jnp.ndarray:
    M, K = x.shape
    N = w_kmajor.shape[1]
    Keven = w_kmajor.shape[0] * 2
    per_channel = w_scale.ndim == 2
    # packing may have padded K (odd K, or grouped row_mult alignment)
    assert K <= Keven <= K + (1 if per_channel else 2 * group_size), \
        (x.shape, w_kmajor.shape, group_size)
    # compute dtype: bf16 stays bf16 (MXU path, int4 exact); f32 stays f32
    cd = x.dtype if x.dtype == jnp.bfloat16 else jnp.float32
    x = pad_to(x.astype(cd), Keven, 1)
    K2 = Keven // 2

    if per_channel:
        assert bk % 2 == 0, bk
        bkh = bk // 2
    else:
        G = group_size
        assert Keven % (2 * G) == 0, (K, G)      # groups align to the halves
        bkh = bk // 2
        if bkh % G:                              # self-heal invalid tile
            bkh = max(G, -(-bkh // G) * G)
        gpbh = bkh // G

    x_lo = pad_to(pad_to(x[:, :K2], bm, 0), bkh, 1)
    x_hi = pad_to(pad_to(x[:, K2:], bm, 0), bkh, 1)
    w_kmajor = pad_to(pad_to(w_kmajor, bkh, 0), bn, 1)
    Mp = x_lo.shape[0]
    Np = w_kmajor.shape[1]
    nk = x_lo.shape[1] // bkh
    interpret = default_interpret(interpret)
    x_specs = [
        pl.BlockSpec((bm, bkh), lambda i, j, k: (i, k)),
        pl.BlockSpec((bm, bkh), lambda i, j, k: (i, k)),
        pl.BlockSpec((bkh, bn), lambda i, j, k: (k, j)),
    ]

    if per_channel:
        out = pl.pallas_call(
            functools.partial(_kernel_per_channel, nk=nk),
            grid=(Mp // bm, Np // bn, nk),
            in_specs=x_specs + [pl.BlockSpec((1, bn), lambda i, j, k: (0, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
            interpret=interpret,
            name="w4a16_matmul",
        )(x_lo, x_hi, w_kmajor, pad_to(w_scale, bn, 1))
    else:
        ng2 = (Keven // G) // 2                  # groups per planar half
        rows = x_lo.shape[1] // G                # scale rows the grid reads
        s_lo = pad_to(_pad_rows(w_scale[:ng2], rows), bn, 2)
        s_hi = pad_to(_pad_rows(w_scale[ng2:], rows), bn, 2)
        out = pl.pallas_call(
            functools.partial(_kernel_grouped, nk=nk, gpbh=gpbh, gsize=G),
            grid=(Mp // bm, Np // bn, nk),
            in_specs=x_specs + [
                pl.BlockSpec((gpbh, 1, bn), lambda i, j, k: (k, 0, j)),
                pl.BlockSpec((gpbh, 1, bn), lambda i, j, k: (k, 0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
            interpret=interpret,
            name="w4a16_matmul",
        )(x_lo, x_hi, w_kmajor, s_lo, s_hi)
    return out[:M, :N]

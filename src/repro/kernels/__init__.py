"""Pallas TPU kernels for the paper's compute hot-spot: low-bit multiplication.

lut_mul4      -- the paper's LUT mechanism re-homed to VMEM (onehot/take)
int4_matmul   -- W4A4 planar-nibble MXU matmul (+ fused activation-quantize
                 variant) with fused dequant epilogue
w4a16_matmul  -- weight-only int4 serving matmul, activation-dtype MXU
                 contraction with scales folded into the epilogue
paged_attention -- fused paged-KV decode attention (reads pool pages in
                 place via scalar-prefetched block tables, online softmax)
                 + tiled flash prefill, with bit-exact XLA twins
packing       -- the one int4 weight layout (planar K-major) and the
                 shared nibble pack/unpack helpers
autotune      -- per-shape (bm, bn, bk) tile search with an on-disk cache
ops           -- public wrappers: block lookup, dispatch
                 (Pallas on TPU, interpreter for tests, XLA twin elsewhere)
ref           -- pure-jnp oracles
"""
from . import autotune, ops, packing, ref  # noqa: F401

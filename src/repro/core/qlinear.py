"""QuantizedLinear: the paper's technique as a first-class framework feature.

Every projection in every architecture config routes through `qdense`.  The
backend is selected by `QuantConfig.backend` out of the registry in
`core.backends` (one function per backend, registered by name — new backends
are additions, not edits):

  float       -- plain bf16/f32 GEMM (reference / ablation baseline)
  fake_quant  -- QAT: STE fake-quant on weights (per-out-channel) and
                 activations (per-tensor dynamic); float GEMM. Training mode.
  int_sim     -- W4A4 integer GEMM in XLA (int8 dot, int32 accum, dequant
                 epilogue): identical math to kernels/int4_matmul.py, usable
                 inside multi-device pjit graphs (dry-run / CPU).
  pallas_int4 -- kernels.ops.int4_matmul_fused: quantize + int8-MXU matmul +
                 dequant in one pallas_call (real TPU path; XLA twin math
                 on CPU/GPU — see kernels.ops dispatch).
  w4a16       -- weight-only serving: kernels.ops.w4a16_matmul (activation-
                 dtype MXU contraction, scales in the epilogue; XLA twin
                 elsewhere).  Tile shapes come from kernels.autotune.
  lut4        -- W4A4 through the paper's LUT multiplier amortized across a
                 GEMM tile: kernels.ops.lut4_matmul (per-nibble product
                 tables + lane-dim take, MXU-free int32 accumulation; XLA
                 twin is the same int8 dot as int_sim — bit-identical).
  netlist     -- bit-exact FPGA-netlist simulation of every 4-bit product
                 (the paper's circuit, used as the end-to-end oracle; O(bits)
                 slower, tests / tiny shapes only).

Which backend runs at which call site is decided by the active QuantPlan
(`core.quant_plan`): the `tag`/site string each model layer passes names the
call site, and `Runtime.quant_cfg(arch, site)` resolves it to a per-site
QuantConfig before calling qdense.

Weights are stored as float master copies (training) — serving-time packing
is done once by `pack_weight_nd` (through `quant_plan.plan_pack_tree`), in
the kernels' planar K-major layout (`kernels.packing`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.packing import pack_kmajor, unpack_kmajor
from .quant import quant_scale, quantize


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    backend: str = "fake_quant"     # float | fake_quant | int_sim | pallas_int4 | lut4 | w4a16 | netlist
    w_bits: int = 4
    a_bits: int = 4
    group_size: int = 0             # 0 => per-output-channel scales
    quantize_embedding: bool = False

    @property
    def quantized(self) -> bool:
        return self.backend != "float"


FLOAT = QuantConfig(backend="float")
QAT_W4A4 = QuantConfig(backend="fake_quant")
INT_SIM_W4A4 = QuantConfig(backend="int_sim")


def _flatten_batch(x: jnp.ndarray):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def qdense(
    w,                              # [K, N] float master weight OR packed dict
    x: jnp.ndarray,                 # [..., K]
    cfg: QuantConfig,
    bias: Optional[jnp.ndarray] = None,
    tag: str = "",
) -> jnp.ndarray:
    """Quantized dense layer. Output dtype follows x.

    `w` may be a pre-packed serving weight (`{"packed": uint8 [K'/2, N],
    "scale": [1, N]}`, from `quant_plan.plan_pack_tree`): weight bytes drop
    4x vs bf16 — the paper's area argument at system level.  Packed backends:
    `w4a16_packed` (dequant + bf16 GEMM) and `w4a4_packed` (dynamic per-token
    int4 activations + int8 GEMM + int32 accum, the full technique).

    `tag` names the call site (e.g. "block[3].ffn.w_in"): the same string
    keys the per-site backend choice in the active QuantPlan *and*
    per-deployment-shape tile tuning in `kernels.autotune`, so the same GEMM
    shape can carry different tuned blocks at different sites.  Kernel-backed
    GEMMs run through the Pallas kernels on TPU and their XLA twins elsewhere
    (`ops` dispatch) — identical math either way.

    The shared wrapper here owns batch flattening, the reshape epilogue,
    bias add and output-dtype cast; the per-backend GEMMs live in
    `core.backends` (registry — see `register_backend`).
    """
    from .backends import get_backend

    if isinstance(w, dict) and "packed" in w:
        fn = _packed_backend
    else:
        if cfg.backend in ("w4a4_packed", "w4a16_packed"):
            # weight not packed (too small / excluded by the plan packer):
            # equivalent on-the-fly path
            cfg = dataclasses.replace(
                cfg,
                backend="int_sim" if cfg.backend == "w4a4_packed" else "w4a16")
        fn = get_backend(cfg.backend)
    out_dtype = x.dtype
    x2, lead = _flatten_batch(x)
    y = fn(w, x2, cfg, tag)
    y = y.reshape(*lead, y.shape[-1])
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y.astype(out_dtype)


def _packed_backend(w, x2, cfg: QuantConfig, tag: str = ""):
    """Serving path: `w` from plan_pack_tree / pack_weight_nd.

    On Pallas backends the GEMM runs through the kernels (W4A4: fused
    activation-quantize; W4A16: per-channel epilogue kernel), which read
    the planar K-major weight as packed.  Elsewhere: XLA twins."""
    packed, w_scale = w["packed"], w["scale"]
    K = x2.shape[1]
    # packed weights are int4 by construction; int_sim keeps its documented
    # pure-XLA/pjit contract even on Pallas backends, and non-int4
    # activation configs keep the XLA path (a_bits honored)
    kernel_ok = ops.use_pallas() and packed.ndim == 2
    if cfg.backend in ("w4a4_packed", "int_sim", "pallas_int4", "lut4"):
        xf = x2.astype(jnp.float32)
        if kernel_ok and cfg.backend == "lut4" and cfg.a_bits == 4:
            # table-lookup kernel: weights stay packed in-kernel (the tables
            # index the planar byte directly), activations quantized here
            a_scale = quant_scale(xf, axis=1, bits=4)
            a_q = quantize(xf, a_scale, bits=4)
            return ops.lut4_matmul(a_q, a_scale, packed, w_scale, tag=tag)
        if kernel_ok and cfg.backend not in ("int_sim", "lut4") \
                and cfg.a_bits == 4:
            return ops.int4_matmul_fused(xf, packed, w_scale, tag=tag)
        a_scale = quant_scale(xf, axis=1, bits=cfg.a_bits)
        a_q = quantize(xf, a_scale, bits=cfg.a_bits)
        w_q = unpack_kmajor(packed)[..., :K, :]
        acc = jnp.dot(a_q, w_q, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * a_scale * w_scale
    if cfg.backend not in ("w4a16", "w4a16_packed"):
        # a packed weight reaching a backend with no packed path used to
        # fall through to the w4a16 dequant branch silently — wrong math
        # for anything that isn't weight-only.  Loud beats lenient: the
        # plan/manifest checks in checkpoint.restore_quantized keep a legal
        # configuration from ever landing here.
        raise ValueError(
            f"packed weight at site {tag!r} reached backend "
            f"{cfg.backend!r}, which has no packed-weight path; restore "
            f"with the plan the checkpoint was packed under (the manifest "
            f"records per-site backends) or rebuild from float masters")
    # w4a16 / w4a16_packed: pack_weight_nd scales are per-output-channel
    # [1, N] or per-group [K//G, 1, N] — the group size is recovered from
    # the scale shape
    g = K // w_scale.shape[0] if w_scale.ndim == 3 else K
    if kernel_ok:                       # via the epilogue kernel
        return ops.w4a16_matmul(x2, packed, w_scale, g, tag=tag)
    # dequant + activation-dtype GEMM
    wf = unpack_kmajor(packed)[..., :K, :].astype(jnp.float32)
    if w_scale.ndim == 3:
        N = wf.shape[-1]
        wf = (wf.reshape(K // g, g, N) * w_scale).reshape(K, N)
    else:
        wf = wf * w_scale
    return jnp.dot(x2, wf.astype(x2.dtype))


#: linear-weight leaf names eligible for serving-side packing (allowlist).
PACKABLE_NAMES = frozenset({
    "wq", "wk", "wv", "wo",                  # attention projections
    "w_in", "w_gate", "w_out",               # FFN / MoE experts
    "in_proj", "out_proj",                   # mamba
    "in_x", "in_g", "w_a", "w_x", "out",     # rg-lru
})


def pack_weight_nd(w: jnp.ndarray, cfg: QuantConfig):
    """Pack a [..., K, N] float weight into the kernels' planar K-major
    layout: ``{"packed": uint8 [..., K'/2, N], "scale": ...}``.  Works for
    plain [K,N], layer-stacked [L,K,N] and stacked experts [L,E,K,N].

    Scales follow `cfg.group_size`: 0 (or >= K) gives per-output-channel
    scales [..., 1, N] and K' = K rounded up to even; a divisor G of K
    gives per-group scales [..., K//G, 1, N] and K' = K rounded up to a
    multiple of 2G, so each planar half covers whole groups (the padding
    rows are zero int4 values).  The grouping is the one the on-the-fly
    w4a16 backend computes, so grouped plans keep their numerics through a
    quantized checkpoint."""
    K, N = w.shape[-2], w.shape[-1]
    g = cfg.group_size
    row_mult = 2
    if g and 0 < g < K:
        # same contract as the on-the-fly group_quantize: a group size that
        # doesn't divide K is a plan error, not a silent per-channel
        # fallback (the checkpoint must carry the numerics the plan names)
        assert K % g == 0, (K, g)
        wg = w.reshape(*w.shape[:-2], K // g, g, N)
        scale = quant_scale(wg, axis=-2, bits=cfg.w_bits)  # [..., K//g, 1, N]
        q = quantize(wg, scale, bits=cfg.w_bits).reshape(w.shape)
        row_mult = 2 * g
    else:
        scale = quant_scale(w, axis=-2, bits=cfg.w_bits)   # [..., 1, N]
        q = quantize(w, scale, bits=cfg.w_bits)
    return {"packed": pack_kmajor(q, row_mult), "scale": scale}

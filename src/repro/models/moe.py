"""Mixture-of-Experts block with expert parallelism.

Dispatch is sort-based (argsort by expert id -> capacity-bounded per-expert
buffers -> batched expert GEMMs -> scatter-add combine).  Under a mesh
context the block runs inside `shard_map`: tokens stay sharded on the data
axis (replicated across `model`), experts are sharded on the `model` axis
(E/tp experts per device), each device computes only its experts'
contributions, and a single `psum` over `model` combines them — the same
per-layer collective volume as a Megatron FFN, with no all-to-all needed
because activations are TP-replicated between blocks.

Every expert projection uses the paper's int4 technique via fake-quant
(expert weights quantize per-output-channel exactly like dense FFNs).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, Runtime
from repro.core.quant import fake_quant
from repro.core.quant_plan import join_site
from repro.distributed.sharding import current_mesh, dp_axes, shard_map
from .common import normal_init


def init_moe(key, cfg: ArchConfig) -> Dict:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.d_ff_expert or cfg.d_ff
    ks = jax.random.split(key, 4)
    p = {
        "router": {"w": normal_init(ks[0], (D, E))},
        "experts": {
            "w_in": normal_init(ks[1], (E, D, F)),
            "w_out": normal_init(ks[2], (E, F, D), fan_in=F),
        },
    }
    if cfg.ffn_type == "swiglu":
        p["experts"]["w_gate"] = normal_init(ks[3], (E, D, F))
    return p


def _capacity(n_tokens: int, cfg: ArchConfig) -> int:
    c = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)


def _expert_ffn(buf, experts, cfg: ArchConfig, rt: Runtime, site: str = "moe"):
    """buf [El, C, D] -> [El, C, D] through the (quantized) expert MLPs.
    All expert weights share one plan site (`<site>.experts`): they run as a
    batched einsum, so per-expert backends are not addressable."""
    qc = rt.quant_cfg(cfg, join_site(site, "experts"))

    def dense(w, k: int):
        if isinstance(w, dict):                # packed int4 serving weights
            from repro.kernels.packing import unpack_kmajor

            q = unpack_kmajor(w["packed"])[..., :k, :]     # [El, k, N]
            return (q.astype(jnp.float32) * w["scale"]).astype(buf.dtype)
        if qc.backend == "fake_quant":
            # per-output-channel fake-quant along each expert's reduction dim
            w = fake_quant(w, axis=1, bits=qc.w_bits)
        return w.astype(buf.dtype)

    D = buf.shape[-1]
    h = jnp.einsum("ecd,edf->ecf", buf, dense(experts["w_in"], D))
    if "w_gate" in experts:
        g = jnp.einsum("ecd,edf->ecf", buf, dense(experts["w_gate"], D))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, dense(experts["w_out"], h.shape[-1]))


def _moe_shard(xf, router_w, experts, *, e_start, n_local, cfg, rt, axis=None,
               site="moe"):
    """Core dispatch/compute/combine for `n_local` experts starting at
    `e_start`. xf [T, D]. Returns (partial y [T, D], per-token aux [T])."""
    T, D = xf.shape
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    gate, idx = jax.lax.top_k(probs, k)                        # [T, k]
    if k > 1:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-9)

    flat_e = idx.reshape(-1)                                   # [T*k]
    flat_g = gate.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)                    # [E]
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * k) - starts[sorted_e]
    tok = order // k

    local = (sorted_e >= e_start) & (sorted_e < e_start + n_local) & (rank < C)
    slot_e = jnp.clip(sorted_e - e_start, 0, n_local - 1)
    slot_c = jnp.clip(rank, 0, C - 1)
    w = jnp.where(local, 1.0, 0.0).astype(xf.dtype)

    buf = jnp.zeros((n_local, C, D), xf.dtype)
    buf = buf.at[slot_e, slot_c].add(w[:, None] * xf[tok])

    out_buf = _expert_ffn(buf, experts, cfg, rt, site=site)    # [El, C, D]

    gathered = out_buf[slot_e, slot_c]                         # [T*k, D]
    contrib = gathered * (jnp.where(local, flat_g[order], 0.0)).astype(xf.dtype)[:, None]
    y = jnp.zeros((T, D), xf.dtype).at[tok].add(contrib)

    # Switch-style load-balance aux: E * sum_e( frac_tokens_e * mean_prob_e )
    frac = counts.astype(jnp.float32) / (T * k)
    mean_p = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(frac * mean_p)
    if axis is not None:
        y = jax.lax.psum(y, axis)
    return y, jnp.full((T,), aux, jnp.float32)


def apply_moe(
    params: Dict, x: jnp.ndarray, cfg: ArchConfig, rt: Runtime,
    site: str = "moe",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B, S, D] -> (y [B, S, D], aux scalar)."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    mesh = current_mesh()
    dpa = dp_axes()
    dp_size = 1
    if mesh is not None:
        for a in dpa:
            dp_size *= mesh.shape[a]
    use_shard_map = (
        mesh is not None
        and cfg.n_experts % mesh.shape["model"] == 0
        and (B * S) % dp_size == 0
        and B % dp_size == 0          # xf keeps dim-0 sharding after reshape
    )
    if use_shard_map:
        tp = mesh.shape["model"]
        dp = mesh.shape["data"]
        n_local = cfg.n_experts // tp
        dspec = dpa if len(dpa) > 1 else dpa[0]

        # Per-leaf spec + FSDP-gather axis.  Expert weights are E-sharded on
        # `model` and (when divisible) sharded on `data` along the gatherable
        # axis: F for w_in/w_gate ([E,D,F]; packed [E,D/2,F]) and their
        # scales [E,1,F]; axis 1 for w_out ([E,F,D]; packed [E,F/2,D], whose
        # packed rows are gathered whole before the unpack).  The tiny
        # w_out.scale [E,1,D] stays replicated.
        def leaf_plan(name, leaf):
            ax = 1 if name == "w_out" else 2
            if leaf.ndim == 3 and leaf.shape[ax] % dp == 0 and leaf.shape[ax] > 1:
                spec = [None, None, None]
                spec[0] = "model"
                spec[ax] = "data"
                return P(*spec), ax
            return P("model", None, None), None

        especs, gather_ax = {}, {}
        for k, v in params["experts"].items():
            if isinstance(v, dict):
                especs[k], gather_ax[k] = {}, {}
                for kk, leaf in v.items():
                    especs[k][kk], gather_ax[k][kk] = leaf_plan(k, leaf)
            else:
                especs[k], gather_ax[k] = leaf_plan(k, v)

        def body(xf_l, rw, experts_l):
            # FSDP-style gather of data-sharded expert weights; the backward
            # of all_gather is the matching reduce-scatter.  Float master
            # weights are cast to bf16 *before* the gather (mixed-precision
            # FSDP: halves gather + grad reduce-scatter bytes; the f32
            # master/moments stay sharded at rest).
            def gather(w, ax):
                if isinstance(w, dict):
                    return {kk: gather(ww, ax[kk]) for kk, ww in w.items()}
                if (rt.compute_dtype == "bfloat16" and w.dtype == jnp.float32
                        and w.ndim == 3 and w.shape[-2] > 1):
                    w = w.astype(jnp.bfloat16)   # not quant scales [E,1,*]
                if ax is None:
                    return w
                return jax.lax.all_gather(w, "data", axis=ax, tiled=True)

            experts_l = {k: gather(w, gather_ax[k])
                         for k, w in experts_l.items()}
            e_start = jax.lax.axis_index("model") * n_local
            return _moe_shard(
                xf_l, rw, experts_l,
                e_start=e_start, n_local=n_local, cfg=cfg, rt=rt, axis="model",
                site=site,
            )

        y, aux_t = shard_map(
            body,
            mesh=mesh,
            in_specs=(P(dspec, None), P(None, None), especs),
            out_specs=(P(dspec, None), P(dspec)),
            check=False,
        )(xf, params["router"]["w"], params["experts"])
    else:
        y, aux_t = _moe_shard(
            xf, params["router"]["w"], params["experts"],
            e_start=0, n_local=cfg.n_experts, cfg=cfg, rt=rt, site=site,
        )
    return y.reshape(B, S, D), jnp.mean(aux_t)

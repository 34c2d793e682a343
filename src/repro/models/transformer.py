"""Decoder LM assembly: embedding -> (scan over pattern-repeat groups of
blocks) -> tail blocks -> norm -> logits, with unified KV/state caches and
chunked cross-entropy.

Layer stacking: `cfg.pattern` defines one repeat unit (e.g. ("A",) uniform,
("M",) mamba, ("R","R","A") recurrentgemma); params/caches for the
`cfg.n_repeats` units are stacked on a leading axis and iterated with
`lax.scan` (production/memory variant) or a Python loop (`scan_layers=False`
cost-probe variant — exact HLO FLOP accounting, see EXPERIMENTS.md).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, Runtime
from repro.core.qlinear import qdense
from repro.core.quant_plan import (
    active_plan,
    join_site,
    layers_per_repeat,
    plan_repeat_uniform,
)
from repro.distributed.sharding import shard
from .attention import (
    POOL_LEAVES,
    apply_attention,
    init_attention,
    init_attn_cache,
)
from .common import normal_init, rms_norm, sinusoidal_pos_embed
from .ffn import apply_ffn, init_ffn
from .moe import apply_moe, init_moe
from .rglru import apply_rglru, init_rglru, init_rglru_cache
from .ssm import apply_mamba, init_mamba, init_mamba_cache


# ----------------------------------------------------------------- blocks --
def init_block(key, block_type: str, cfg: ArchConfig) -> Dict:
    ks = jax.random.split(key, 4)
    D = cfg.d_model
    p: Dict = {"norm1": jnp.ones((D,))}
    if block_type == "A":
        p["attn"] = init_attention(ks[0], cfg)
        if cfg.family == "moe":
            p["norm2"] = jnp.ones((D,))
            p["moe"] = init_moe(ks[1], cfg)
            if cfg.shared_expert:
                p["shared"] = init_ffn(ks[2], cfg, cfg.d_ff_expert or cfg.d_ff)
            if cfg.moe_dense_ff:
                p["dense_ffn"] = init_ffn(ks[3], cfg, cfg.moe_dense_ff)
        elif cfg.d_ff:
            p["norm2"] = jnp.ones((D,))
            p["ffn"] = init_ffn(ks[1], cfg)
    elif block_type == "M":
        p["mamba"] = init_mamba(ks[0], cfg)
    elif block_type == "R":
        p["lru"] = init_rglru(ks[0], cfg)
        if cfg.d_ff:
            p["norm2"] = jnp.ones((D,))
            p["ffn"] = init_ffn(ks[1], cfg)
    else:
        raise ValueError(block_type)
    return p


def init_block_cache(block_type: str, cfg: ArchConfig, rt: Runtime,
                     batch: int, seq: int):
    if block_type == "A":
        return {"attn": init_attn_cache(cfg, rt, batch, seq)}
    if block_type == "M":
        return {"mamba": init_mamba_cache(cfg, batch)}
    if block_type == "R":
        return {"lru": init_rglru_cache(cfg, batch)}
    raise ValueError(block_type)


def apply_block(
    block_type: str, p: Dict, x, cfg, rt, positions,
    cache=None, update_cache=False, site: str = "",
):
    """Returns (x, new_cache, aux).  `site` is the block's site prefix
    (e.g. "block[3]"): sub-layers resolve their quant backend and autotune
    tiles under it (see core.quant_plan)."""
    aux = jnp.zeros((), jnp.float32)
    normed = rms_norm(x, p["norm1"], cfg.norm_eps)
    if block_type == "A":
        h, nc = apply_attention(
            p["attn"], normed, cfg, rt, positions,
            cache.get("attn") if cache else None, update_cache, site=site,
        )
        x = x + h
        if cfg.family == "moe":
            n2 = rms_norm(x, p["norm2"], cfg.norm_eps)
            my, aux = apply_moe(p["moe"], n2, cfg, rt,
                                site=join_site(site, "moe"))
            extra = 0.0
            if cfg.shared_expert:
                extra = apply_ffn(p["shared"], n2, cfg, rt,
                                  site=join_site(site, "shared"))
            if cfg.moe_dense_ff:
                extra = apply_ffn(p["dense_ffn"], n2, cfg, rt,
                                  site=join_site(site, "dense_ffn"))
            x = x + my + extra
        elif cfg.d_ff:
            x = x + apply_ffn(p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps),
                              cfg, rt, site=join_site(site, "ffn"))
        return x, ({"attn": nc} if nc is not None else None), aux
    if block_type == "M":
        h, nc = apply_mamba(p["mamba"], normed, cfg, rt,
                            cache.get("mamba") if cache else None, update_cache,
                            site=join_site(site, "mamba"))
        return x + h, ({"mamba": nc} if nc is not None else None), aux
    if block_type == "R":
        h, nc = apply_rglru(p["lru"], normed, cfg, rt,
                            cache.get("lru") if cache else None, update_cache,
                            site=join_site(site, "lru"))
        x = x + h
        if cfg.d_ff:
            x = x + apply_ffn(p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps),
                              cfg, rt, site=join_site(site, "ffn"))
        return x, ({"lru": nc} if nc is not None else None), aux
    raise ValueError(block_type)


# ------------------------------------------------------------------ model --
def init_model(key, cfg: ArchConfig) -> Dict:
    ks = jax.random.split(key, 4 + len(cfg.tail))
    Vp, D = cfg.vocab_padded, cfg.d_model
    params: Dict = {
        "embed": {"tok": normal_init(ks[0], (Vp, D), fan_in=D)},
        "final_norm": jnp.ones((D,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": normal_init(ks[1], (D, Vp))}

    def init_unit(k):
        uks = jax.random.split(k, len(cfg.pattern))
        return {f"u{j}": init_block(uks[j], bt, cfg)
                for j, bt in enumerate(cfg.pattern)}

    unit_keys = jax.random.split(ks[2], cfg.n_repeats)
    params["layers"] = jax.vmap(init_unit)(unit_keys)   # stacked on axis 0
    for t, bt in enumerate(cfg.tail):
        params[f"tail{t}"] = init_block(ks[3 + t], bt, cfg)
    return params


def init_caches(cfg: ArchConfig, rt: Runtime, batch: int, seq: int):
    def unit_cache(_):
        return {f"u{j}": init_block_cache(bt, cfg, rt, batch, seq)
                for j, bt in enumerate(cfg.pattern)}

    stacked = jax.vmap(unit_cache)(jnp.arange(cfg.n_repeats))
    tail = {f"tail{t}": init_block_cache(bt, cfg, rt, batch, seq)
            for t, bt in enumerate(cfg.tail)}
    return {"rep": stacked, "tail": tail}


def forward(
    params: Dict,
    tokens: jnp.ndarray,              # [B, S] int32
    cfg: ArchConfig,
    rt: Runtime,
    positions: Optional[jnp.ndarray] = None,   # [B,S] or [3,B,S]
    caches: Optional[Dict] = None,
    update_cache: bool = False,
    return_hidden: bool = False,
):
    """Returns (logits_or_hidden, new_caches, aux_mean)."""
    B, S = tokens.shape
    tokens = shard(tokens, "tokens")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    dt = jnp.bfloat16 if rt.compute_dtype == "bfloat16" else jnp.float32

    x = params["embed"]["tok"][tokens].astype(dt)
    if cfg.rope == "none":
        tpos = positions if positions.ndim == 2 else positions[0]
        x = x + sinusoidal_pos_embed(tpos, cfg.d_model).astype(dt)
    x = shard(x, "act_btd")

    P = len(cfg.pattern)

    def make_body(unit_sites):
        def unit_body(carry, xs):
            xc, aux_acc = carry
            unit_params, unit_cache = xs
            new_unit_cache = {} if unit_cache is not None else None
            for j, bt in enumerate(cfg.pattern):
                blk_cache = (unit_cache[f"u{j}"]
                             if unit_cache is not None else None)
                xc, nc, aux = apply_block(
                    bt, unit_params[f"u{j}"], xc, cfg, rt, positions,
                    blk_cache, update_cache, site=unit_sites[j],
                )
                if new_unit_cache is not None:
                    new_unit_cache[f"u{j}"] = nc if nc is not None else blk_cache
            return (xc, aux_acc + aux), new_unit_cache

        if rt.remat == "dots":
            return jax.checkpoint(
                unit_body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        if rt.remat == "full":
            return jax.checkpoint(unit_body)
        return unit_body

    aux0 = jnp.zeros((), jnp.float32)
    rep_caches = caches["rep"] if caches is not None else None
    # per-site plan resolution happens OUTSIDE the scan body (at trace
    # time), so the compiled graph stays static: lax.scan traces one body
    # for all repeat units and therefore requires every unit to resolve to
    # the same per-site configs.  A plan that distinguishes repeats (e.g.
    # "block[0].*=float") — or a plan-packed tree split per repeat — takes
    # the unrolled layer loop instead.
    per_repeat = layers_per_repeat(params)
    use_scan = (rt.scan_layers and not per_repeat
                and plan_repeat_uniform(active_plan(cfg, rt), cfg))
    # The stacked caches ride through the loop whole (`_take_unit`/
    # `_put_unit`), never scanned as xs/ys: an output stack beside the input
    # stack is a second copy of the KV pool for the whole step, however the
    # step donates its caches.  A paged pool is never even sliced: each
    # layer reads and writes it in place at its layer index.
    if use_scan:
        body = make_body([f"block[{j}]" for j in range(P)])
        if rep_caches is None:
            (x, aux_sum), new_rep = jax.lax.scan(
                lambda c, p: body(c, (p, None)), (x, aux0), params["layers"]
            )
        else:
            def cached_body(carry, xs):
                xc, aux_acc, rep = carry
                unit_p, r = xs
                (xc, aux_acc), nc = body((xc, aux_acc),
                                         (unit_p, _take_unit(rep, r)))
                return (xc, aux_acc, _put_unit(rep, nc, r)), None

            (x, aux_sum, new_rep), _ = jax.lax.scan(
                cached_body, (x, aux0, rep_caches),
                (params["layers"], jnp.arange(cfg.n_repeats)),
            )
    else:
        new_rep = rep_caches
        carry = (x, aux0)
        for r in range(cfg.n_repeats):
            unit_p = (params["layers"][f"r{r}"] if per_repeat
                      else jax.tree.map(lambda a: a[r], params["layers"]))
            unit_c = (_take_unit(new_rep, jnp.int32(r))
                      if rep_caches is not None else None)
            body = make_body([f"block[{r * P + j}]" for j in range(P)])
            carry, nc = body(carry, (unit_p, unit_c))
            if rep_caches is not None:
                new_rep = _put_unit(new_rep, nc, r)
        x, aux_sum = carry

    new_caches = None
    if caches is not None:
        new_caches = {"rep": new_rep, "tail": {}}
    for t, bt in enumerate(cfg.tail):
        tc = (_bind_layer(caches["tail"][f"tail{t}"], jnp.int32(0),
                          lambda a: a)
              if caches is not None else None)
        x, nc, aux = apply_block(bt, params[f"tail{t}"], x, cfg, rt,
                                 positions, tc, update_cache,
                                 site=f"block[{cfg.n_repeats * P + t}]")
        aux_sum = aux_sum + aux
        if new_caches is not None:
            new_caches["tail"][f"tail{t}"] = _unbind_layer(
                caches["tail"][f"tail{t}"], nc if nc is not None else tc,
                lambda a, u: u)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    aux_mean = aux_sum / max(cfg.n_layers, 1)
    if return_hidden:
        return x, new_caches, aux_mean

    logits = _logits(params, x, cfg, rt)
    return logits, new_caches, aux_mean


def _bind_layer(node, layer, take):
    """A layer's view of a cache tree: `take` applied to every leaf, except
    that a paged attention cache (a dict with a block table) keeps its
    layer-stacked pool leaves whole and gains its ``"layer"`` index."""
    if "tbl" in node:
        out = {k: v if k in POOL_LEAVES else take(v) for k, v in node.items()}
        out["layer"] = layer
        return out
    return {k: _bind_layer(v, layer, take) if isinstance(v, dict) else take(v)
            for k, v in node.items()}


def _unbind_layer(node, new, put):
    """Inverse of `_bind_layer`: `put(old, new)` for every leaf, except that
    a paged attention cache takes its written pool leaves from `new` as
    they are and keeps its own block table (a layer never writes it)."""
    if "tbl" in node:
        return {k: new[k] if k in POOL_LEAVES else v for k, v in node.items()}
    return {k: (_unbind_layer(v, new[k], put) if isinstance(v, dict)
                else put(v, new[k]))
            for k, v in node.items()}


def _take_unit(rep, r):
    """Repeat unit `r`'s caches out of the stacked tree: per-layer leaves
    sliced at `r`, paged pools whole and addressed at layer `r`."""
    return _bind_layer(rep, r, lambda a: a[r])


def _put_unit(rep, unit, r):
    """Write unit `r`'s caches back into the stacked tree, in place."""
    return _unbind_layer(
        rep, unit,
        lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, r, 0))


def _logits(params, x, cfg: ArchConfig, rt: Runtime):
    """x [..., D] -> logits [..., Vp]; keeps token dims data-sharded and the
    vocab dim TP-sharded (2D flattened-token and 3D [B,S,D] forms).

    The head quantizes per the plan's "lm_head" site (uniform legacy
    configs map quantize_embedding=False to a float lm_head rule)."""
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(x.dtype)              # [Vp, D]
        logits = jnp.einsum("...d,vd->...v", x, w)
    else:
        logits = qdense(params["lm_head"]["w"], x,
                        rt.quant_cfg(cfg, "lm_head"), tag="lm_head")
    return shard(logits, "act_tv" if logits.ndim == 2 else "act_btv")


# ------------------------------------------------------------------- loss --
def lm_loss(
    params: Dict,
    tokens: jnp.ndarray,              # [B, S+1]: inputs/targets shifted
    cfg: ArchConfig,
    rt: Runtime,
    positions: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict]:
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    hidden, _, aux = forward(params, inp, cfg, rt, positions,
                             return_hidden=True)
    B, S, D = hidden.shape
    hf = hidden.reshape(B * S, D)
    tf = tgt.reshape(B * S)

    chunk = rt.loss_chunk
    if chunk and (B * S) % chunk == 0 and (B * S) > chunk:
        n = (B * S) // chunk

        def step(acc, xs):
            h, t = xs
            nll = _xent(params, h, t, cfg, rt)
            return acc + jnp.sum(nll), None

        total, _ = jax.lax.scan(
            step, jnp.zeros((), jnp.float32),
            (hf.reshape(n, chunk, D), tf.reshape(n, chunk)),
        )
    else:
        total = jnp.sum(_xent(params, hf, tf, cfg, rt))

    loss = total / (B * S)
    if cfg.n_experts:
        loss = loss + cfg.router_aux_coef * aux
    return loss, {"nll": total / (B * S), "aux": aux}


def _xent(params, h, t, cfg: ArchConfig, rt: Runtime):
    h = shard(h, "act_td")                                      # [n, D]
    logits = _logits(params, h, cfg, rt)                        # [n, Vp]
    logits = logits.astype(jnp.float32)
    Vp = logits.shape[-1]
    if Vp != cfg.vocab:
        mask = jnp.arange(Vp) < cfg.vocab
        logits = jnp.where(mask[None, :], logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
    return lse - picked


# ------------------------------------------------------------ serve steps --
def prefill(params, tokens, cfg, rt, caches, positions=None):
    """Fill caches with a prompt; returns (last_logits [B, V], caches)."""
    hidden, new_caches, _ = forward(
        params, tokens, cfg, rt, positions, caches,
        update_cache=True, return_hidden=True,
    )
    logits = _logits(params, hidden[:, -1:], cfg, rt)[:, 0]
    return logits, new_caches


def decode_step(params, token, cfg, rt, caches, positions):
    """One decode step. token [B, 1]; positions [B, 1] absolute positions."""
    hidden, new_caches, _ = forward(
        params, token, cfg, rt, positions, caches,
        update_cache=True, return_hidden=True,
    )
    logits = _logits(params, hidden, cfg, rt)[:, 0]
    return logits, new_caches

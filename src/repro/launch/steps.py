"""Step functions + input specs shared by the trainer, server and dry-run.

`input_specs(arch, shape)` returns ShapeDtypeStruct stand-ins for every model
input of an (architecture x assigned-shape) cell — weak-type-correct,
shardable, no device allocation — exactly what `.lower()` needs.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ArchConfig, Runtime, Shape
from repro.distributed.sharding import (
    dp_axes,
    make_param_shardings,
    mesh_context,
    specs_to_shardings,
)
from repro.models import decode_step, init_caches, init_model, lm_loss
from repro.models.transformer import _logits, forward
from repro.models.transformer import prefill as prefill_fn
from repro.optim import adamw_init, adamw_update, warmup_cosine


# ------------------------------------------------------------- train state --
def init_train_state(key, cfg: ArchConfig):
    params = init_model(key, cfg)
    return {"params": params, "opt": adamw_init(params),
            "step": jnp.zeros((), jnp.int32)}


def make_train_step(cfg: ArchConfig, rt: Runtime, *, peak_lr=3e-4,
                    warmup=100, total_steps=10000):
    def train_step(state, batch):
        """batch: tokens [B, S+1]."""
        def loss_fn(p):
            return lm_loss(p, batch, cfg, rt)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"])
        lr = warmup_cosine(state["step"], peak_lr=peak_lr,
                           warmup_steps=warmup, total_steps=total_steps)
        params, opt, info = adamw_update(state["params"], grads, state["opt"], lr)
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        out = {"loss": loss, "lr": lr, **metrics, **info}
        return new_state, out

    return train_step


def make_prefill_step(cfg: ArchConfig, rt: Runtime):
    def prefill_step(params, tokens, caches, positions=None):
        return prefill_fn(params, tokens, cfg, rt, caches, positions)

    return prefill_step


def make_decode_step(cfg: ArchConfig, rt: Runtime):
    def step(params, token, caches, positions):
        return decode_step(params, token, cfg, rt, caches, positions)

    return step


def make_serving_steps(cfg: ArchConfig, rt: Runtime, paged: bool = False):
    """(jit'd prefill, jit'd tail-prefill-or-None, jit'd decode) for the
    continuous-batching engine.

    All donate the cache argument (the KV pool is the dominant buffer and
    is threaded through every step; the layer loop writes it in place, so
    a step holds one copy of it: ``models.transformer.forward``) and run
    greedy argmax *inside* the jit,
    so the only device->host traffic per step is one int32 per row.  jit
    re-specializes per input shape, so the engine's batch/prompt bucketing
    bounds the number of compilations — one per (bucket) signature, cached
    across the serving run.

    ``paged=True`` returns steps that additionally take the engine's
    device-resident block-table pool (``tbl_all`` [max_batch, pages_per_seq])
    and the step's slot ids: the per-row tables are gathered and bound to
    every layer inside the jit, so the host never assembles a block table
    per step — rows move host->device only when a request is admitted or
    its allocation grows.  The tail-prefill step is the chunked-prefill
    seam for prefix-cache hits: it runs the same prefill with
    ``rt.prefill_over_cache`` set, so the (suffix-only) queries attend over
    the gathered page pool — cached prefix pages included — instead of just
    the in-flight K/V.  For the contiguous layout it is None (no pages to
    share).
    """
    vocab = cfg.vocab

    def _greedy(logits):
        return jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)

    if paged:
        import dataclasses

        from repro.serving.kv_pages import with_block_tables

        rt_tail = dataclasses.replace(rt, prefill_over_cache=True)

        def make_prefill(rt_used):
            def prefill_step(params, tokens, caches, positions, tbl_all,
                             slots):
                caches = with_block_tables(caches,
                                           jnp.take(tbl_all, slots, 0))
                logits, caches = prefill_fn(params, tokens, cfg, rt_used,
                                            caches, positions)
                return _greedy(logits), caches

            return prefill_step

        def dec_step(params, token, caches, positions, tbl_all, slots):
            caches = with_block_tables(caches, jnp.take(tbl_all, slots, 0))
            logits, caches = decode_step(params, token, cfg, rt, caches,
                                         positions)
            return _greedy(logits), caches

        return (jax.jit(make_prefill(rt), donate_argnums=(2,)),
                jax.jit(make_prefill(rt_tail), donate_argnums=(2,)),
                jax.jit(dec_step, donate_argnums=(2,)))

    def prefill_step(params, tokens, caches, positions):
        logits, caches = prefill_fn(params, tokens, cfg, rt, caches,
                                    positions)
        return _greedy(logits), caches

    def dec_step(params, token, caches, positions):
        logits, caches = decode_step(params, token, cfg, rt, caches,
                                     positions)
        return _greedy(logits), caches

    return (jax.jit(prefill_step, donate_argnums=(2,)),
            None,
            jax.jit(dec_step, donate_argnums=(2,)))


def make_ragged_step(cfg: ArchConfig, rt: Runtime):
    """One jit'd step for the ragged token-major engine: a flat [1, T] pack
    of mixed prefill-chunk and decode tokens, routed per row through
    ``slots`` (which block-table row each token belongs to, -1 = padding).

    The signature depends only on the padded token budget T (and the fixed
    max_batch/pages_per_seq of the table pool) — never on how many requests
    are prefilling vs decoding — so once the budget's shape is warm,
    steady-state recompiles are zero *by construction*, not by bucketing.

    ``emit_rows`` [max_batch] names, per slot, the packed row whose logits
    produce that request's next token (-1 = no emission this step: the
    request's prefill still has chunks to go, or the slot is empty); the
    lm head runs only on those max_batch gathered rows, and greedy argmax
    stays inside the jit like the bucketed steps."""
    from repro.serving.kv_pages import with_token_slots

    vocab = cfg.vocab

    def ragged_step(params, tokens, caches, positions, tbl_all, slots,
                    emit_rows):
        caches = with_token_slots(caches, tbl_all, slots)
        hidden, caches, _ = forward(params, tokens, cfg, rt, positions,
                                    caches, update_cache=True,
                                    return_hidden=True)
        h = jnp.take(hidden, jnp.clip(emit_rows, 0, None), axis=1)  # [1,mb,D]
        logits = _logits(params, h, cfg, rt)[0]                     # [mb, V]
        nxt = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        return jnp.where(emit_rows >= 0, nxt, -1), caches

    return jax.jit(ragged_step, donate_argnums=(2,))


# ------------------------------------------------------------ input specs --
def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _dp_spec(mesh):
    dpa = dp_axes() if mesh is not None else ()
    if not dpa:
        return None
    return dpa if len(dpa) > 1 else dpa[0]


def input_specs(cfg: ArchConfig, shape: Shape, mesh=None, rt: Runtime = None) -> Dict:
    """ShapeDtypeStruct stand-ins for the cell's step-function inputs."""
    rt = rt or Runtime()
    B, S = shape.batch, shape.seq

    def tok_sharding(b):
        if mesh is None:
            return None
        dspec = _dp_spec(mesh)
        size = 1
        for a in (dspec if isinstance(dspec, tuple) else (dspec,)):
            size *= mesh.shape[a]
        return NamedSharding(mesh, P(dspec if b % size == 0 else None, None))

    with mesh_context(mesh):
        if shape.kind == "train":
            return {"batch": _sds((B, S + 1), jnp.int32, tok_sharding(B))}
        if shape.kind == "prefill":
            caches = jax.eval_shape(
                lambda: init_caches(cfg, rt, batch=B, seq=S))
            caches = _shard_cache_specs(caches, mesh)
            return {
                "tokens": _sds((B, S), jnp.int32, tok_sharding(B)),
                "caches": caches,
            }
        if shape.kind == "decode":
            caches = jax.eval_shape(
                lambda: init_caches(cfg, rt, batch=B, seq=S))
            caches = _shard_cache_specs(caches, mesh)
            return {
                "token": _sds((B, 1), jnp.int32, tok_sharding(B)),
                "caches": caches,
                "positions": _sds((B, 1), jnp.int32, tok_sharding(B)),
            }
    raise ValueError(shape.kind)


def _shard_cache_specs(caches, mesh):
    """KV/state caches: shard the *batch* dim over data when divisible.
    Stacked per-repeat caches are [n_repeats, B, ...] (batch at dim 1);
    tail-block caches are [B, ...] (batch at dim 0)."""
    if mesh is None:
        return caches
    dspec = _dp_spec(mesh)
    size = 1
    for a in (dspec if isinstance(dspec, tuple) else (dspec,)):
        size *= mesh.shape[a]

    def shard_leaf(batch_dim):
        def inner(leaf):
            ax = [None] * leaf.ndim
            if leaf.ndim > batch_dim and leaf.shape[batch_dim] % size == 0 \
                    and leaf.shape[batch_dim] > 1:
                ax[batch_dim] = dspec
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=NamedSharding(mesh, P(*ax)))
        return inner

    return {
        "rep": jax.tree.map(shard_leaf(1), caches["rep"]),
        "tail": jax.tree.map(shard_leaf(0), caches["tail"]),
    }


def state_specs(cfg: ArchConfig, mesh, *, zero: bool = True):
    """(ShapeDtypeStruct tree, sharding tree) for the full train state."""
    state = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg))
    pspecs = make_param_shardings(state["params"], mesh)
    ospecs = {
        "mu": make_param_shardings(state["opt"]["mu"], mesh, zero=zero),
        "nu": make_param_shardings(state["opt"]["nu"], mesh, zero=zero),
        "step": P(),
    }
    specs = {"params": pspecs, "opt": ospecs, "step": P()}
    shardings = specs_to_shardings(specs, mesh)
    sds = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        state, shardings,
    )
    return sds, shardings


def param_specs_only(cfg: ArchConfig, mesh):
    params = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg))
    specs = make_param_shardings(params, mesh)
    shardings = specs_to_shardings(specs, mesh)
    sds = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params, shardings,
    )
    return sds, shardings

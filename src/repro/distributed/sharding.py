"""Sharding rules: logical activation names + parameter-path rules ->
PartitionSpecs on the production mesh (DP x TP [x pod], GQA-aware).

A context-managed `MeshContext` makes the rules visible inside model code via
`shard(x, "act_btd")`-style constraints; with no context active the helpers
are no-ops so the same model code runs on a single CPU device.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with replication checking off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def dp_axes() -> Tuple[str, ...]:
    m = current_mesh()
    if m is None:
        return ()
    return ("pod", "data") if "pod" in m.axis_names else ("data",)


TP = "model"


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        if mesh is None:
            yield
        else:
            with mesh:
                yield
    finally:
        _state.mesh = prev


#: logical activation specs (model axis sizes are checked at constraint time)
def _act_spec(name: str) -> P:
    dp = dp_axes()
    dpa = dp if len(dp) > 1 else (dp[0] if dp else None)
    return {
        "act_btd": P(dpa, None, None),        # [B, S, D] replicated over TP
        "act_btf": P(dpa, None, TP),          # [B, S, F] FFN hidden
        "act_bthd": P(dpa, None, TP),         # [B, S, H*hd] combined heads
        "act_btv": P(dpa, None, TP),          # [B, S, V] logits
        "act_td": P(dpa, None),               # [T, D] flattened tokens
        "act_tv": P(dpa, TP),                 # [T, V] flattened logits
        "tokens": P(dpa, None),               # [B, S]
        "moe_expert": P(TP, None, None),      # [E, C, D] expert buffers
    }[name]


def tp_size() -> int:
    m = current_mesh()
    return m.shape[TP] if m is not None else 1


def shard_spec(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """Constraint with an explicit spec (no divisibility guard)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard(x: jnp.ndarray, name: str) -> jnp.ndarray:
    """Apply a logical sharding constraint if a mesh context is active and
    every named axis divides the corresponding array dimension."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = _act_spec(name)
    # divisibility guard: drop axes that do not divide
    fixed = []
    for dim, axes in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if axes is None:
            fixed.append(None)
            continue
        ax_tuple = axes if isinstance(axes, tuple) else (axes,)
        size = 1
        for a in ax_tuple:
            size *= mesh.shape[a]
        fixed.append(axes if dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*fixed))
    )


# ------------------------------------------------------------ param rules --
#: (path regex, spec builder).  Specs written for *unstacked* params; a layer-
#: stacked param (extra leading dim from scan-over-layers) gets None prepended.
def param_spec(path: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    tp = mesh.shape[TP]

    def fits(dim_idx: int) -> bool:
        return shape[dim_idx] % tp == 0

    rules = [
        (r"embed/tok$", lambda: P(TP, None) if fits(0) else P(None, None)),
        (r"(lm_head|router)/w$", lambda: P(None, TP) if fits(1) else P()),
        (r"w(q|k|v|kv|qkv)(/w)?$", lambda: P(None, TP) if fits(1) else P(None, None)),
        (r"w(q|k|v|qkv)_bias$", lambda: P(TP,) if fits(0) else P(None)),
        (r"wo(/w)?$", lambda: P(TP, None) if fits(0) else P(None, None)),
        (r"ffn/(w_in|w_gate)$", lambda: P(None, TP) if fits(1) else P(None, None)),
        (r"ffn/w_out$", lambda: P(TP, None) if fits(0) else P(None, None)),
        (r"ffn/(b_in|b_gate)$", lambda: P(TP,) if fits(0) else P(None)),
        # Experts: E over TP (expert parallelism) + F over data (FSDP-style
        # weight sharding; gathered per-layer inside the MoE shard_map body,
        # whose backward is the matching reduce-scatter).
        (r"experts/(w_in|w_gate)$", lambda: P(TP, None, "data")
            if shape[2] % mesh.shape["data"] == 0 else P(TP, None, None)),
        (r"experts/w_out$", lambda: P(TP, "data", None)
            if shape[1] % mesh.shape["data"] == 0 else P(TP, None, None)),
        (r"(mamba|lru)/in_proj$", lambda: P(None, TP) if fits(1) else P(None, None)),
        (r"(mamba|lru)/out_proj$", lambda: P(TP, None) if fits(0) else P(None, None)),
        (r"lru/w_(a|x)$", lambda: P(None, TP) if fits(1) else P(None, None)),
    ]
    for pat, builder in rules:
        if re.search(pat, path):
            spec = builder()
            return spec
    return P()                                                  # replicate


def stacked_param_spec(path: str, shape, mesh: Mesh, stacked: bool) -> P:
    inner_shape = shape[1:] if stacked else shape
    spec = param_spec(path, inner_shape, mesh)
    if stacked:
        return P(*((None,) + tuple(spec)))
    return spec


def make_param_shardings(params, mesh: Mesh, stacked_prefixes=("layers",),
                         zero: bool = False):
    """PartitionSpec pytree for a param tree (paths joined with '/').

    `zero=True` (ZeRO-style, for optimizer state trees): additionally shard
    the first yet-unsharded dimension divisible by the data-axis size, so
    fp32 Adam moments spread over the full mesh instead of only TP.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    dp = mesh.shape["data"]
    specs = []
    for keypath, leaf in flat:
        path = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in keypath
        )
        rel = path
        for pre in ("mu/", "nu/"):          # optimizer trees mirror params
            if rel.startswith(pre):
                rel = rel[len(pre):]
        for suf in ("/packed", "/scale"):   # packed serving weights
            if rel.endswith(suf):
                rel = rel[: -len(suf)]
        stacked = any(rel.startswith(p) for p in stacked_prefixes)
        spec = stacked_param_spec(rel, leaf.shape, mesh, stacked)
        if zero and "data" not in jax.tree.leaves(tuple(spec)):
            ax = list(spec) + [None] * (leaf.ndim - len(spec))
            for d in range(leaf.ndim):
                if ax[d] is None and leaf.shape[d] % dp == 0 and \
                        leaf.shape[d] >= dp:
                    ax[d] = "data"
                    break
            spec = P(*ax)
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def specs_to_shardings(specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )

"""Seeded weights, made on the device, one layer at a time.

The benchmark, not the program, owns the weights: the served model and
the plain reference both draw them from this generator and the run's
``--seed``, so the reference takes nothing that the program made.  Each
leaf has its own key, folded from the seed, the layer and the leaf's
place, so a layer can be drawn alone: the program's tree is packed layer
by layer inside one jitted call (no float32 copy of the whole model is
ever held), and the reference draws each layer again when it needs it.

Linear weights are N(0, 1/fan_in); norm weights 1 + N(0, 0.1^2); biases
N(0, 0.1^2).  The embedding is N(0, 1/d_model), which the tied head also
reads.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: the seven projections of a layer, all int4 in the served model
LINEAR = ("attn.wq", "attn.wk", "attn.wv", "attn.wo",
          "ffn.w_in", "ffn.w_gate", "ffn.w_out")

_EMBED, _FINAL_NORM = 1 << 20, (1 << 20) + 1


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number the driver may pass (over 32 bits)."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def layer_leaves(m: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(path, shape, kind) of one layer's leaves, in the program's tree
    layout; `m` is a configuration file's ``model`` section."""
    D, H, KV, hd, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    out = [("norm1", (D,), "norm"),
           ("attn.wq", (D, H * hd), "linear"),
           ("attn.wk", (D, KV * hd), "linear"),
           ("attn.wv", (D, KV * hd), "linear"),
           ("attn.wo", (H * hd, D), "linear")]
    if m["qkv_bias"]:
        out += [("attn.wq_bias", (H * hd,), "bias"),
                ("attn.wk_bias", (KV * hd,), "bias"),
                ("attn.wv_bias", (KV * hd,), "bias")]
    if m["qk_norm"]:
        out += [("attn.q_norm", (hd,), "norm"),
                ("attn.k_norm", (hd,), "norm")]
    out += [("norm2", (D,), "norm"),
            ("ffn.w_in", (D, F), "linear"),
            ("ffn.w_gate", (D, F), "linear"),
            ("ffn.w_out", (F, D), "linear")]
    return out


def _draw(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "linear":
        return z / math.sqrt(shape[0])
    if kind == "norm":
        return 1.0 + 0.1 * z
    return 0.1 * z


def layer(key, l, m: Dict) -> Dict[str, jax.Array]:
    """Layer `l`'s float32 weights, flat {path: array}."""
    kl = jax.random.fold_in(key, l)
    return {path: _draw(jax.random.fold_in(kl, i), shape, kind)
            for i, (path, shape, kind) in enumerate(layer_leaves(m))}


def embedding(key, m: Dict) -> jax.Array:
    """[vocab_padded, d_model] float32."""
    vp = -(-m["vocab"] // 128) * 128
    z = jax.random.normal(jax.random.fold_in(key, _EMBED),
                          (vp, m["d_model"]), jnp.float32)
    return z / math.sqrt(m["d_model"])


def final_norm(key, m: Dict) -> jax.Array:
    return _draw(jax.random.fold_in(key, _FINAL_NORM), (m["d_model"],),
                 "norm")


def nest(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, a in flat.items():
        node = out
        *parents, leaf = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = a
    return out


def served_params(cfg, rt, m: Dict, seed: int):
    """The program's serving tree for this seed, made in one jitted call:
    each layer is drawn and packed by the program's own
    ``pack_for_serving`` in turn, and the packed layers are stacked."""
    from repro.core.quant_plan import pack_for_serving

    def pack_layer(key, l):
        one = jax.tree.map(lambda a: a[None], nest(layer(key, l, m)))
        packed = pack_for_serving({"layers": {"u0": one}}, cfg, rt)
        return jax.tree.map(lambda a: a[0], packed["layers"]["u0"])

    def build(key):
        layers = jax.lax.map(lambda l: pack_layer(key, l),
                             jnp.arange(m["n_layers"]))
        return {"embed": {"tok": embedding(key, m)},
                "final_norm": final_norm(key, m),
                "layers": {"u0": layers}}

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))

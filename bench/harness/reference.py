"""The plain reference: the served model's forward pass in float32.

It imports nothing of the program.  It draws the weights from the seed
itself (``weights``), quantizes each projection to int4 with one
symmetric float32 scale per output channel (scale = max|w| / 7, codes
round(w / scale) clipped to [-8, 7]), which is what the configuration's
``w4a16`` states, and computes everything else in float32 at the highest
matmul precision: RMSNorm, the projections (with biases), qk-norm, RoPE
(rotate-half, theta from the file), causal GQA softmax attention, SwiGLU,
and the head tied to the embedding.

It runs one sequence at a time, right-padded to a power of two (at least
``PAD``, at most the cell's context), and the whole model layer by layer:
each layer's weights are drawn once and applied to every sequence, so
only one layer is ever held.  The padded lengths and the number of rows
read per sequence are fixed per cell, so each run compiles nothing new.

``act`` names the control's precision: the reference with every
activation that enters a projection (the head included) and every key and
value rounded to it, one scale per row: ``"fp8"`` (float8 e4m3) or
``"int8"``.  ``None`` is the reference itself.  The control is put in the
program's place: teacher-forced over the same prompts and served tokens,
it names the token it would put first at each served position, and that
token is judged by the reference exactly as a served token is.
"""

from __future__ import annotations

import functools
import math
from collections import namedtuple
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

PAD = 512
CHUNK = 512
HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _round_rows(x, act: Optional[str]):
    """x rounded to the control's precision, one scale per row."""
    if act is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    if act == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if act == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    raise ValueError(act)


def _int4(w):
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True),
                        1e-8) / 7.0
    return jnp.clip(jnp.round(w / scale), -8, 7) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [S, heads, hd]; rotate-half over the two halves of hd."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA over one sequence: q [S, H, hd], k/v [S, KV, hd]."""
    S, H, hd = q.shape
    KV = k.shape[1]
    qg = q.reshape(S, KV, H // KV, hd)
    chunk = min(CHUNK, S)
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * chunk, chunk, 0)
        s = jnp.einsum("qkgh,tkh->kgqt", qb, k, precision=HI) / math.sqrt(hd)
        qpos = i * chunk + jnp.arange(chunk)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkh->qkgh", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(S // chunk))
    return out.reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=("m", "act"))
def _layer(w, x, *, m, act):
    """One decoder layer over x [S, D] (positions 0..S-1)."""
    H, KV, hd, eps = m.n_heads, m.n_kv_heads, m.head_dim, m.norm_eps
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _round_rows(_rms(x, w["norm1"], eps), act)
    q = _mm(h, w["attn.wq"]).reshape(S, H, hd)
    k = _mm(h, w["attn.wk"]).reshape(S, KV, hd)
    v = _mm(h, w["attn.wv"]).reshape(S, KV, hd)
    if m.qkv_bias:
        q = q + w["attn.wq_bias"].reshape(H, hd)
        k = k + w["attn.wk_bias"].reshape(KV, hd)
        v = v + w["attn.wv_bias"].reshape(KV, hd)
    if m.qk_norm:
        q = _rms(q, w["attn.q_norm"], eps)
        k = _rms(k, w["attn.k_norm"], eps)
    q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
    k, v = _round_rows(k, act), _round_rows(v, act)
    o = _round_rows(_attention(q, k, v).reshape(S, H * hd), act)
    x = x + _mm(o, w["attn.wo"])
    h = _round_rows(_rms(x, w["norm2"], eps), act)
    f = jax.nn.silu(_mm(h, w["ffn.w_gate"])) * _mm(h, w["ffn.w_in"])
    return x + _mm(_round_rows(f, act), w["ffn.w_out"])


@functools.partial(jax.jit, static_argnames=("m",))
def _draw_layer(key, l, *, m):
    w = weights.layer(key, l, m._asdict())
    return {p: (_int4(a) if p in weights.LINEAR else a) for p, a in w.items()}


def _head(key, m):
    """The final norm's weights and the tied head [vocab, d]."""
    return (weights.final_norm(key, m._asdict()),
            weights.embedding(key, m._asdict())[:m.vocab])


def _logits(head, h, rows, m, act):
    """Logits [len(rows), vocab] of the final hidden rows h[rows]."""
    norm, emb = head
    return _mm(_round_rows(_rms(h[rows], norm, m.norm_eps), act), emb.T)


def _gaps(logits, toks):
    """How far each named token's logit lies below the row's best."""
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, toks[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnames=("m", "act"))
def _row_gaps(key, xr, xc, rows, toks, *, m, act):
    """Under the reference's logits at `rows`, the gap of each token of
    `toks`; with a control (`act`), of the token that the control's logits
    (from its own hidden states `xc`) put first there instead."""
    head = _head(key, m)
    if act is not None:
        toks = jnp.argmax(_logits(head, xc, rows, m, act), -1)
    return _gaps(_logits(head, xr, rows, m, None), toks)


def model_spec(m: Dict):
    """The configuration's model sizes as a hashable static argument."""
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "qkv_bias", "qk_norm", "rope_theta",
            "norm_eps")
    return namedtuple("ModelSpec", keys)(*(m[k] for k in keys))


def padded_length(n: int, max_len: int) -> int:
    """The power of two (at least PAD) that holds n tokens, capped at
    max_len (a multiple of CHUNK)."""
    size = PAD
    while size < n:
        size *= 2
    return min(size, max_len)


def _hidden(spec, key, m: Dict, seqs, act: Optional[str],
            max_len: int) -> List[jax.Array]:
    """Final hidden states of each sequence's tokens[:-1], padded."""
    emb = weights.embedding(key, m)
    xs = []
    for toks, _ in seqs:
        n = len(toks) - 1
        padded = np.zeros(padded_length(n, max_len), np.int32)
        padded[:n] = toks[:-1]
        xs.append(emb[jnp.asarray(padded)])
    del emb
    for l in range(spec.n_layers):
        w = _draw_layer(key, l, m=spec)
        xs = [_layer(w, x, m=spec, act=act) for x in xs]
    return xs


def gap_readings(m: Dict, seed: int, seqs: Sequence[Tuple[np.ndarray, int]],
                 max_len: int, max_served: int,
                 controls: Sequence[str] = ()) -> Dict[str, np.ndarray]:
    """`seqs` holds (tokens, n_prompt): a prompt and the tokens served
    after it, at most `max_len` and `max_served` long.  Returns, under
    ``"served"``, the gap by which each served token's logit lies below
    the reference's best at its position; and for each control precision,
    the gap of the token that the control puts first at the same
    positions.  One entry per served token, in order."""
    spec = model_spec(m)
    key = weights.seed_key(seed)
    ref = _hidden(spec, key, m, seqs, None, max_len)
    ctls = {act: _hidden(spec, key, m, seqs, act, max_len)
            for act in controls}
    out = {name: [] for name in ("served", *controls)}
    rp = max_served
    for i, (toks, n_prompt) in enumerate(seqs):
        r = len(toks) - n_prompt
        rows = jnp.asarray(np.minimum(n_prompt - 1 + np.arange(rp),
                                      ref[i].shape[0] - 1))
        served = np.zeros(rp, np.int32)
        served[:r] = toks[n_prompt:]
        for name, x in (("served", ref[i]),
                        *((act, xs[i]) for act, xs in ctls.items())):
            act = None if name == "served" else name
            g = _row_gaps(key, ref[i], x, rows, jnp.asarray(served), m=spec,
                          act=act)
            out[name].append(np.asarray(g)[:r])
    return {name: np.concatenate(g) for name, g in out.items()}


#: the numbers a configuration's ``check`` may compare, each over the gaps
#: of every served token in the sample
STATS = {"gap_max": lambda g: float(np.max(g)),
         "gap_mean": lambda g: float(np.mean(g))}

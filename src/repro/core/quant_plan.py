"""Site-addressable quantization plans: mixed precision as a first-class
config object.

The paper's multiplier makes *dense arrays* of 4-bit products cheap, but real
deployments never quantize uniformly — sensitive sites (lm_head, first/last
blocks, attention output) keep higher precision while the bulk runs W4 (cf.
Vakili et al., dynamic per-operation reconfiguration; Böttcher & Kumm, mixed
sub-multiplier precisions inside one product).  A ``QuantPlan`` maps
glob-style *site patterns* to per-site ``QuantConfig``s:

    QuantPlan(rules=(
        ("block[0].attn.*", QuantConfig(backend="float")),
        ("ffn.*",           QuantConfig(backend="w4a16")),
        ("lm_head",         QuantConfig(backend="float")),
        ("*",               QuantConfig(backend="int_sim")),
    ))

Site names are hierarchical and unified with the autotune tile-tuning tags —
one site string keys both the quant choice and the (bm, bn, bk) tile lookup:

    block[<i>].attn.qkv | block[<i>].attn.wo          (i = global layer idx)
    block[<i>].ffn.{w_in,w_gate,w_out}
    block[<i>].moe.experts | block[<i>].shared.* | block[<i>].dense_ffn.*
    block[<i>].mamba.{in_proj,out_proj}
    block[<i>].lru.{in_x,in_g,w_a,w_x,out}
    lm_head

Matching: ``*``/``?`` are wildcards, every other character (including
``[``/``]``) is literal.  A pattern matches the full site or any
``.``-aligned suffix, so ``attn.qkv`` matches ``block[3].attn.qkv``.
Precedence is by *specificity* — the matching pattern with the most literal
characters wins (``block[0].attn.qkv`` beats ``attn.*`` beats ``*``); among
equal specificity, the later rule wins.

Plans come from three spec forms (``get_plan``): a named preset
(``uniform_w4a4``, ``w4a16_sensitive_fp``, ``qat_mixed``, ...), a JSON file
path, or an inline ``pattern=backend[/g<group>][;...]`` string — the latter
two are what ``--quant-plan <name|path>`` accepts on every launcher.

The scan-stacked layer loop constraint: ``lax.scan`` traces one body for all
repeat units, so per-site resolution must happen *outside* the scan body.
``plan_repeat_uniform`` decides whether every repeat unit resolves
identically (scan stays on, compiled graph static); a plan that
distinguishes repeats forces the unrolled layer loop, and
``plan_pack_tree`` then splits the stacked weights into per-repeat subtrees
(``layers = {"r0": ..., "r1": ...}``) so each layer can carry a different
weight format.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .qlinear import QuantConfig

#: backends the live serving path packs ahead of time (legacy-compatible:
#: exactly the set build_params always packed).
SERVE_PACKED = frozenset({"w4a4_packed", "w4a16_packed"})

#: backends a *quantized checkpoint* stores packed (everything that serves
#: from int4 nibbles; fake_quant/netlist/float sites keep float masters).
CKPT_PACKED = SERVE_PACKED | frozenset(
    {"int_sim", "pallas_int4", "lut4", "w4a16"})


def join_site(prefix: str, leaf: str) -> str:
    """``"block[3]" + "attn.qkv" -> "block[3].attn.qkv"``; empty prefix ok."""
    return f"{prefix}.{leaf}" if prefix else leaf


# ------------------------------------------------------------- matching ----
@functools.lru_cache(maxsize=4096)
def _compiled(pattern: str) -> "re.Pattern[str]":
    out = []
    for ch in pattern:
        if ch == "*":
            out.append(".*")
        elif ch == "?":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return re.compile("".join(out) + r"\Z")


def pattern_matches(pattern: str, site: str) -> bool:
    """Full-site or dot-aligned-suffix glob match with literal brackets."""
    rx = _compiled(pattern)
    if rx.match(site):
        return True
    idx = site.find(".")
    while idx != -1:
        if rx.match(site[idx + 1:]):
            return True
        idx = site.find(".", idx + 1)
    return False


def specificity(pattern: str) -> int:
    """Number of literal (non-wildcard) characters — the precedence key."""
    return len(pattern) - pattern.count("*") - pattern.count("?")


# ----------------------------------------------------------------- plan ----
@dataclasses.dataclass(frozen=True)
class QuantPlan:
    """Ordered (pattern, QuantConfig) rules; frozen and hashable so it can
    key trace-time caches."""

    rules: Tuple[Tuple[str, QuantConfig], ...]
    name: str = ""

    def resolve(self, site: str) -> QuantConfig:
        return _resolve(self, site)

    @property
    def backends(self) -> frozenset:
        return frozenset(qc.backend for _, qc in self.rules)


@functools.lru_cache(maxsize=65536)
def _resolve(plan: QuantPlan, site: str) -> QuantConfig:
    best: Optional[QuantConfig] = None
    best_key = (-1, -1)
    for i, (pattern, qc) in enumerate(plan.rules):
        if not pattern_matches(pattern, site):
            continue
        key = (specificity(pattern), i)
        if key > best_key:
            best, best_key = qc, key
    if best is None:
        # a silent default here would let a typo'd plan (e.g. "ffn=w4a16"
        # with no "*" rule) serve the whole model unquantized while reports
        # label it quantized — fail loudly instead
        raise ValueError(
            f"site {site!r} matches no rule of plan "
            f"{plan.name or plan.rules!r}; add a catch-all '*' rule")
    return best


# -------------------------------------------------------- (de)serialize ----
_QC_FIELDS = ("backend", "w_bits", "a_bits", "group_size", "quantize_embedding")


def plan_to_dict(plan: QuantPlan) -> Dict:
    return {
        "name": plan.name,
        "rules": [
            {"pattern": pattern,
             **{f: getattr(qc, f) for f in _QC_FIELDS}}
            for pattern, qc in plan.rules
        ],
    }


def plan_from_dict(d: Dict) -> QuantPlan:
    rules = tuple(
        (r["pattern"],
         QuantConfig(**{f: r[f] for f in _QC_FIELDS if f in r}))
        for r in d["rules"]
    )
    return QuantPlan(rules=rules, name=d.get("name", ""))


def _parse_inline(spec: str) -> QuantPlan:
    """``"block[0].*=float;ffn.*=w4a16/g128;*=int_sim"`` -> QuantPlan."""
    rules = []
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        pattern, _, rhs = part.partition("=")
        if not rhs:
            raise ValueError(f"bad plan rule {part!r}: expected pattern=backend")
        backend, *opts = rhs.split("/")
        kw = {"backend": backend.strip()}
        for opt in opts:
            if opt.startswith("g"):
                kw["group_size"] = int(opt[1:])
            elif opt.startswith("w"):
                kw["w_bits"] = int(opt[1:])
            elif opt.startswith("a"):
                kw["a_bits"] = int(opt[1:])
            else:
                raise ValueError(f"unknown plan option {opt!r} in {part!r}")
        rules.append((pattern.strip(), QuantConfig(**kw)))
    return QuantPlan(rules=tuple(rules), name="inline")


_FLOAT = QuantConfig(backend="float")

#: named presets — the spec forms every ``--quant-plan`` flag accepts.
PRESETS: Dict[str, QuantPlan] = {
    # uniform W4A4 integer GEMMs; lm_head stays float (the classic recipe)
    "uniform_w4a4": QuantPlan(
        name="uniform_w4a4",
        rules=(("*", QuantConfig(backend="int_sim")),
               ("lm_head", _FLOAT)),
    ),
    # weight-only int4 everywhere except the sensitive sites, which stay fp
    "w4a16_sensitive_fp": QuantPlan(
        name="w4a16_sensitive_fp",
        rules=(("*", QuantConfig(backend="w4a16", a_bits=16, group_size=128)),
               ("block[0].*", _FLOAT),
               ("lm_head", _FLOAT)),
    ),
    # QAT with the first block and head trained in full precision
    "qat_mixed": QuantPlan(
        name="qat_mixed",
        rules=(("*", QuantConfig(backend="fake_quant")),
               ("block[0].*", _FLOAT),
               ("lm_head", _FLOAT)),
    ),
    # pre-packed W4A4 serving (legacy `--quant w4a4_packed` as a plan)
    "serve_w4a4": QuantPlan(
        name="serve_w4a4",
        rules=(("*", QuantConfig(backend="w4a4_packed")),
               ("lm_head", _FLOAT)),
    ),
    # the mixed deployment plan: w4a16 FFNs, float lm_head + block-0
    # attention, int-sim W4A4 everywhere else
    "mixed_sensitive": QuantPlan(
        name="mixed_sensitive",
        rules=(("*", QuantConfig(backend="int_sim")),
               ("ffn.*", QuantConfig(backend="w4a16", a_bits=16)),
               ("block[0].attn.*", _FLOAT),
               ("lm_head", _FLOAT)),
    ),
}

_PLAN_CACHE: Dict[str, QuantPlan] = {}


def get_plan(spec: str) -> QuantPlan:
    """Resolve a plan spec: preset name | JSON file path | inline rules.
    File plans are cached per (path, mtime), so editing the file in a
    long-lived process picks up the new rules."""
    if spec in PRESETS:
        return PRESETS[spec]
    key = spec
    is_file = spec.endswith(".json") or os.path.exists(spec)
    if is_file:
        try:
            key = f"{spec}@{os.stat(spec).st_mtime_ns}"
        except OSError:
            pass
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        return hit
    if is_file:
        with open(spec) as f:
            plan = plan_from_dict(json.load(f))
    elif "=" in spec:
        plan = _parse_inline(spec)
    else:
        raise ValueError(
            f"unknown quant plan {spec!r}: not a preset "
            f"({sorted(PRESETS)}), not a file, and not inline rules "
            "(pattern=backend[;...])")
    _PLAN_CACHE[key] = plan
    return plan


@functools.lru_cache(maxsize=256)
def uniform_plan(qc: QuantConfig) -> QuantPlan:
    """The single-QuantConfig world as a plan (legacy-compatible: lm_head
    stays float unless the config opts in via quantize_embedding)."""
    rules: Tuple[Tuple[str, QuantConfig], ...] = (("*", qc),)
    if qc.quantized and not qc.quantize_embedding:
        rules += (("lm_head", dataclasses.replace(qc, backend="float")),)
    return QuantPlan(rules=rules, name=f"uniform_{qc.backend}")


def active_plan(arch, rt) -> QuantPlan:
    """The plan in effect for (arch, runtime).

    Precedence: ``Runtime.quant_plan`` (name|path|inline) > the deprecated
    ``Runtime.quant_backend`` string (mapped to a uniform plan so it keeps
    working) > ``ArchConfig.quant_plan`` > uniform ``ArchConfig.quant``.
    """
    rt_plan = getattr(rt, "quant_plan", None)
    if rt_plan:
        return get_plan(rt_plan)
    if rt.quant_backend is not None:
        return uniform_plan(
            dataclasses.replace(arch.quant, backend=rt.quant_backend))
    arch_plan = getattr(arch, "quant_plan", None)
    if arch_plan:
        return get_plan(arch_plan)
    return uniform_plan(arch.quant)


# ------------------------------------------------- scan-uniformity check ----
def block_leaf_sites(block_type: str, cfg) -> Tuple[str, ...]:
    """The quantizable leaf sites inside one block of the given type
    (relative to the block's ``block[<i>]`` prefix)."""
    ffn = ("ffn.w_in", "ffn.w_gate", "ffn.w_out")
    if block_type == "A":
        sites = ["attn.qkv", "attn.wo"]
        if cfg.family == "moe":
            sites.append("moe.experts")
            if cfg.shared_expert:
                sites += ["shared.w_in", "shared.w_gate", "shared.w_out"]
            if cfg.moe_dense_ff:
                sites += ["dense_ffn.w_in", "dense_ffn.w_gate",
                          "dense_ffn.w_out"]
        elif cfg.d_ff:
            sites += list(ffn)
        return tuple(sites)
    if block_type == "M":
        return ("mamba.in_proj", "mamba.out_proj")
    if block_type == "R":
        sites = ["lru.in_x", "lru.in_g", "lru.w_a", "lru.w_x", "lru.out"]
        if cfg.d_ff:
            sites += list(ffn)
        return tuple(sites)
    raise ValueError(block_type)


@functools.lru_cache(maxsize=1024)
def plan_repeat_uniform(plan: QuantPlan, cfg) -> bool:
    """True iff every scan repeat unit resolves to the same per-site configs
    as repeat 0 — the condition for keeping ``lax.scan`` over layers (one
    traced body for all repeats).  Resolved at trace time, outside the scan
    body, so the compiled graph stays static either way."""
    P = len(cfg.pattern)
    for j, bt in enumerate(cfg.pattern):
        for leaf in block_leaf_sites(bt, cfg):
            base = plan.resolve(f"block[{j}].{leaf}")
            for r in range(1, cfg.n_repeats):
                if plan.resolve(f"block[{r * P + j}].{leaf}") != base:
                    return False
    return True


# -------------------------------------------------- plan-aware packing ----
def _leaf_site(comps: Tuple[str, ...]) -> str:
    """Block-relative param path -> site leaf (wq/wk/wv unify to attn.qkv;
    expert stacks address as one <container>.experts site)."""
    if comps and comps[0] == "attn" and comps[-1] in ("wq", "wk", "wv"):
        return "attn.qkv"
    if "experts" in comps:
        return f"{comps[0]}.experts"
    return ".".join(comps)


def plan_pack_tree(params, cfg, plan: QuantPlan, *,
                   min_size: int = 1 << 12,
                   backends: frozenset = SERVE_PACKED,
                   scale_dtype=jnp.float32,
                   site_log: Optional[Dict[str, str]] = None):
    """Pack model weights into the int4 serving format *per resolved site*.

    Sites resolving to a backend outside ``backends`` (float, fake_quant,
    netlist, ...) keep their float masters.  With a repeat-uniform plan the
    stacked ``layers`` tree packs in place (scan-compatible); otherwise it
    splits into per-repeat subtrees ``{"r0": ..., "r1": ...}`` so different
    layers can carry different weight formats — the forward pass detects the
    split and unrolls.  ``scale_dtype=bfloat16`` is the quantized-checkpoint
    storage format (4x smaller artifacts; see checkpoint.save_quantized).

    ``site_log`` (optional dict, mutated in place) records which backend each
    *actually packed* site resolved to — the checkpoint manifest stores it so
    a restore can verify per-site that the serving plan rebuilds the same
    backend the nibbles were packed for (a ``lut4`` site silently served as
    nibble-unpack w4a4 would be a wrong-kernel bug, not just a perf bug)."""
    from .qlinear import PACKABLE_NAMES, pack_weight_nd

    def pack_leaf(leaf, site: str, *, check_name: Optional[str] = None):
        qc = plan.resolve(site)
        # expert stacks pack only for the pre-packing backends: live serving
        # of on-the-fly backends (int_sim/w4a16) runs experts from float
        # masters (models/moe.py dequantizes packed dicts but never
        # quantizes masters), so packing them into a checkpoint would change
        # the served math vs the same plan on masters
        site_backends = backends
        if site.endswith(".experts"):
            site_backends = backends & SERVE_PACKED
        packable = (
            qc.backend in site_backends
            and (check_name is None or check_name in PACKABLE_NAMES)
            and getattr(leaf, "ndim", 0) >= 2
            and leaf.size >= min_size
            and leaf.shape[-1] % 2 == 0
            and leaf.dtype in (jnp.float32, jnp.bfloat16)
        )
        if not packable:
            return leaf
        if site_log is not None:
            site_log[site] = qc.backend
        # grouped scales only exist for the weight-only backends (W4A4's
        # int32 accumulation runs over full K, so its scales are per-channel
        # by construction), and expert stacks dequantize per-channel in the
        # batched einsum (models/moe.py)
        if qc.backend not in ("w4a16", "w4a16_packed") \
                or site.endswith(".experts"):
            qc = dataclasses.replace(qc, group_size=0)
        packed = pack_weight_nd(leaf.astype(jnp.float32), qc)
        packed["scale"] = packed["scale"].astype(scale_dtype)
        return packed

    def pack_block(bp, prefix: str):
        def rec(node, comps):
            if isinstance(node, dict):
                return {k: rec(v, comps + (k,)) for k, v in node.items()}
            return pack_leaf(node, join_site(prefix, _leaf_site(comps)),
                             check_name=comps[-1])
        return rec(bp, ())

    P, R = len(cfg.pattern), cfg.n_repeats
    out = dict(params)
    layers = params["layers"]
    if plan_repeat_uniform(plan, cfg):
        out["layers"] = {
            f"u{j}": pack_block(layers[f"u{j}"], f"block[{j}]")
            for j in range(P)
        }
    else:
        out["layers"] = {
            f"r{r}": {
                f"u{j}": pack_block(
                    jax.tree.map(lambda a, r=r: a[r], layers[f"u{j}"]),
                    f"block[{r * P + j}]")
                for j in range(P)
            }
            for r in range(R)
        }
    for t in range(len(cfg.tail)):
        out[f"tail{t}"] = pack_block(params[f"tail{t}"], f"block[{R * P + t}]")
    if "lm_head" in params:
        out["lm_head"] = {
            "w": pack_leaf(params["lm_head"]["w"], "lm_head")}
    return out


def layers_per_repeat(params) -> bool:
    """True when ``params["layers"]`` was split per-repeat by a
    non-repeat-uniform plan (forward must unroll)."""
    layers = params.get("layers")
    return isinstance(layers, dict) and "r0" in layers


def pack_for_serving(params, cfg, rt):
    """Serving-side weight preparation under the active plan: pack the
    sites whose backend pre-packs (legacy ``w4a4_packed``/``w4a16_packed``)
    into the kernels' planar K-major layout.  No-op when the plan never
    pre-packs — int_sim/w4a16 sites quantize on the fly from masters unless
    they come from a quantized checkpoint (checkpoint.restore_quantized
    hands back already-packed trees)."""
    plan = active_plan(cfg, rt)
    if not (plan.backends & SERVE_PACKED):
        return params
    return plan_pack_tree(params, cfg, plan, backends=SERVE_PACKED)

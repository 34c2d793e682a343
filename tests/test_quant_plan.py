"""Per-site QuantPlan: pattern matching, plan-aware forward, the backend
registry, and the quantized checkpoint format."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_quantized, save_quantized
from repro.configs import Runtime, ServingConfig, get_config
from repro.core import backends as qbackends
from repro.core import quant_plan as qp
from repro.core.qlinear import QuantConfig, qdense
from repro.core.quant_plan import (
    CKPT_PACKED,
    QuantPlan,
    active_plan,
    get_plan,
    plan_pack_tree,
    plan_repeat_uniform,
)
from repro.models import forward, init_model
from repro.models.common import rms_norm
from repro.models.transformer import apply_block

CFG = get_config("qwen2-0.5b").reduced(n_layers=2)
RT_KW = dict(scan_layers=True, attn_impl="chunked", attn_chunk_q=8,
             loss_chunk=0, remat="none")

#: non-uniform reference plan: w4a16 FFNs, float lm_head + block-0
#: attention, int_sim elsewhere (the acceptance plan)
MIXED = "mixed_sensitive"


def _params():
    return init_model(jax.random.PRNGKey(0), CFG)


def _tokens(batch=2, seq=16):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                              CFG.vocab, dtype=jnp.int32)


def _tree_items(tree):
    return {
        tuple(str(getattr(k, "key", k)) for k in kp): leaf
        for kp, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def assert_trees_bit_equal(a, b):
    fa, fb = _tree_items(a), _tree_items(b)
    assert fa.keys() == fb.keys()
    for k, la in fa.items():
        lb = fb[k]
        assert la.dtype == lb.dtype, (k, la.dtype, lb.dtype)
        assert np.array_equal(np.asarray(la), np.asarray(lb)), k


# ------------------------------------------------------------- matching ----
def test_pattern_precedence():
    A = QuantConfig(backend="float")
    B = QuantConfig(backend="int_sim")
    C = QuantConfig(backend="w4a16")
    plan = QuantPlan(rules=(
        ("*", A), ("attn.*", B), ("block[0].attn.qkv", C)))
    # block[0].attn.qkv beats attn.* beats *
    assert plan.resolve("block[0].attn.qkv") == C
    assert plan.resolve("block[1].attn.qkv") == B          # suffix glob
    assert plan.resolve("block[1].ffn.w_in") == A
    assert plan.resolve("lm_head") == A
    # brackets are literal, not character classes
    assert not qp.pattern_matches("block[0].*", "block0.attn.qkv")
    assert qp.pattern_matches("block[0].*", "block[0].attn.qkv")
    # block[0].* is more specific than ffn.*
    plan2 = QuantPlan(rules=(("ffn.*", B), ("block[0].*", A)))
    assert plan2.resolve("block[0].ffn.w_in") == A
    assert plan2.resolve("block[1].ffn.w_in") == B


def test_plan_specs_and_json_roundtrip(tmp_path):
    plan = get_plan("block[0].*=float;ffn.*=w4a16/g32;*=int_sim")
    assert plan.resolve("block[0].ffn.w_in").backend == "float"
    assert plan.resolve("block[1].ffn.w_in") == QuantConfig(
        backend="w4a16", group_size=32)
    assert plan.resolve("block[1].attn.qkv").backend == "int_sim"

    d = qp.plan_to_dict(plan)
    assert qp.plan_from_dict(d) == plan
    path = tmp_path / "plan.json"
    path.write_text(json.dumps(d))
    assert get_plan(str(path)) == plan

    for name in qp.PRESETS:                   # every preset resolves
        p = get_plan(name)
        assert p.resolve("block[3].ffn.w_in").backend
    with pytest.raises(ValueError):
        get_plan("not_a_preset_or_file_or_rules")

    # a typo'd plan with no catch-all fails loudly instead of silently
    # serving float everywhere
    with pytest.raises(ValueError, match="catch-all"):
        get_plan("ffn=w4a16").resolve("block[0].ffn.w_in")

    # editing a plan file in a long-lived process takes effect (mtime key)
    path2 = tmp_path / "plan2.json"
    path2.write_text(json.dumps(qp.plan_to_dict(get_plan("*=int_sim"))))
    assert get_plan(str(path2)).resolve("x").backend == "int_sim"
    path2.write_text(json.dumps(qp.plan_to_dict(get_plan("*=float"))))
    os.utime(path2, ns=(1, 987654321))  # force a distinct mtime regardless
    assert get_plan(str(path2)).resolve("x").backend == "float"


def test_runtime_override_routes_through_plan():
    # deprecated backend-string override keeps working (uniform plan) and
    # no longer loses the arch's bits/group settings
    arch = CFG
    rt = Runtime(quant_backend="w4a16")
    qc = rt.quant_cfg(arch)
    assert qc.backend == "w4a16" and qc.w_bits == arch.quant.w_bits
    assert rt.quant_cfg(arch, "lm_head").backend == "float"
    # plan override wins over the backend string and is per-site
    rt2 = Runtime(quant_plan=MIXED, quant_backend="float")
    assert rt2.quant_cfg(arch, "block[0].attn.qkv").backend == "float"
    assert rt2.quant_cfg(arch, "block[1].attn.qkv").backend == "int_sim"
    assert rt2.quant_cfg(arch, "block[1].ffn.w_in").backend == "w4a16"


def test_backend_registry_extension():
    w = jnp.asarray(np.random.default_rng(0).standard_normal((16, 8)),
                    jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((4, 16)),
                    jnp.float32)

    @qbackends.register_backend("double_float")
    def _double(w_, x2, cfg, tag):
        return 2.0 * jnp.dot(x2, w_.astype(x2.dtype))

    try:
        y = qdense(w, x, QuantConfig(backend="double_float"))
        y_ref = qdense(w, x, QuantConfig(backend="float"))
        np.testing.assert_allclose(np.asarray(y), 2 * np.asarray(y_ref),
                                   rtol=1e-6)
    finally:
        del qbackends.BACKENDS["double_float"]
    with pytest.raises(ValueError, match="unknown quant backend"):
        qdense(w, x, QuantConfig(backend="no_such_backend"))


# ------------------------------------------------------------- forward ----
def test_uniform_plan_matches_legacy_backend():
    params, toks = _params(), _tokens()
    for backend in ("int_sim", "fake_quant"):
        rt_a = Runtime(quant_backend=backend, **RT_KW)
        rt_b = Runtime(quant_plan=f"*={backend};lm_head=float", **RT_KW)
        la = np.asarray(forward(params, toks, CFG, rt_a)[0], np.float32)
        lb = np.asarray(forward(params, toks, CFG, rt_b)[0], np.float32)
        assert np.array_equal(la, lb), backend


def test_mixed_plan_matches_manual_per_site_dispatch():
    """Forward under a per-layer plan == hand-rolled per-layer dispatch
    (layer 0 float, layer 1 int_sim) on a 2-layer model."""
    params, toks = _params(), _tokens()
    plan_spec = "block[0].*=float;*=int_sim;lm_head=float"
    rt_plan = Runtime(quant_plan=plan_spec, **RT_KW)
    assert not plan_repeat_uniform(active_plan(CFG, rt_plan), CFG)
    got = np.asarray(forward(params, toks, CFG, rt_plan)[0], np.float32)

    # manual reference: uniform-backend Runtime per layer
    B, S = toks.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["embed"]["tok"][toks].astype(jnp.bfloat16)
    layer_rts = [Runtime(quant_backend="float", **RT_KW),
                 Runtime(quant_backend="int_sim", **RT_KW)]
    for r, rt_r in enumerate(layer_rts):
        unit_p = jax.tree.map(lambda a: a[r], params["layers"])["u0"]
        x, _, _ = apply_block("A", unit_p, x, CFG, rt_r, positions)
    x = rms_norm(x, params["final_norm"], CFG.norm_eps)
    w = params["embed"]["tok"].astype(x.dtype)      # qwen2 ties embeddings
    ref = np.asarray(jnp.einsum("...d,vd->...v", x, w), np.float32)
    assert np.array_equal(got, ref)

    # scan-flag invariance: the non-uniform plan forces the unrolled loop
    rt_unroll = Runtime(quant_plan=plan_spec, **{**RT_KW,
                                                 "scan_layers": False})
    got2 = np.asarray(forward(params, toks, CFG, rt_unroll)[0], np.float32)
    assert np.array_equal(got, got2)


def test_grouped_w4a16_packing_keeps_group_numerics():
    """A w4a16/gN site packs with per-group scales, so a grouped plan keeps
    its numerics through a quantized checkpoint (packed == on-the-fly)."""
    import dataclasses

    from repro.core.qlinear import pack_weight_nd

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
    cfg = QuantConfig(backend="w4a16", group_size=128)
    packed = pack_weight_nd(w, cfg)
    assert packed["scale"].shape == (2, 1, 64)        # per-group, not [1, N]
    y_fly = np.asarray(qdense(w, x, cfg), np.float32)
    y_packed = np.asarray(
        qdense(packed, x, dataclasses.replace(cfg, backend="w4a16_packed")),
        np.float32)
    np.testing.assert_allclose(y_packed, y_fly, rtol=1e-6)
    # and a per-channel config still stores [1, N] scales
    assert pack_weight_nd(w, QuantConfig(backend="w4a16"))["scale"].shape \
        == (1, 64)


def test_group_size_must_divide_k_like_on_the_fly():
    """pack_weight_nd rejects non-dividing group sizes exactly like the
    on-the-fly group_quantize path — no silent per-channel fallback that
    would bake different numerics into a checkpoint than the plan names."""
    from repro.core.qlinear import pack_weight_nd

    w = jnp.ones((192, 16), jnp.float32)
    with pytest.raises(AssertionError):
        pack_weight_nd(w, QuantConfig(backend="w4a16", group_size=100))


def test_ckpt_experts_match_live_serving_semantics():
    """Expert stacks pack only for pre-packing backends: on-the-fly plans
    (int_sim) serve experts from float masters live, so the checkpoint
    must keep them float too."""
    from repro.configs import REGISTRY

    moe = next(c for c in sorted(REGISTRY.values(), key=lambda c: c.name)
               if c.n_experts).reduced()
    params = init_model(jax.random.PRNGKey(0), moe)
    for spec, packed_expected in (("*=int_sim;lm_head=float", False),
                                  ("serve_w4a4", True)):
        tree = plan_pack_tree(params, moe, get_plan(spec),
                              backends=CKPT_PACKED, min_size=1)
        blocks = (tree["layers"]["u0"] if "u0" in tree["layers"]
                  else tree["layers"]["r0"]["u0"])
        w_in = blocks["moe"]["experts"]["w_in"]
        assert isinstance(w_in, dict) == packed_expected, spec


def test_prepack_row_mult_covers_groups():
    """pack_weight_nd writes the planar K-major layout with K rounded up to
    whole scale groups per planar half (row_mult = 2G), for plain and
    layer-stacked weights alike; per-channel scales round K to even."""
    from repro.core.qlinear import pack_weight_nd
    from repro.kernels.packing import unpack_kmajor

    rng = np.random.default_rng(2)
    w2 = jnp.asarray(rng.standard_normal((96, 16)), jnp.float32)
    w3 = jnp.asarray(rng.standard_normal((2, 96, 16)), jnp.float32)
    g32 = QuantConfig(backend="w4a16", group_size=32)
    a = pack_weight_nd(w2, g32)                   # [3,1,16] scale
    b = pack_weight_nd(w3, g32)                   # [2,3,1,16]
    c = pack_weight_nd(w2, QuantConfig(backend="w4a16"))
    d = pack_weight_nd(w2[:95], QuantConfig(backend="w4a16"))
    assert a["scale"].shape == (3, 1, 16)
    assert b["scale"].shape == (2, 3, 1, 16)
    # K=96, G=32 -> K' rounded to 2G=64 -> 128 -> K'/2 = 64 planar rows
    assert a["packed"].shape == (64, 16) and a["packed"].dtype == jnp.uint8
    assert b["packed"].shape == (2, 64, 16)
    # per-channel: K'=96 (already even) -> 48 planar rows; odd K=95 -> 96
    assert c["packed"].shape == (48, 16)
    assert d["packed"].shape == (48, 16)
    # the padding rows are zero int4 values
    assert not np.asarray(unpack_kmajor(a["packed"])[96:]).any()
    assert not np.asarray(unpack_kmajor(d["packed"])[95:]).any()


def _master_of(params, path):
    """The float master a packed site came from: a per-repeat split tree
    (``layers/r{r}/...``) indexes repeat r of the stacked master."""
    r = None
    if path[0] == "layers" and path[1][0] == "r" and path[1][1:].isdigit():
        r, path = int(path[1][1:]), (path[0],) + path[2:]
    node = params
    for k in path:
        node = node[k]
    return node if r is None else node[r]


@pytest.mark.parametrize("group", [0, 32], ids=["per_channel", "g32"])
@pytest.mark.parametrize("stacked", [True, False], ids=["stacked", "plain"])
def test_serving_tree_holds_one_layout(monkeypatch, group, stacked):
    """On a Pallas backend the serving tree holds each packed weight once:
    exactly {"packed", "scale"} per site (no second layout beside it), and
    the packed bytes are K'·N/2 per site, K' rounded up to whole planar
    halves.  d_model 96 with G=32 makes K=96 sites pad to K'=128."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    cfg = get_config("qwen2-0.5b").reduced(n_layers=2, d_model=96)
    spec = "*=w4a16_packed" + (f"/g{group}" if group else "")
    if not stacked:                   # non-uniform: per-repeat 2-D weights
        spec = "block[0].*=float;" + spec
    params = init_model(jax.random.PRNGKey(0), cfg)
    tree = qp.pack_for_serving(params, cfg, Runtime(quant_plan=spec,
                                                    **RT_KW))
    assert ("r0" not in tree["layers"]) == stacked

    sites, got, want = 0, 0, 0
    for kp, node in jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda n: isinstance(n, dict) and "packed" in n)[0]:
        if not isinstance(node, dict):
            continue
        assert set(node) == {"packed", "scale"}, kp
        path = tuple(str(getattr(k, "key", k)) for k in kp)
        master = _master_of(params, path)
        K, N = master.shape[-2:]
        rm = 2 * group if group and group < K else 2
        Kp = -(-K // rm) * rm
        lead = master.shape[:-2]
        assert node["packed"].shape == (*lead, Kp // 2, N), path
        assert node["packed"].dtype == jnp.uint8
        assert node["packed"].ndim == (3 if stacked else 2)
        sites += 1
        got += node["packed"].nbytes
        want += int(np.prod(lead, dtype=np.int64)) * Kp * N // 2
    assert sites >= 4
    assert got == want


# ---------------------------------------------------------- checkpoints ----
def test_quantized_ckpt_roundtrip_bit_exact(tmp_path):
    params = _params()
    plan = get_plan(MIXED)
    # a stale partial save must be garbage-collected, not break anything
    os.makedirs(tmp_path / "step_00000000.tmp_dead")
    save_quantized(str(tmp_path), 0, params, CFG, plan=plan)
    assert latest_step(str(tmp_path)) == 0
    assert not any(".tmp_" in n for n in os.listdir(tmp_path))

    restored, manifest = restore_quantized(str(tmp_path))
    assert manifest["format"] == "quantized-v2"
    assert qp.plan_from_dict(manifest["plan"]) == plan

    # the optional plan guard: a Runtime whose active plan differs from the
    # stored one must be rejected (mismatched backends would serve wrong
    # math silently), the matching one accepted
    restore_quantized(str(tmp_path), cfg=CFG,
                      rt=Runtime(quant_plan=MIXED, **RT_KW))
    with pytest.raises(AssertionError, match="does not match"):
        restore_quantized(str(tmp_path), cfg=CFG,
                          rt=Runtime(quant_backend="w4a4_packed", **RT_KW))

    ref = plan_pack_tree(params, CFG, plan, backends=CKPT_PACKED,
                         scale_dtype=jnp.bfloat16)
    assert_trees_bit_equal(restored, ref)
    # the format actually is packed: uint8 nibbles + bf16 scales present
    dtypes = {leaf.dtype.name for leaf in jax.tree_util.tree_leaves(restored)}
    assert "uint8" in dtypes and "bfloat16" in dtypes
    # non-uniform plan => per-repeat weight trees (block 0 float attention)
    assert set(restored["layers"]) == {"r0", "r1"}
    assert restored["layers"]["r0"]["u0"]["attn"]["wq"].dtype == jnp.float32
    assert restored["layers"]["r1"]["u0"]["attn"]["wq"]["packed"].dtype \
        == jnp.uint8


def test_restore_refuses_v1_checkpoint(tmp_path):
    """A quantized-v1 manifest holds the retired N-major layout: restoring
    it is refused loudly, naming the way out (re-pack from masters)."""
    save_quantized(str(tmp_path), 0, _params(), CFG,
                   plan=get_plan("*=w4a16_packed"))
    mpath = tmp_path / "step_00000000" / "manifest.json"
    manifest = json.loads(mpath.read_text())
    assert manifest["format"] == "quantized-v2"
    manifest["format"] = "quantized-v1"
    mpath.write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="N-major.*save_quantized"):
        restore_quantized(str(tmp_path))


def test_quantized_ckpt_serves_bit_identical(tmp_path):
    """Acceptance: a non-uniform plan serves from a quantized checkpoint
    with bit-identical logits and generated tokens vs the same plan applied
    to float masters."""
    params = _params()
    rt = Runtime(quant_plan=MIXED, **RT_KW)
    save_quantized(str(tmp_path), 0, params, CFG, rt=rt)
    restored, _ = restore_quantized(str(tmp_path))
    ref = plan_pack_tree(params, CFG, get_plan(MIXED), backends=CKPT_PACKED,
                         scale_dtype=jnp.bfloat16)

    toks = _tokens(1, 8)
    la = np.asarray(forward(restored, toks, CFG, rt)[0], np.float32)
    lb = np.asarray(forward(ref, toks, CFG, rt)[0], np.float32)
    assert np.array_equal(la, lb)

    # end-to-end through the continuous-batching engine
    from repro.serving.engine import InferenceEngine

    sv = ServingConfig(layout="paged", max_batch=2, page_size=8,
                       num_pages=16, max_ctx=32)
    outs = []
    for p in (restored, ref):
        eng = InferenceEngine(CFG, rt, sv, params=p)
        for prompt in ([3, 1, 4, 1, 5], [9, 2, 6]):
            eng.submit(prompt, max_new=4)
        eng.run_until_idle()
        outs.append([r.tokens for r in sorted(eng.collect(),
                                              key=lambda r: r.rid)])
    assert outs[0] == outs[1]

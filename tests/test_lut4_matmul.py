"""Table-lookup W4A4 GEMM (`kernels/lut4_matmul.py`) and the `lut4` backend:
kernel vs XLA-twin bitwise parity, plan/serving token identity vs the int4
backend, quantized-checkpoint round-trip with lut4 sites, autotune tags."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import restore_quantized, save_quantized
from repro.configs import Runtime, ServingConfig, get_config
from repro.core.qlinear import QuantConfig, qdense
from repro.core.quant_plan import CKPT_PACKED, get_plan, plan_pack_tree
from repro.kernels import autotune, ops, ref
from repro.kernels.lut4_matmul import lut4_matmul
from repro.kernels.packing import (
    nibble_product_tables,
    pack_kmajor,
    table_take,
)
from repro.models import forward, init_model

CFG = get_config("qwen2-0.5b").reduced(n_layers=2)
RT_KW = dict(scan_layers=True, attn_impl="chunked", attn_chunk_q=8,
             loss_chunk=0, remat="none")


def _rand_case(M, K, N, seed=0):
    rng = np.random.default_rng(seed)
    a_q = jnp.asarray(rng.integers(-8, 8, (M, K)), jnp.int8)
    w_q = jnp.asarray(rng.integers(-8, 8, (K, N)), jnp.int8)
    a_s = jnp.asarray(rng.uniform(0.01, 1.0, (M, 1)), jnp.float32)
    w_s = jnp.asarray(rng.uniform(0.01, 1.0, (1, N)), jnp.float32)
    return a_q, a_s, pack_kmajor(w_q), w_s


# --------------------------------------------------------------- tables ----
def test_nibble_product_tables_exact():
    t_lo, t_hi = nibble_product_tables()
    assert t_lo.shape == t_hi.shape == (16, 256)
    assert t_lo.dtype == t_hi.dtype == np.int8
    sext = lambda v: (v ^ 8) - 8
    for a in range(16):
        for b in range(0, 256, 7):          # stride keeps the loop cheap
            assert t_lo[a, b] == sext(a) * sext(b & 0xF)
            assert t_hi[a, b] == sext(a) * sext(b >> 4)


def test_make_product_lut_is_view_of_gemm_tables():
    """ref.make_product_lut deduped into the table builder: same 256
    entries the elementwise kernels always used."""
    lut = ref.make_product_lut()
    sext = lambda v: (v ^ 8) - 8
    for a in range(16):
        for b in range(16):
            assert lut[(a << 4) | b] == sext(a) * sext(b)


def test_table_take_semantics():
    table = jnp.asarray(np.arange(32, dtype=np.int32).reshape(4, 8))
    rows = jnp.asarray([2, 0])
    lanes = jnp.asarray([[1, 7], [0, 3]])
    got = np.asarray(table_take(table, rows, lanes))
    assert got.tolist() == [[17, 23], [0, 3]]


# --------------------------------------------------------------- parity ----
ODD_SHAPES = [(1, 2, 2), (3, 5, 2), (7, 13, 10), (33, 57, 34),
              (8, 512, 512), (129, 511, 130)]


@pytest.mark.parametrize("M,K,N", ODD_SHAPES)
def test_table_oracle_bitwise_equals_int_dot(M, K, N):
    """The rank-1 identity that makes the XLA twin legitimate: every
    partial product read from the tables == the int8 dot, bit for bit."""
    a_q, a_s, wp, w_s = _rand_case(M, K, N, seed=M * 1000 + N)
    want = np.asarray(ref.int4_matmul_ref(a_q, a_s, wp, w_s))
    got = np.asarray(ref.lut4_matmul_ref(a_q, a_s, wp, w_s))
    assert np.array_equal(want, got)


@pytest.mark.parametrize("M,K,N", ODD_SHAPES)
def test_kernel_bitwise_parity_odd_shapes(M, K, N):
    a_q, a_s, wp, w_s = _rand_case(M, K, N, seed=M + K + N)
    want = np.asarray(ref.int4_matmul_ref(a_q, a_s, wp, w_s))
    got = np.asarray(lut4_matmul(a_q, a_s, wp, w_s,
                                 bm=32, bn=32, bk=16, interpret=True))
    assert np.array_equal(want, got)


@pytest.mark.parametrize("blocks", [dict(bm=8, bn=128, bk=2),
                                    dict(bm=32, bn=256, bk=64),
                                    dict(bm=128, bn=128, bk=256)])
def test_kernel_parity_across_block_shapes(blocks):
    a_q, a_s, wp, w_s = _rand_case(48, 96, 160, seed=9)
    want = np.asarray(ref.int4_matmul_ref(a_q, a_s, wp, w_s))
    got = np.asarray(lut4_matmul(a_q, a_s, wp, w_s,
                                 interpret=True, **blocks))
    assert np.array_equal(want, got)


def test_ops_dispatch_modes(monkeypatch):
    """interpret / XLA-twin dispatch of the one entry point agree bitwise,
    including the env-var override."""
    a_q, a_s, wp, w_s = _rand_case(17, 33, 26, seed=3)
    want = np.asarray(ref.int4_matmul_ref(a_q, a_s, wp, w_s))

    def call(**kw):
        return np.asarray(ops.lut4_matmul(a_q, a_s, wp, w_s, **kw))

    assert np.array_equal(want, call(interpret=True))
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert np.array_equal(want, call())
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert np.array_equal(want, call())               # XLA twin on CPU


# ------------------------------------------------------- plan / serving ----
def test_lut4_backend_matches_int_sim_bitwise():
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.standard_normal((96, 64)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 5, 96)), jnp.float32)
    ya = qdense(w, x, QuantConfig(backend="int_sim"))
    yb = qdense(w, x, QuantConfig(backend="lut4"))
    assert np.array_equal(np.asarray(ya), np.asarray(yb))


@pytest.mark.parametrize("g", [0, 32])
def test_lut4_group_sizes_coerce_per_channel(g):
    """`pat=lut4/gN` parses, and packing coerces to per-channel scales (the
    int32 accumulation runs over full K, like the other W4A4 backends)."""
    plan = get_plan(f"ffn.*=lut4/g{g};*=int_sim" if g else
                    "ffn.*=lut4;*=int_sim")
    qc = plan.resolve("block[0].ffn.w_in")
    assert qc.backend == "lut4" and qc.group_size == g
    params = init_model(jax.random.PRNGKey(0), CFG)
    packed = plan_pack_tree(params, CFG, plan, backends=CKPT_PACKED)
    layers = packed["layers"]           # repeat-uniform plans keep "u0"
    ff = (layers["u0"] if "u0" in layers else layers["r0"]["u0"]
          )["ffn"]["w_in"]
    assert ff["packed"].dtype == jnp.uint8
    # per-channel scales: no group axis ([..., 1, N], same rank as packed)
    assert ff["scale"].ndim == ff["packed"].ndim
    assert ff["scale"].shape[-2] == 1
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, CFG.vocab,
                              dtype=jnp.int32)
    rt = Runtime(quant_plan=f"ffn.*=lut4/g{g};*=int_sim" if g else
                 "ffn.*=lut4;*=int_sim", **RT_KW)
    out = forward(packed, toks, CFG, rt)[0]
    assert np.all(np.isfinite(np.asarray(out, np.float32)))


def test_lut4_uniform_plan_token_identity_vs_int4():
    """Engine acceptance: a uniform lut4 plan generates the exact token
    stream of the existing int4 backend (identical integer math)."""
    from repro.serving.engine import InferenceEngine

    params = init_model(jax.random.PRNGKey(0), CFG)
    sv = ServingConfig(layout="paged", max_batch=2, page_size=8,
                       num_pages=16, max_ctx=32)
    outs = []
    for spec in ("*=lut4;lm_head=float", "*=int_sim;lm_head=float"):
        eng = InferenceEngine(CFG, Runtime(quant_plan=spec, **RT_KW), sv,
                              params=params)
        for prompt in ([3, 1, 4, 1, 5], [9, 2, 6]):
            eng.submit(prompt, max_new=4)
        eng.run_until_idle()
        outs.append([r.tokens for r in sorted(eng.collect(),
                                              key=lambda r: r.rid)])
    assert outs[0] == outs[1]


# ---------------------------------------------------------- checkpoints ----
MIXED_LUT4 = "ffn.*=lut4;attn.*=int_sim;lm_head=float;*=w4a16"


def test_quantized_ckpt_roundtrip_mixed_lut4(tmp_path):
    params = init_model(jax.random.PRNGKey(0), CFG)
    rt = Runtime(quant_plan=MIXED_LUT4, **RT_KW)
    save_quantized(str(tmp_path), 0, params, CFG, rt=rt)
    restored, manifest = restore_quantized(str(tmp_path), cfg=CFG, rt=rt)

    # the manifest records which backend each packed site was laid out for
    sb = manifest["site_backends"]
    assert sb.get("block[0].ffn.w_in") == "lut4"
    assert sb.get("block[0].attn.qkv") == "int_sim"
    assert "lm_head" not in sb                    # float site stays a master

    ref_tree = plan_pack_tree(params, CFG, get_plan(MIXED_LUT4),
                              backends=CKPT_PACKED,
                              scale_dtype=jnp.bfloat16)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, CFG.vocab,
                              dtype=jnp.int32)
    la = np.asarray(forward(restored, toks, CFG, rt)[0], np.float32)
    lb = np.asarray(forward(ref_tree, toks, CFG, rt)[0], np.float32)
    assert np.array_equal(la, lb)

    # restoring under a plan that resolves a lut4 site to another backend
    # must fail per-site, not silently serve nibble-unpack w4a4
    with pytest.raises(AssertionError, match="lut4"):
        restore_quantized(
            str(tmp_path), cfg=CFG,
            rt=Runtime(quant_plan="ffn.*=int_sim;attn.*=int_sim;"
                       "lm_head=float;*=w4a16", **RT_KW))


def test_packed_weight_unknown_backend_is_loud():
    """A packed dict reaching a backend with no packed path raises instead
    of silently dropping into the w4a16 dequant branch (wrong math)."""
    from repro.core.qlinear import pack_weight_nd

    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 64)), jnp.float32)
    pw = pack_weight_nd(w, QuantConfig(backend="lut4", group_size=0))
    with pytest.raises(ValueError, match="no packed-weight path"):
        qdense(pw, x, QuantConfig(backend="netlist"))


# -------------------------------------------------------------- autotune ----
@pytest.fixture
def isolated_cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.ENV_CACHE_PATH, str(path))
    autotune.reset()
    yield path
    autotune.reset()


def test_lut4_blocks_constraints_and_candidates():
    for (M, K, N) in [(1, 512, 512), (8, 512, 512), (256, 511, 130)]:
        b = autotune.lut4_default_blocks(M, K, N)
        assert b["bk"] % 2 == 0 and b["bm"] >= 8 and b["bn"] >= 128
        cands = autotune.lut4_candidate_blocks(M, K, N)
        assert b in cands
        assert len({tuple(sorted(c.items())) for c in cands}) == len(cands)
        for c in cands:
            assert c["bk"] % 2 == 0
    assert autotune.get_blocks("gemm.lut4", 8, 512, 512, "int8") \
        == autotune.lut4_default_blocks(8, 512, 512)


def test_lut4_autotune_tag_roundtrip(isolated_cache):
    """tune() under op gemm.lut4 with a site tag persists, and the exact
    key ops.lut4_matmul looks up wins over the untagged default."""
    cands = autotune.lut4_candidate_blocks(8, 512, 512)
    target = cands[-1]

    def fake_timer(fn):
        return 1.0 if fn() == target else 100.0

    tag = "block[0].ffn.w_in"
    best, us = autotune.tune("gemm.lut4", lambda b: (lambda b=b: b),
                             8, 512, 512, "int8", tag=tag, timer=fake_timer)
    assert best == target and us == 1.0
    assert autotune.get_blocks("gemm.lut4", 8, 512, 512, "int8", tag=tag) \
        == target
    # untagged lookup seeded from the tagged search (setdefault)
    assert autotune.get_blocks("gemm.lut4", 8, 512, 512, "int8") == target
    # fresh process state reads the persisted entry back
    autotune.reset()
    assert autotune.get_blocks("gemm.lut4", 8, 512, 512, "int8", tag=tag) \
        == target

"""Per-kernel validation: shape/dtype sweeps + allclose against ref.py oracles
(interpret mode executes the kernel bodies on CPU; `interpret=None` rows also
check the ops-level dispatch, which routes to the XLA twins off-TPU)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.quant import group_quantize, pack_int4, unpack_int4
from repro.kernels import ops, packing, ref


RNG = np.random.default_rng(1234)


def rand_int4(shape):
    return jnp.asarray(RNG.integers(-8, 8, size=shape, dtype=np.int8))


def pack_grouped(qg, sg, G):
    """Planar K-major weight for `sg`: whole groups per planar half when
    the scales are grouped [K/G, 1, N], even K when per-channel [1, N]."""
    return packing.pack_kmajor(qg, row_mult=2 * G if sg.ndim == 3 else 2)


# ---------------------------------------------------------------- lut_mul4 --
@pytest.mark.parametrize("shape", [(16,), (5, 33), (2, 3, 130), (1, 1, 1, 257)])
@pytest.mark.parametrize("strategy", ["onehot", "take"])
def test_lut_mul4_sweep(shape, strategy):
    a, b = rand_int4(shape), rand_int4(shape)
    got = ops.mul4(a, b, strategy=strategy, interpret=True)
    exp = ref.mul4_ref(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_lut_mul4_exhaustive_all_pairs():
    """All 256 signed int4 pairs through the Pallas LUT kernel (paper §V)."""
    vals = np.arange(-8, 8, dtype=np.int8)
    a = jnp.asarray(np.repeat(vals, 16))
    b = jnp.asarray(np.tile(vals, 16))
    got = ops.mul4(a, b, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), (np.repeat(vals, 16).astype(np.int32)
                          * np.tile(vals, 16).astype(np.int32)).astype(np.int8)
    )


def test_lut_kernel_matches_fpga_netlist():
    """Cross-validate the TPU LUT kernel against the bit-exact FPGA netlist."""
    from repro.core import build_proposed_mult4
    from repro.core.quant import to_unsigned_mag

    nl = build_proposed_mult4()
    q_a, q_b = rand_int4((64,)), rand_int4((64,))
    mag_a, sign_a = to_unsigned_mag(q_a)
    mag_b, sign_b = to_unsigned_mag(q_b)
    netlist_prod = nl(mag_a, mag_b).astype(jnp.int32) * sign_a * sign_b
    kernel_prod = ops.mul4(q_a, q_b, interpret=True).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(netlist_prod), np.asarray(kernel_prod))


# ------------------------------------------------------------- int4_matmul --
def _int4_case(M, K, N):
    aq = rand_int4((M, K))
    a_scale = jnp.asarray(RNG.random((M, 1), dtype=np.float32) + 0.05)
    wq = rand_int4((K, N if N % 2 == 0 else N + 1))
    w_scale = jnp.asarray(RNG.random((1, wq.shape[1]), dtype=np.float32) + 0.05)
    return aq, a_scale, packing.pack_kmajor(wq), w_scale


@pytest.mark.parametrize(
    "M,K,N", [(8, 64, 16), (128, 128, 128), (200, 384, 250), (1, 512, 1024)]
)
def test_int4_matmul_sweep(M, K, N):
    aq, a_scale, wp, w_scale = _int4_case(M, K, N)
    got = ops.int4_matmul(aq, a_scale, wp, w_scale, interpret=True,
                          bm=128, bn=128, bk=128)
    exp = ref.int4_matmul_ref(aq, a_scale, wp, w_scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("M,K,N", [(33, 70, 50), (7, 71, 130), (5, 9, 24)])
@pytest.mark.parametrize("blocks", [{}, dict(bm=32, bn=64, bk=64),
                                    dict(bm=8, bn=128, bk=256)])
def test_int4_matmul_odd_shapes_nondefault_blocks(M, K, N, blocks):
    """Odd (unpadded) M/K/N — including odd K, which the planar layout pads
    to even — across non-default tile shapes."""
    aq, a_scale, wp, w_scale = _int4_case(M, K, N)
    got = ops.int4_matmul(aq, a_scale, wp, w_scale, interpret=True, **blocks)
    exp = ref.int4_matmul_ref(aq, a_scale, wp, w_scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-6, atol=1e-6)


def test_int4_matmul_integer_core_is_exact():
    """With unit scales the kernel must be bit-exact integer arithmetic."""
    M = K = N = 128
    aq, wq = rand_int4((M, K)), rand_int4((K, N))
    ones_m, ones_n = jnp.ones((M, 1), jnp.float32), jnp.ones((1, N), jnp.float32)
    got = ops.int4_matmul(aq, ones_m, packing.pack_kmajor(wq), ones_n,
                          interpret=True)
    exp = jnp.dot(aq.astype(jnp.int32), wq.astype(jnp.int32))
    np.testing.assert_array_equal(np.asarray(got).astype(np.int64),
                                  np.asarray(exp).astype(np.int64))


@pytest.mark.parametrize("M,K,N", [(5, 64, 48), (33, 70, 50), (1, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int4_matmul_fused_quantize(M, K, N, dtype):
    """The in-kernel activation quantize must match quantize-then-matmul.

    Exact .5 ties in x/scale may round one LSB apart between the fused
    kernel and the eager oracle (fast-math reciprocal across the tie — see
    _quantize_tile); each flipped tie moves an output element by at most
    |w| <= 8 weight counts, so rows with ties get a correspondingly wider
    (still tight) bound while tie-free rows must agree to float noise."""
    x = jnp.asarray(RNG.standard_normal((M, K)).astype(np.float32)).astype(dtype)
    wq = rand_int4((K, N + N % 2))
    w_scale = jnp.asarray(RNG.random((1, wq.shape[1]), dtype=np.float32) + 0.05)
    wp = packing.pack_kmajor(wq)
    got = np.asarray(ops.int4_matmul_fused(x, wp, w_scale, interpret=True))
    exp = np.asarray(ref.int4_matmul_fused_ref(x, wp, w_scale))

    x32 = np.asarray(x, np.float32)
    a_scale = np.maximum(np.abs(x32).max(axis=1, keepdims=True), 1e-8) / 7.0
    ratio = x32 / a_scale
    ties = (np.abs(ratio - np.round(ratio)) == 0.5).sum(axis=1)   # per row
    tol = np.abs(exp) * 1e-5 + 1e-5 \
        + (ties * 8.0 * a_scale[:, 0] * float(w_scale.max()))[:, None]
    assert (np.abs(got - exp) <= tol).all(), \
        f"max err {np.abs(got - exp).max()} vs tol {tol.min()}"


# ------------------------------------------------------------ w4a16_matmul --
@pytest.mark.parametrize("M,K,N,G", [(32, 256, 64, 64), (100, 512, 130, 128),
                                     (1, 1024, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_w4a16_sweep(M, K, N, G, dtype):
    w = jnp.asarray(RNG.standard_normal((K, N + N % 2)).astype(np.float32))
    qg, sg = group_quantize(w, G)
    wp = pack_grouped(qg, sg, G)
    x = jnp.asarray(RNG.standard_normal((M, K)).astype(np.float32)).astype(dtype)
    got = ops.w4a16_matmul(x, wp, sg, G, interpret=True, bm=128, bn=128, bk=128)
    exp = ref.w4a16_matmul_ref(x, wp, sg, G)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=tol, atol=tol)


@pytest.mark.parametrize("M,K,N", [(9, 130, 50), (1, 77, 24)])
@pytest.mark.parametrize("blocks", [{}, dict(bm=16, bn=32, bk=64)])
def test_w4a16_per_channel_odd_shapes(M, K, N, blocks):
    """group_size >= K collapses to per-channel 2D scales (the epilogue-only
    kernel); odd K exercises the planar padding."""
    w = jnp.asarray(RNG.standard_normal((K, N + N % 2)).astype(np.float32))
    qg, sg = group_quantize(w, K)            # per-channel: scale [1, N]
    assert sg.ndim == 2
    wp = pack_grouped(qg, sg, K)
    x = jnp.asarray(RNG.standard_normal((M, K)).astype(np.float32))
    got = ops.w4a16_matmul(x, wp, sg, K, interpret=True, **blocks)
    exp = ref.w4a16_matmul_ref(x, wp, sg, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-4, atol=1e-4)


def test_w4a16_grouped_odd_group_count():
    """K = 3 groups: the planar halves can't split the groups evenly, so the
    repack pads K to a 2*G multiple; results must still match the oracle."""
    M, K, N, G = (16, 192, 32, 64)
    w = jnp.asarray(RNG.standard_normal((K, N)).astype(np.float32))
    qg, sg = group_quantize(w, G)
    assert sg.shape[0] == 3
    wp = pack_grouped(qg, sg, G)                # K 192 -> 256: 2 x 2 groups
    assert wp.shape == (128, N)
    x = jnp.asarray(RNG.standard_normal((M, K)).astype(np.float32))
    for blocks in ({}, dict(bm=16, bn=32, bk=128)):
        got = ops.w4a16_matmul(x, wp, sg, G, interpret=True, **blocks)
        exp = ref.w4a16_matmul_ref(x, wp, sg, G)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("G", [64, 0])
def test_w4a16_both_group_size_paths_nondefault_blocks(G):
    """Grouped [K/G,1,N] vs per-channel [1,N] scale paths, same weights."""
    M, K, N = 24, 256, 96
    w = jnp.asarray(RNG.standard_normal((K, N)).astype(np.float32))
    g = G if G else K
    qg, sg = group_quantize(w, g)
    assert sg.ndim == (3 if G else 2)
    wp = pack_grouped(qg, sg, g)
    x = jnp.asarray(RNG.standard_normal((M, K)).astype(np.float32))
    got = ops.w4a16_matmul(x, wp, sg, g, interpret=True, bm=32, bn=32, bk=256)
    exp = ref.w4a16_matmul_ref(x, wp, sg, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- ops dispatch --
def test_ops_dispatch_xla_twin_matches_kernels(monkeypatch):
    """Off-TPU, interpret=None dispatches to the XLA twins — same math as
    the interpreted kernels, full XLA speed (the serving path on CPU)."""
    import jax

    if jax.default_backend() == "tpu":
        pytest.skip("dispatch test targets non-TPU hosts")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert not ops.use_pallas()
    aq, a_scale, wp, w_scale = _int4_case(16, 64, 32)
    np.testing.assert_allclose(
        np.asarray(ops.int4_matmul(aq, a_scale, wp, w_scale)),
        np.asarray(ops.int4_matmul(aq, a_scale, wp, w_scale, interpret=True)),
        rtol=1e-6)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.use_pallas()


# --------------------------------------------------------------- packing ----
@pytest.mark.parametrize("axis", [0, 1, -1])
def test_pack_roundtrip(axis):
    q = rand_int4((48, 64))
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(pack_int4(q, axis), axis)), np.asarray(q)
    )


@pytest.mark.parametrize("K", [48, 37])
def test_kmajor_roundtrip(K):
    q = rand_int4((K, 32))
    km = packing.pack_kmajor(q)
    assert km.shape == ((K + 1) // 2, 32) and km.dtype == jnp.uint8
    back = packing.unpack_kmajor(km)[:K]
    np.testing.assert_array_equal(np.asarray(back), np.asarray(q))


def test_kmajor_row_mult_alignment():
    q = rand_int4((96, 16))
    km = packing.pack_kmajor(q, row_mult=64)           # K -> 128, halves of 64
    assert km.shape == (64, 16)
    back = packing.unpack_kmajor(km)
    np.testing.assert_array_equal(np.asarray(back[:96]), np.asarray(q))
    assert not np.asarray(back[96:]).any()             # zero int4 padding

"""Work functions against hand counts."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from work import flash_prefill, model, paged_decode_attention, w4a16_matmul  # noqa: E402,E501

QWEN2 = {"n_layers": 24, "d_model": 896, "n_heads": 14, "n_kv_heads": 2,
         "head_dim": 64, "d_ff": 4864, "vocab": 151936}


def test_gemm_by_hand():
    # x [64, 896] bf16 @ int4 W [896, 4864] + f32 scales -> y [64, 4864] bf16
    flops, nbytes = w4a16_matmul.work(64, 896, 4864)
    assert flops == 2 * 64 * 896 * 4864 == 557842432
    assert nbytes == 896 * 4864 // 2 + 4864 * 4 + 64 * 896 * 2 + 64 * 4864 * 2
    assert nbytes == 2179072 + 19456 + 114688 + 622592
    assert len(list(w4a16_matmul.calls(QWEN2, 8))) == 24 * 7


def test_decode_attention_by_hand():
    # two live rows of 100 and 300 keys, batch bucket 4, 14 q / 2 kv heads
    flops, nbytes = paged_decode_attention.work(QWEN2, [100, 300], 4)
    assert flops == 4 * 14 * 64 * 400
    assert nbytes == 400 * 2 * 64 * 2 * 2 + 4 * 14 * 64 * 2 * 2
    assert len(list(paged_decode_attention.calls(QWEN2, 4, [1, 2]))) == 24


def test_flash_prefill_by_hand():
    flops, nbytes = flash_prefill.work(QWEN2, 1024)
    assert flops == 4 * 14 * 64 * 1024 * 1025 / 2
    assert nbytes == 1024 * 64 * (2 * 14 + 2 * 2) * 2


def test_model_counts():
    lin = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert sum(k * n for k, n in model.linears(QWEN2)) == lin
    assert model.kv_bytes_per_token(QWEN2) == 12288
    assert model.weight_bytes(QWEN2) == 24 * (lin // 2 + 4 * (
        896 + 128 + 128 + 896 + 4864 * 2 + 896)) + 151936 * 896 * 2
    assert model.decode_flops(QWEN2, 10) == (
        24 * (2 * lin + 4 * 14 * 64 * 10) + 2 * 896 * 151936)
    assert model.prefill_flops(QWEN2, 3) == (
        24 * (2 * lin * 3 + 4 * 14 * 64 * 6) + 2 * 896 * 151936)
    assert model.decode_step_bytes(QWEN2, [5, 7]) == (
        model.weight_bytes(QWEN2) + 12 * 12288)

"""A whole run of a qwen3-shaped cell on the CPU at a tiny size: the
mathematics of the qwen3-4b configuration (per-head RMSNorm on queries and
keys before RoPE, no QKV bias, an attention width of twice the hidden
size, a tied head) through the served path and against the plain
reference.

The tiny cell is the registry's ``qwen3-4b`` at two layers, d 128, 4/2
heads, head dim 64 (so H·hd = 2·d), d_ff 256, vocab 512, served under the
cell's plan, engine and harness.  Its limit on the mean gap of the served
tokens, 0.0015, sits between what sound runs read on the CPU (0 to
0.00044 over eight seeds, 77 to 97 tokens each) and what the fp8 control
reads in the program's place over the same positions (0.0050 to 0.0172
over the same seeds).  A program that skips the key norm must read
incorrect."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run_cell  # noqa: E402

run_cell.setup_paths()

TINY_MODEL = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 64, "d_ff": 256, "vocab": 512, "qk_norm": True,
              "qkv_bias": False, "tie_embeddings": True,
              "rope_theta": 1000000.0, "norm_eps": 1e-06}
LIMIT = 0.0015


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    root = tmp_path_factory.mktemp("qk_norm_cell")
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "tiny-qwen3.json").write_text(json.dumps({
        "name": "tiny-qwen3", "registry": "qwen3-4b",
        "changed": {k: TINY_MODEL[k] for k in (
            "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab")},
        "model": TINY_MODEL, "quant": "w4a16_packed",
        "check": {"gap_mean": LIMIT}}))
    mix = {"loop": "open", "rate_rps": 8.0,
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                      "min": 8, "max": 64},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                      "min": 2, "max": 16},
           "serving": {"max_batch": 4, "max_ctx": 128, "page_size": 16},
           "trace_seconds": 1,
           "check": {"served_tokens": 96, "max_requests": 12}}
    (root / "bench" / "traffic" / "tiny-chat.json").write_text(
        json.dumps(mix))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-qwen3", "source": "test",
                         "file": "tiny-qwen3.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny-qwen3.chat", "config": "tiny-qwen3",
         "traffic": "tiny-chat", "chips": 1, "why": "test"}]
    for group in (bench["end_to_end"], bench["per_layer"]):
        for e in group:
            if "workloads" in e:
                e["workloads"] = ["tiny-qwen3.chat"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root / "BENCHMARK.json"


def _run(bench_file, seed):
    return run_cell.main(["--workload", "tiny-qwen3.chat", "--seed",
                          str(seed), "--seconds", "2"],
                         require_tpu=False, bench_file=bench_file)


def test_registry_is_the_published_shape(bench_file):
    from harness import spec

    cfg = spec.arch_config(spec.load_cell("tiny-qwen3.chat",
                                          bench_file).config)
    assert cfg.qk_norm and not cfg.qkv_bias and cfg.tie_embeddings
    assert cfg.n_heads * cfg.hd == 2 * cfg.d_model


def test_sound_run_is_correct(bench_file):
    out = _run(bench_file, seed=2**31 + 11)
    assert out["correct"] and out["failed"] == 0
    assert out["checked_tokens"] > 0
    assert out["checks"]["gap_mean"]["value"] <= LIMIT


def test_skipped_key_norm_is_not_correct(bench_file, monkeypatch):
    """The program leaves the keys unnormalized (the query norm stays):
    the reference, which applies both, must tell."""
    from repro.models import attention

    real = attention.rms_norm

    def norm(x, w, eps):
        # apply_attention norms q [B, S, H, hd] and k [B, S, KV, hd] only
        if x.shape[-2] == TINY_MODEL["n_kv_heads"]:
            return x
        return real(x, w, eps)

    monkeypatch.setattr(attention, "rms_norm", norm)
    out = _run(bench_file, seed=5)
    assert not out["correct"]
    assert out["checks"]["gap_mean"]["value"] > LIMIT

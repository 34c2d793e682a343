"""A whole run of a cell, on the CPU at a tiny size: the harness's look
for a chip is skipped (``require_tpu=False``) and everything else runs.

The tiny cell is a two-layer qwen2-shaped model (d 128, 4/2 heads, head
dim 32, d_ff 256, vocab 512) served under the same plan, engine and
harness as the real cells.  Its limit on the mean gap of the served
tokens, 0.0015, sits between what sound runs read on the CPU (0.0001 to
0.0004 over eight seeds, 80 to 99 tokens each) and what the fp8 control
reads in the program's place over the same positions (0.0052 to 0.0196
over the same seeds)."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run_cell  # noqa: E402

run_cell.setup_paths()

TINY_MODEL = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 32, "d_ff": 256, "vocab": 512, "qk_norm": False,
              "qkv_bias": True, "tie_embeddings": True,
              "rope_theta": 1000000.0, "norm_eps": 1e-06}
LIMIT = 0.0015


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    root = tmp_path_factory.mktemp("cell")
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "tiny.json").write_text(json.dumps({
        "name": "tiny", "registry": "qwen2-0.5b",
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
    (root / "bench" / "traffic" / "tiny-doc.json").write_text(json.dumps(
        dict(mix, loop="closed", clients=2, block=4)))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "tiny.json", "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.chat", "config": "tiny", "traffic": "tiny-chat",
         "chips": 1, "why": "test"},
        {"name": "tiny.doc", "config": "tiny", "traffic": "tiny-doc",
         "chips": 1, "why": "test"}]
    for group in (bench["end_to_end"], bench["per_layer"]):
        for e in group:
            if "workloads" in e:
                e["workloads"] = ["tiny.doc"] if "longdoc" in e["name"] \
                    else ["tiny.chat"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root / "BENCHMARK.json"


def _run(bench_file, *args, seed=7):
    return run_cell.main(["--workload", "tiny.chat", "--seed", str(seed),
                          "--seconds", "2", *args],
                         require_tpu=False, bench_file=bench_file)


def test_refuses_without_a_tpu(bench_file, capsys):
    with pytest.raises(SystemExit) as e:
        run_cell.main(["--workload", "tiny.chat", "--seed", "1",
                       "--seconds", "1"], bench_file=bench_file)
    assert "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_refuses_the_interpreter(bench_file, capsys, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(SystemExit) as e:
        run_cell.main(["--workload", "tiny.chat", "--seed", "1",
                       "--seconds", "1"], bench_file=bench_file)
    assert "REPRO_PALLAS_INTERPRET" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_sound_run_is_correct(bench_file, capsys):
    out = _run(bench_file, seed=2**31 + 5)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(line) == json.loads(json.dumps(out))
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 16
    assert list(out)[-1] == "checks"
    assert out["checks"]["gap_mean"]["value"] <= LIMIT
    assert set(out["metrics"]) == {"ttft_p95_ms", "itl_p95_ms",
                                   "tokens_s", "setup_s"}
    assert out["compiles_in_window"] == 0


def _altered_tokens(monkeypatch):
    from repro.serving import engine

    real = engine.make_serving_steps

    def steps(*a, **kw):
        prefill, tail, decode = real(*a, **kw)

        def altered(*args):
            nxt, caches = decode(*args)
            return (nxt + 1) % TINY_MODEL["vocab"], caches

        altered._cache_size = decode._cache_size
        return prefill, tail, altered

    monkeypatch.setattr(engine, "make_serving_steps", steps)


def _state_unchanged(monkeypatch):
    from repro.serving import kv_pages

    monkeypatch.setattr(kv_pages, "paged_write",
                        lambda cache, k, v, abs_pos: cache)


@pytest.mark.parametrize("fault", [_altered_tokens, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_path_is_not_correct(bench_file, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(bench_file, seed=3)
    assert not out["correct"]
    assert out["checks"]["gap_mean"]["value"] > LIMIT


def test_control_is_not_correct(bench_file):
    """The fp8 control, put in the program's place over the same served
    positions, fails the check that the program passes on the same run."""
    from harness import spec

    cell = spec.load_cell("tiny.chat", bench_file)
    rec = run_cell.serve_window(cell, 11, 2.0, False, require_tpu=False)[0]
    program, n = run_cell.check(cell, 11, rec)
    control, n_control = run_cell.check(cell, 11, rec, control="fp8")
    assert n == n_control > 0
    assert run_cell.verdict(program)
    assert not run_cell.verdict(control)


def test_readings_line(bench_file):
    import readings

    (line,) = readings.main(["--workload", "tiny.chat", "--seeds", "12",
                             "--seconds", "2", "--controls", "fp8"],
                            require_tpu=False, bench_file=bench_file)
    assert line["checked_tokens"] > 0
    assert line["served.gap_mean"] <= LIMIT < line["fp8.gap_mean"]
    assert line["served.gap_max"] < line["fp8.gap_max"]


def test_closed_loop_traced_run(bench_file):
    out = run_cell.main(["--workload", "tiny.doc", "--seed", "4",
                         "--seconds", "2", "--trace", "1"],
                        require_tpu=False, bench_file=bench_file)
    assert out["correct"]
    # off the chip only the program's counters have something to read
    assert set(out["metrics"]) == {"token_util.longdoc"}
    assert 0 < out["metrics"]["token_util.longdoc"]["value"] <= 100
    assert "breakdown" in out and out["device"]["window_s"] > 0

"""The reduction from a device trace to shares: on a hand-made trace with
known answers, and on a trimmed copy of a chip trace kept beside this
file, whose readings are pinned so that every PR computes them the same
way."""

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness.devtrace import Trace, union  # noqa: E402

MS = 1_000_000  # ns


def _hand_trace():
    # window 0..100 ms; a decode module 10..30 with two kernel ops and
    # one other op; a prefill module 50..90 with one kernel op; a step
    # span around each module and a wait span between them
    return Trace({
        "devices": [{
            "name": "/device:TPU:0",
            "ops": [["while.5", 10 * MS, 20 * MS],
                    ["w4a16_matmul.1", 10 * MS, 5 * MS],
                    ["fusion.7", 15 * MS, 5 * MS],
                    ["w4a16_matmul.2", 22 * MS, 8 * MS],
                    ["flash_prefill.3", 50 * MS, 40 * MS],
                    ["fusion.8", 95 * MS, 10 * MS]],
            "modules": [["jit_dec_step(1)", 10 * MS, 20 * MS],
                        ["jit_prefill_step(2)", 50 * MS, 40 * MS],
                        ["jit_dec_step(1)", 95 * MS, 10 * MS]]}],
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.step", 5 * MS, 30 * MS],
                 ["bench.wait", 35 * MS, 10 * MS],
                 ["bench.step", 45 * MS, 50 * MS]]})


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_hand_trace_readings():
    tr = _hand_trace()
    assert tr.window_s == pytest.approx(0.1)
    # busy: 10..30 (the while holds 20..22 too), 50..90, 95..100 (clipped)
    assert tr.busy_s() == pytest.approx(0.065)
    # the last decode module runs past the window: left out
    assert tr.modules("jit_dec_step") == [(10 * MS, 30 * MS)]
    assert tr.kernel_s(["w4a16_matmul"],
                       within=tr.modules("jit_dec_step")) == \
        pytest.approx(0.013)
    assert tr.kernel_s(["w4a16_matmul"],
                       within=tr.modules("jit_prefill_step")) == 0.0
    assert tr.kernel_s(["flash_prefill"]) == pytest.approx(0.040)
    # idle: 0..10 (in the first step), 30..50 (mid-point in the wait),
    # 90..95 (in the second step), longest first
    assert tr.idle_gaps() == [["bench.wait", pytest.approx(0.020)],
                              ["bench.step", pytest.approx(0.010)],
                              ["bench.step", pytest.approx(0.005)]]
    top = dict(tr.top_ops())
    assert top == {"flash_prefill": pytest.approx(0.040),
                   "w4a16_matmul": pytest.approx(0.013),
                   "fusion": pytest.approx(0.010)}


FIXTURE = Path(__file__).parent / "fixtures" / "qwen2-0.5b.chat.trace.json.gz"


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    with gzip.open(FIXTURE, "rt") as f:
        rec = json.load(f)
    tr = Trace(rec["trace"])
    got = {"window_s": tr.window_s, "busy_s": tr.busy_s(),
           "decode_modules": len(tr.modules("jit_dec_step")),
           "gemm_s": tr.kernel_s(rec["gemm_names"],
                                 within=tr.modules("jit_dec_step")),
           "attention_s": tr.kernel_s(["paged_decode_attention"])}
    for key, want in rec["pinned"].items():
        assert got[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < got["busy_s"] < got["window_s"]
    assert 0 < got["gemm_s"] and 0 < got["attention_s"] < got["busy_s"]

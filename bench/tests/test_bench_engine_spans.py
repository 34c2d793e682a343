"""Readings of the engine's step spans against the device trace: on
hand-made traces with known idle stretches, steps and prefills, and on a
trimmed chip trace that holds the engine's spans, kept beside this file,
whose readings are pinned."""

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness.devtrace import Trace  # noqa: E402
from harness.enginespans import (  # noqa: E402
    prefill_stall_ms,
    spans,
    step_host_idle_ms,
)
from harness.layers import DECODE_MODULE, Run, load_metric  # noqa: E402

MS = 1_000_000  # ns


def _host(*spans_ms):
    return [[name, a * MS, (b - a) * MS] for name, a, b in spans_ms]


def _chat_trace():
    # window 0..100 ms.  Step 1 (5..52) admits one request: its prefill
    # runs on the device 12..30, then the decode 34..50.  Step 2 (59..95)
    # decodes only, on the device 70..88.  A step cut by the window's
    # start is left out.
    return Trace({
        "devices": [{
            "name": "/device:TPU:0",
            "ops": [["flash_prefill.1", 12 * MS, 18 * MS],
                    ["w4a16_matmul.2", 34 * MS, 16 * MS],
                    ["w4a16_matmul.3", 70 * MS, 18 * MS]],
            "modules": [["jit_prefill_step(1)", 12 * MS, 18 * MS],
                        ["jit_dec_step(2)", 34 * MS, 16 * MS],
                        ["jit_dec_step(2)", 70 * MS, 18 * MS]]}],
        "host": _host(
            ("bench.window", 0, 100),
            ("engine.step", -5, 3),
            ("bench.step", 4, 53),
            ("engine.step", 5, 52),
            ("engine.schedule", 5, 8),
            ("engine.prefill", 8, 31),
            ("engine.prefill.build", 8, 10),
            ("engine.sync_tables", 10, 11),
            ("engine.prefill.dispatch", 11, 12),
            ("engine.prefill.readback", 29, 31),
            ("engine.decode", 31, 51),
            ("engine.decode.build", 31, 32.5),
            ("engine.sync_tables", 32.5, 33),
            ("engine.decode.dispatch", 33, 34),
            ("engine.decode.readback", 34, 50),
            ("engine.decode.apply", 50, 51),
            ("engine.observe", 51, 52),
            ("bench.step", 58, 96),
            ("engine.step", 59, 95),
            ("engine.schedule", 59, 61),
            ("engine.decode", 61, 92),
            ("engine.decode.build", 61, 68),
            ("engine.sync_tables", 68, 69),
            ("engine.decode.dispatch", 69, 70),
            ("engine.decode.readback", 70, 89),
            ("engine.decode.apply", 89, 92),
            ("engine.retire", 92, 93),
            ("engine.observe", 93, 95))})


def _longdoc_trace():
    # window 0..100 ms, two prefill-only steps: 0..40 with the device
    # busy 10..35, 50..90 with the device busy 55..85
    return Trace({
        "devices": [{
            "name": "/device:TPU:0",
            "ops": [["flash_prefill.1", 10 * MS, 25 * MS],
                    ["flash_prefill.1", 55 * MS, 30 * MS]],
            "modules": []}],
        "host": _host(("bench.window", 0, 100),
                      ("engine.step", 0, 40), ("engine.prefill", 2, 38),
                      ("engine.step", 50, 90), ("engine.prefill", 52, 88))})


def test_spans_lie_wholly_inside_the_window():
    tr = _chat_trace()
    assert spans(tr, "engine.step") == [(5 * MS, 52 * MS), (59 * MS, 95 * MS)]
    assert spans(tr, "engine.nothing") == []


def test_step_host_idle_ms_chat():
    # step 1: 47 ms long, 18 + 16 busy -> 13 idle; step 2: 36 long, 18
    # busy -> 18 idle; mean 15.5
    assert step_host_idle_ms(_chat_trace()) == pytest.approx(15.5)


def test_step_host_idle_ms_longdoc():
    # 40 - 25 = 15 and 40 - 30 = 10 idle: mean 12.5
    assert step_host_idle_ms(_longdoc_trace()) == pytest.approx(12.5)


def test_prefill_stall_ms_chat():
    # only step 1 holds a prefill and a decode: its prefill took 23 ms;
    # prefill-only steps hold no decode batch to stall
    assert prefill_stall_ms(_chat_trace()) == pytest.approx(23.0)
    assert prefill_stall_ms(_longdoc_trace()) is None


def test_idle_gaps_named_by_engine_phase():
    # each stretch with no op is named by the innermost span around its
    # middle: with the engine's spans, a phase of the step
    assert _chat_trace().idle_gaps() == [
        ["engine.schedule", pytest.approx(0.020)],      # 50..70
        ["engine.schedule", pytest.approx(0.012)],      # 0..12
        ["engine.observe", pytest.approx(0.012)],       # 88..100
        ["engine.decode.build", pytest.approx(0.004)]]  # 30..34


def test_trace_without_engine_spans_reads_nothing():
    raw = _chat_trace().raw
    bare = Trace({"devices": raw["devices"],
                  "host": [s for s in raw["host"]
                           if not s[0].startswith("engine.")]})
    assert step_host_idle_ms(bare) is None
    assert prefill_stall_ms(bare) is None


FIXTURE = (Path(__file__).parent / "fixtures"
           / "qwen2-0.5b.chat.engine.trace.json.gz")


def _recorded():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def _readings(tr: Trace, gemm_names) -> dict:
    run = Run(m={}, sv=None, record=None, peaks=None, trace=tr)
    return {"window_s": tr.window_s, "busy_s": tr.busy_s(),
            "decode_modules": len(tr.modules(DECODE_MODULE)),
            "gemm_s": tr.kernel_s(gemm_names,
                                  within=tr.modules(DECODE_MODULE)),
            "attention_s": tr.kernel_s(["paged_decode_attention"]),
            "top_ops": tr.top_ops(),
            "idle_gap_s": [s for _, s in tr.idle_gaps()],
            "idle_share": load_metric("idle_share.chat").read(run),
            "decode_step_ms": load_metric("decode_step_ms.chat").read(run)}


def test_recorded_engine_trace_readings():
    rec = _recorded()
    tr = Trace(rec["trace"])
    got = _readings(tr, rec["gemm_names"])
    got["step_host_idle_ms"] = step_host_idle_ms(tr)
    got["prefill_stall_ms"] = prefill_stall_ms(tr)
    for key, want in rec["pinned"].items():
        assert got[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < got["step_host_idle_ms"] and 0 < got["prefill_stall_ms"]
    names = [n for n, _ in tr.idle_gaps()]
    assert any(n.startswith("engine.") for n in names), names


def test_recorded_engine_spans_leave_other_readings_alone():
    # the readers that came before the engine's spans read the same
    # numbers with and without them; only the gaps' names change
    rec = _recorded()
    raw = rec["trace"]
    bare = {"devices": raw["devices"],
            "host": [s for s in raw["host"]
                     if not s[0].startswith("engine.")]}
    assert len(bare["host"]) < len(raw["host"])
    assert _readings(Trace(raw), rec["gemm_names"]) == \
        _readings(Trace(bare), rec["gemm_names"])

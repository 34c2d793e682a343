"""Serving telemetry: metrics registry semantics (percentile edges, text
exposition), the recompile sentinel (once per new bucket shape, loud on
steady-state), the engine's step spans (recorded only under a profiler,
nested by phase), the names the device trace reads, and end-to-end
neutrality — telemetry on vs off generates identical tokens."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import Runtime, ServingConfig, get_config
from repro.observability import (
    NULL_REGISTRY,
    JitWatch,
    MetricsRegistry,
    RecompileError,
    Telemetry,
)
from repro.serving.api import poisson_trace, run_trace
from repro.serving.engine import InferenceEngine


# ----------------------------------------------------------------- metrics --
def test_counter_gauge_identity_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "help text")
    c.inc()
    c.inc(3)
    assert reg.counter("reqs_total").value == 4          # same cell
    # labelled metrics are distinct cells per label set
    reg.counter("ops_total", op="a").inc(2)
    reg.counter("ops_total", op="b").inc(5)
    snap = reg.snapshot()["counters"]
    assert snap["reqs_total"] == 4
    assert snap['ops_total{op="a"}'] == 2
    assert snap['ops_total{op="b"}'] == 5
    reg.gauge("depth").set(7)
    assert reg.snapshot()["gauges"]["depth"] == 7.0


def test_histogram_single_observation_is_exact():
    reg = MetricsRegistry()
    h = reg.histogram("lat_us")
    h.observe(123.4)
    s = h.summary()
    assert s["count"] == 1 and s["sum"] == pytest.approx(123.4)
    # clamping to the observed [min, max] makes one-value histograms exact
    assert s["p50"] == s["p95"] == s["p99"] == pytest.approx(123.4)
    assert s["min"] == s["max"] == pytest.approx(123.4)


def test_histogram_percentiles_bounded_and_monotonic():
    h = MetricsRegistry().histogram("lat_us")
    rng = np.random.default_rng(0)
    vals = rng.uniform(10.0, 50_000.0, size=500)
    for v in vals:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 500
    assert s["min"] == pytest.approx(vals.min())
    assert s["max"] == pytest.approx(vals.max())
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    # bucketed estimate stays in the ballpark of the exact percentile
    assert s["p50"] == pytest.approx(np.percentile(vals, 50), rel=1.0)


def test_histogram_all_equal_and_empty():
    h = MetricsRegistry().histogram("lat_us")
    assert h.percentile(50) is None
    assert h.summary()["p99"] is None
    for _ in range(10):
        h.observe(400.0)
    assert h.percentile(50) == pytest.approx(400.0)
    assert h.percentile(99) == pytest.approx(400.0)


def test_render_text_prometheus_shape():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "finished requests").inc(2)
    reg.gauge("depth", "queue depth").set(3)
    reg.histogram("lat_us", "latency", buckets=(10.0, 100.0)).observe(50.0)
    text = reg.render_text()
    assert "# HELP reqs_total finished requests" in text
    assert "# TYPE reqs_total counter" in text
    assert "reqs_total 2" in text
    assert "depth 3.0" in text
    # cumulative buckets + the open-ended +Inf bucket + _sum/_count
    assert 'lat_us_bucket{le="10"} 0' in text
    assert 'lat_us_bucket{le="100"} 1' in text
    assert 'lat_us_bucket{le="+Inf"} 1' in text
    assert "lat_us_sum 50.0" in text
    assert "lat_us_count 1" in text


def test_null_registry_is_inert():
    m = NULL_REGISTRY.counter("x")
    m.inc()
    NULL_REGISTRY.gauge("y").set(1)
    NULL_REGISTRY.histogram("z").observe(2.0)
    assert NULL_REGISTRY.snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}
    assert NULL_REGISTRY.render_text() == ""


# --------------------------------------------------------------- jit watch --
class _FakeJit:
    """Stub with a controllable cache: set .size to simulate compiles."""

    def __init__(self):
        self.size = 0

    def _cache_size(self):
        return self.size


def test_jit_watch_counts_once_per_new_shape():
    reg = MetricsRegistry()
    w = JitWatch(reg)
    f = _FakeJit()
    w.register("decode", f)
    f.size = 1                                   # first bucket compiles
    assert w.after_call("decode", (2, 1), step=0) == 1
    assert w.after_call("decode", (2, 1), step=1) == 0   # cached replay
    f.size = 2                                   # second bucket compiles
    assert w.after_call("decode", (4, 1), step=2) == 1
    assert w.total == 2 and w.steady_state == 0
    assert reg.snapshot()["counters"]['jit_compiles_total{fn="decode"}'] == 2


def test_jit_watch_flags_steady_state_and_strict_raises():
    w = JitWatch(MetricsRegistry())
    f = _FakeJit()
    w.register("decode", f)
    f.size = 1
    w.after_call("decode", (2, 1), step=0)
    f.size = 2                                   # recompile, same shape
    assert w.after_call("decode", (2, 1), step=5) == 1
    assert w.steady_state == 1
    assert w.snapshot()["events"][-1]["steady_state"] is True

    strict = JitWatch(MetricsRegistry(), strict=True)
    g = _FakeJit()
    strict.register("decode", g)
    g.size = 1
    strict.after_call("decode", (2, 1))
    g.size = 2
    with pytest.raises(RecompileError, match="decode"):
        strict.after_call("decode", (2, 1))


def test_jit_watch_absorb_rebaselines():
    w = JitWatch(strict=True)
    f = _FakeJit()
    w.register("decode", f)
    f.size = 1
    w.after_call("decode", (2, 1))
    f.size = 3                      # out-of-loop probe calls (profile())
    w.absorb()
    assert w.after_call("decode", (2, 1)) == 0   # not a steady-state hit


def test_jit_watch_novelty_fallback_without_cache_api():
    w = JitWatch(strict=True)
    w.register("decode", lambda x: x)            # no _cache_size
    assert w.after_call("decode", (2, 1)) == 1   # new shape ~ compile
    assert w.after_call("decode", (2, 1)) == 0   # degrades to never-fires
    assert w.steady_state == 0


def test_jit_watch_on_real_jit():
    w = JitWatch()
    f = jax.jit(lambda x: x + 1)
    w.register("f", f)
    f(jnp.zeros((2,), jnp.float32))
    assert w.after_call("f", (2,)) == 1
    f(jnp.zeros((2,), jnp.float32))
    assert w.after_call("f", (2,)) == 0          # cache hit
    f(jnp.zeros((3,), jnp.float32))
    assert w.after_call("f", (3,)) == 1
    assert w.total == 2 and w.steady_state == 0


# -------------------------------------------------------------- engine e2e --
@pytest.fixture(scope="module")
def reduced_cfg():
    return get_config("qwen2-0.5b").reduced()


def _engine(cfg, telemetry=None, clock=None, **sv_kw):
    rt = Runtime(quant_backend="float", cache_dtype="bfloat16", remat="none",
                 loss_chunk=0)
    sv = ServingConfig(**{"layout": "paged", "max_batch": 2, "page_size": 8,
                          "num_pages": 32, "max_ctx": 32, **sv_kw})
    kw = {"clock": clock} if clock is not None else {}
    return InferenceEngine(cfg, rt, sv, seed=0, telemetry=telemetry, **kw)


def _profiled(log_dir, fn):
    """Run `fn()` under the profiler; its result and the engine spans it
    recorded: one list per host thread of (name, start, end, args),
    ordered by start, parents before their children."""
    with jax.profiler.trace(str(log_dir)):
        out = fn()
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    threads = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                           dict(ev.stats)) for ev in line.events
                          if ev.name.startswith("engine.")),
                         key=lambda e: (e[1], -e[2]))
            if evs:
                threads.append(evs)
    return out, threads


def _span_tree(threads):
    """(span, [child spans]) for every span, a child being a span that
    lies directly inside its parent on the same thread."""
    nodes = []
    for evs in threads:
        stack = []
        for ev in evs:
            while stack and ev[1] >= stack[-1][0][2]:
                stack.pop()
            node = (ev, [])
            if stack:
                assert ev[2] <= stack[-1][0][2], (ev, stack[-1][0])
                stack[-1][1].append(ev)
            stack.append(node)
            nodes.append(node)
    return nodes


def _children(nodes, name):
    return [(ev, [c[0] for c in kids]) for ev, kids in nodes
            if ev[0] == name]


STEP_PHASES = {"engine.schedule", "engine.prefill", "engine.decode",
               "engine.ragged", "engine.retire", "engine.observe"}


def _check_steps(nodes, n_steps):
    steps = _children(nodes, "engine.step")
    assert len(steps) == n_steps
    for ev, kids in steps:
        assert {"waiting", "running"} <= set(ev[3])
        assert "engine.schedule" in kids
        assert set(kids) <= STEP_PHASES, kids
    # every phase span sits inside a step
    inside = sum((kids for _, kids in steps), [])
    assert sorted(inside) == sorted(
        ev[0] for ev, _ in nodes if ev[0] in STEP_PHASES)


def test_engine_telemetry_is_token_identity_neutral(reduced_cfg, tmp_path):
    trace = poisson_trace(4, 1.0, [8], [4], reduced_cfg.vocab, seed=5)
    # full telemetry, strict sentinel (a steady-state recompile would
    # raise), spans recorded under the profiler ...
    tm = Telemetry(metrics=True, strict_recompiles=True)
    eng = _engine(reduced_cfg, telemetry=tm)
    eng.warmup([8])
    (stats, fin), threads = _profiled(tmp_path, lambda: run_trace(eng, trace))
    # ... against no registry and no profiler: the spans are inert
    _, fin_off = run_trace(_engine(reduced_cfg, Telemetry.disabled()), trace)
    assert [r.tokens for r in fin] == [r.tokens for r in fin_off]

    # the profile covers every engine step
    assert sum(1 for evs in threads for ev in evs
               if ev[0] == "engine.step") == stats["steps"]
    # registry agrees with the engine's own counts; latency histograms
    # carry the typed outcome label (all four requests finished cleanly)
    hists = stats["metrics"]["histograms"]
    assert hists['ttft_us{outcome="ok"}']["count"] \
        == stats["requests_finished"] == 4
    assert hists["step_wall_us"]["count"] == stats["steps"]
    counters = stats["metrics"]["counters"]
    assert counters["decode_tokens_total"] == stats["decode_tokens"]
    assert counters["requests_finished_total"] == 4
    assert counters['requests_retired_total{outcome="ok"}'] == 4
    assert stats["outcomes"] == {"ok": 4}
    # warmup compiled every bucket: zero steady-state recompiles (strict
    # mode would have raised) and a non-empty compile ledger
    assert stats["recompiles"]["steady_state"] == 0
    assert stats["recompiles"]["total"] > 0
    # Prometheus exposition renders the same registry
    assert 'ttft_us_count{outcome="ok"} 4' in eng.metrics.render_text()


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_engine_step_spans_nest_by_phase(reduced_cfg, tmp_path, layout):
    trace = poisson_trace(4, 1.0, [8, 16], [3], reduced_cfg.vocab, seed=3)
    eng = _engine(reduced_cfg, layout=layout)
    eng.warmup([8, 16])
    (stats, fin), threads = _profiled(tmp_path, lambda: run_trace(eng, trace))
    nodes = _span_tree(threads)
    _check_steps(nodes, stats["steps"])
    sync = ["engine.sync_tables"] if layout == "paged" else []
    prefills = _children(nodes, "engine.prefill")
    assert sorted(ev[3]["rid"] for ev, _ in prefills) == \
        sorted(r.rid for r in fin)
    for ev, kids in prefills:
        assert ev[3]["bucket"] >= ev[3]["tokens"] and "hit" in ev[3]
        assert kids == ["engine.prefill.build", *sync,
                        "engine.prefill.dispatch", "engine.prefill.readback"]
    decodes = _children(nodes, "engine.decode")
    assert sum(ev[3]["rows"] for ev, _ in decodes) == stats["decode_tokens"]
    for ev, kids in decodes:
        assert 1 <= ev[3]["rows"] <= ev[3]["bucket"]
        assert kids == ["engine.decode.build", *sync,
                        "engine.decode.dispatch", "engine.decode.readback",
                        "engine.decode.apply"]
    for ev, kids in _children(nodes, "engine.sync_tables"):
        assert "rows" in ev[3] and kids == []


def test_engine_ragged_step_spans_nest_by_phase(reduced_cfg, tmp_path):
    trace = poisson_trace(3, 1.0, [8, 16], [3], reduced_cfg.vocab, seed=3)
    eng = _engine(reduced_cfg, step="ragged", token_budget=16)
    eng.warmup()
    (stats, fin), threads = _profiled(tmp_path, lambda: run_trace(eng, trace))
    assert len(fin) == 3
    nodes = _span_tree(threads)
    _check_steps(nodes, stats["steps"])
    assert not _children(nodes, "engine.prefill")
    assert not _children(nodes, "engine.decode")
    ragged = _children(nodes, "engine.ragged")
    assert ragged
    for ev, kids in ragged:
        assert 1 <= ev[3]["tokens"] <= 16
        assert kids == ["engine.ragged.build", "engine.sync_tables",
                        "engine.ragged.dispatch", "engine.ragged.readback",
                        "engine.ragged.apply"]


def test_spans_record_only_under_a_profiler(tmp_path):
    span = Telemetry.disabled().trace.span
    with span("engine.before", rid=1):
        pass

    def during():
        with span("engine.during", rid=2):
            pass

    _, threads = _profiled(tmp_path, during)
    assert [(ev[0], ev[3]) for evs in threads for ev in evs] == \
        [("engine.during", {"rid": 2})]


def test_serving_step_jits_carry_the_trace_module_names(reduced_cfg):
    # the benchmark finds each step in the device trace by the XLA module
    # its jit lowers to
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
    from harness.layers import DECODE_MODULE, PREFILL_MODULE

    eng = _engine(reduced_cfg)
    tok = jnp.zeros((1, 8), jnp.int32)
    args = (eng.params, tok, eng.caches, jnp.full((1, 8), -1, jnp.int32),
            eng._tbl, jnp.zeros((1,), jnp.int32))
    for fn, want in ((eng._decode, DECODE_MODULE),
                     (eng._prefill, PREFILL_MODULE),
                     (eng._prefill_tail, PREFILL_MODULE)):
        text = fn.lower(*args).as_text()
        assert f"module @{want} " in text, text[:200]


def test_engine_stats_with_zero_finished_requests(reduced_cfg):
    eng = _engine(reduced_cfg, Telemetry.disabled())
    stats = eng.stats()
    assert stats["requests_finished"] == 0
    # no fake numbers: every derived latency degrades to None
    for key in ("latency_p50_s", "latency_mean_s", "ttft_p50_s",
                "ttft_mean_s", "decode_tok_per_s"):
        assert stats[key] is None
    assert stats["metrics"] == {"counters": {}, "gauges": {},
                                "histograms": {}}


def test_engine_ttft_survives_zero_clock(reduced_cfg):
    # a fake clock pinned at 0.0 makes t_first == 0.0 exactly; the stats
    # must treat that as a real first-token time, not a missing one
    trace = poisson_trace(2, 1.0, [8], [2], reduced_cfg.vocab, seed=5)
    eng = _engine(reduced_cfg, clock=lambda: 0.0)
    stats, fin = run_trace(eng, trace)
    assert len(fin) == 2
    assert stats["ttft_p50_s"] == 0.0            # present, not None
    assert stats["latency_p50_s"] == 0.0


def test_engine_profile_stamped_with_step(reduced_cfg):
    trace = poisson_trace(2, 1.0, [8], [2], reduced_cfg.vocab, seed=5)
    tm = Telemetry(metrics=True, strict_recompiles=True)
    eng = _engine(reduced_cfg, telemetry=tm)
    eng.warmup([8])
    run_trace(eng, trace)
    prof = eng.profile()
    stats = eng.stats()
    assert prof["at_step"] == stats["steps"]
    assert stats["profile_at_step"] == stats["steps"]
    # profile()'s probe compiles were absorbed: decoding again under the
    # strict sentinel must not flag them as steady-state recompiles
    eng.submit(np.arange(8, dtype=np.int32), 2)
    eng.run_until_idle()


def test_telemetry_bundle_modes():
    tm = Telemetry()
    assert tm.registry.enabled
    assert tm.enabled
    off = Telemetry.disabled()
    assert not off.enabled
    assert off.jit_watch.after_call("decode", (1, 1)) == 0
    # spans have no switch of their own: the profiler is the switch
    assert off.trace.span is tm.trace.span is jax.profiler.TraceAnnotation

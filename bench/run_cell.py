"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run_cell.py --workload qwen2-0.5b.chat --seed 7 \\
        --seconds 30 --trace 0

The cell (``BENCHMARK.json``) names a configuration and a traffic file.
The run makes the configuration's weights on the device from ``--seed``,
builds the engine the way ``launch/serve.py`` serves (paged KV, bucketed
step, prefix cache on, the configuration's quantization), warms up the
prompt and decode buckets the traffic uses, then drives the traffic for
``--seconds`` of wall clock.  ``--trace 1`` profiles the end of the window
and reports the per-layer metrics instead of the end-to-end ones.

After the window it frees the engine and checks what the window served
against the plain reference (``harness/reference.py``): how far each
served token's logit lies below the reference's best, over a sample of
finished requests drawn from the seed, summed up by the numbers that the
configuration's ``check`` names, each against its limit.  The numbers compared
are printed with their limits as the last lines of standard error and
under ``checks``, the last key of the result line: the last line of
standard output, one JSON object.

It runs on a TPU only: with no TPU, fewer chips than the cell asks for,
or ``REPRO_PALLAS_INTERPRET`` set, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def _fail(msg: str) -> None:
    raise SystemExit(f"run_cell: {msg}")


def device_info(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count of the devices JAX sees; on anything but
    enough TPU chips (or with the Pallas interpreter forced) the run
    stops."""
    import jax

    if require_tpu and os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
        _fail("REPRO_PALLAS_INTERPRET is set; the benchmark times the "
              "compiled kernels only")
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        _fail(f"no TPU: JAX found {info['platform']} ({info['kind']})")
    if len(devs) < chips:
        _fail(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return info


def setup_paths() -> None:
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def enable_cache() -> None:
    """JAX's persistent compilation cache in the checkout, at a fixed
    path (the program's enable_compile_cache takes the directory given
    here), keeping every program so that only a cell's first run
    compiles."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def _say(msg: str) -> None:
    print(f"run_cell: {msg}", file=sys.stderr, flush=True)


def _warm_request(engine, cell) -> None:
    """One short request through submit/step/collect, so the first request
    of the window runs no eager op for the first time."""
    import numpy as np

    engine.submit(np.zeros(cell.traffic["prompt"]["min"], np.int32), 2)
    while engine.scheduler.running or engine.scheduler.waiting:
        engine.step()
    engine.collect()


def kv_live(rec, sv) -> dict:
    """How much of the KV pool the running requests fill over the
    window's steps: the mean and the largest share."""
    pool = sv.num_pages * sv.page_size
    live = [s.live_kv / pool for s in rec.steps_in(rec.t0, rec.t1)]
    if not live:
        return {}
    return {"kv_live_share_mean": sum(live) / len(live),
            "kv_live_share_max": max(live)}


def serve_window(cell, seed: int, seconds: float, trace: bool,
                 require_tpu: bool = True):
    """Set up, run the window, read the per-run numbers.  Returns
    (record, run context, device info, set-up seconds, extras)."""
    import jax

    from harness import drive, layers, spec, traffic, weights
    from harness.devtrace import Capture
    from harness.stats import percentile
    from repro.serving.engine import InferenceEngine

    if require_tpu:
        enable_cache()
    # (host time, event) of every trace, every compile request and every
    # persistent-cache hit: a request without a hit compiled
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append((time.perf_counter(), name))
        if "jaxpr_to_mlir" in name else None)
    jax.monitoring.register_event_listener(
        lambda name, **kw: compiles.append((time.perf_counter(), name))
        if name.startswith("/jax/compilation_cache/") else None)
    dev = device_info(cell.chips, require_tpu)
    peaks_all = spec.load_json(BENCH / "peaks.json")["devices"]
    if require_tpu and dev["kind"] not in peaks_all:
        _fail(f"bench/peaks.json has no row for {dev['kind']!r}")
    peaks = peaks_all.get(dev["kind"])

    cfg = spec.arch_config(cell.config)
    m = cell.config["model"]
    rt = spec.runtime(cell)
    sv = spec.serving_config(cell)
    t = time.perf_counter()
    params = weights.served_params(cfg, rt, m, seed)
    _say(f"weights made in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    engine = InferenceEngine(cfg, rt, sv, params=params,
                             clock=time.perf_counter)
    del params
    tr = cell.traffic
    engine.warmup(traffic.prompt_lengths(tr))
    _warm_request(engine, cell)
    _say(f"engine built and warmed up in {time.perf_counter() - t:.3f} s")

    cap, hooks, traced = None, [], []
    if trace:
        cap = Capture()
        lead = max(0.0, seconds - tr.get("trace_seconds", seconds))
        hooks = [(lead, lambda: (cap.start(),
                                 traced.append(time.perf_counter()))),
                 (seconds, lambda: (traced.append(time.perf_counter()),
                                    cap.stop()))]
    if tr["loop"] == "open":
        rec = drive.run_open(
            engine, lambda b: traffic.open_loop(tr, seed, seconds, cfg.vocab,
                                                b),
            seconds, spans=trace, hooks=hooks,
            run_in=traffic.run_in(tr, seed, cfg.vocab))
    else:
        rec = drive.run_closed(
            engine, traffic.closed_stream(tr, seed, cfg.vocab),
            tr["clients"], seconds, spans=trace, hooks=hooks)
    setup_s = rec.start - T_START
    dev["memory_peak_bytes"] = _peak_bytes()
    extras = {
        "setup_cache_misses": sum(
            (n.endswith("compile_requests_use_cache")
             - n.endswith("cache_hits")) for t, n in compiles if t < rec.t0),
        "compiles_in_window": sum(1 for t, _ in compiles
                                  if rec.t0 <= t <= rec.t1),
        "recompiles_steady_state":
            engine.stats()["recompiles"]["steady_state"],
        "preemptions": rec.counters1["preemptions"]
        - rec.counters0["preemptions"],
        "lateness_p95_ms": (1e3 * percentile(rec.lateness, 95)
                            if rec.lateness else None),
        "steps": len(rec.steps_in(rec.t0, rec.t1)),
        **kv_live(rec, sv),
    }
    run = layers.Run(m=m, sv=sv, record=rec, peaks=peaks)
    if cap is not None:
        t = time.perf_counter()
        run.trace = cap.read()
        _say(f"trace read in {time.perf_counter() - t:.3f} s")
        run.traced = (traced[0], traced[1])
        dev["busy_s"] = run.trace.busy_s()
        dev["window_s"] = run.trace.window_s
    del engine
    gc.collect()
    return rec, run, dev, setup_s, extras


def check(cell, seed: int, rec, control=None) -> tuple:
    """The comparison with the plain reference: ({name: (value, limit)}
    for each number the configuration's ``check`` names, value None where
    no request finished; the number of served tokens it covered).  With
    `control`, that control precision is put in
    the program's place: the tokens judged are the ones it puts first at
    the served positions."""
    from harness import reference, results

    limits = cell.config["check"]
    seqs = results.sample(rec, seed, cell.traffic["check"]["served_tokens"],
                          cell.traffic["check"]["max_requests"])
    if not seqs:
        return {k: (None, lim) for k, lim in limits.items()}, 0
    controls = (control,) if control else ()
    gaps = reference.gap_readings(
        cell.config["model"], seed, seqs, cell.serving["max_ctx"],
        cell.traffic["output"]["max"], controls)[control or "served"]
    return ({k: (reference.STATS[k](gaps), lim) for k, lim in limits.items()},
            len(gaps))


def verdict(checks: dict) -> bool:
    """Correct when every number compared has a value within its limit."""
    return all(v is not None and v <= lim for v, lim in checks.values())


def main(argv=None, require_tpu: bool = True, bench_file=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    setup_paths()
    from harness import layers, results, spec

    bench = spec.load_json(bench_file or ROOT / "BENCHMARK.json")
    cell = spec.load_cell(args.workload, bench_file)
    rec, run, dev, setup_s, extras = serve_window(
        cell, args.seed, args.seconds, bool(args.trace), require_tpu)

    metrics = {}
    if args.trace:
        for pm in bench["per_layer"]:
            if args.workload not in pm.get("workloads", [args.workload]):
                continue
            value = layers.load_metric(pm["name"]).read(run)
            if value is not None:
                metrics[pm["name"]] = {"value": value, "unit": pm["unit"]}
    else:
        names = [e["name"] for e in bench["end_to_end"]
                 if args.workload in e.get("workloads", [args.workload])]
        units = {e["name"]: e["unit"] for e in bench["end_to_end"]}
        values = results.end_to_end(rec, names)
        values["setup_s"] = setup_s
        metrics = {n: {"value": values[n], "unit": units[n]}
                   for n in names}
    attempted, failed = results.attempted_failed(rec)

    t = time.perf_counter()
    checks, checked_tokens = check(cell, args.seed, rec)
    _say(f"reference check took {time.perf_counter() - t:.3f} s")
    correct = verdict(checks)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev, **extras,
           "checked_tokens": checked_tokens}
    if args.trace:
        out["breakdown"] = {"device_ops": run.trace.top_ops(),
                            "idle_gaps": run.trace.idle_gaps()}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    _say(f"checked {out['checked_tokens']} served tokens")
    for k, (v, lim) in checks.items():
        _say(f"check {k} {v} limit {lim}")
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()

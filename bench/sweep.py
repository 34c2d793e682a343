"""Find the highest rate an open-loop cell sustains: a few fixed rates,
one window each, in one process.

    python3 bench/sweep.py --workload qwen2-0.5b.chat --rates 8,12,16,20 \\
        --seconds 20 --seed 5

Each rate first runs in for the traffic file's ``run_in_s`` at that rate,
so that its window opens on a loaded engine.  For each rate it prints the
queue depth (requests waiting for admission) and the requests running,
over the window's first tenth and its last tenth, with the tails,
tokens/s and the KV pool's live share at that rate.  A backlog that grows
from the first tenth to the last marks a rate above the knee.  The cell itself offers a fixed rate;
this is how that rate was chosen, not part of a run.
"""

from __future__ import annotations

import argparse
import json
import sys

import run_cell


def main(argv=None, require_tpu: bool = True, bench_file=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run_cell.setup_paths()
    import time

    from harness import drive, results, spec, traffic, weights
    from harness.stats import percentile
    from repro.serving.engine import InferenceEngine

    if require_tpu:
        run_cell.enable_cache()
    cell = spec.load_cell(args.workload, bench_file)
    run_cell.device_info(cell.chips, require_tpu)
    cfg = spec.arch_config(cell.config)
    rt, sv = spec.runtime(cell), spec.serving_config(cell)
    engine = InferenceEngine(
        cfg, rt, sv, clock=time.perf_counter,
        params=weights.served_params(cfg, rt, cell.config["model"],
                                     args.seed))
    engine.warmup(traffic.prompt_lengths(cell.traffic))
    run_cell._warm_request(engine, cell)
    out = []
    T = args.seconds
    for rate in (float(r) for r in args.rates.split(",")):
        tr = dict(cell.traffic, rate_rps=rate)
        depth = []
        hooks = [(k * T / 20, lambda k=k: depth.append(
            (k, len(engine.scheduler.waiting),
             len(engine.scheduler.running)))) for k in range(21)]
        rec = drive.run_open(
            engine, lambda b: traffic.open_loop(tr, args.seed, T, cfg.vocab,
                                                b),
            T, drain_s=0.0, hooks=hooks,
            run_in=traffic.run_in(tr, args.seed, cfg.vocab))

        def mean(rows, i):
            return sum(r[i] for r in rows) / len(rows)

        first = [d for d in depth if d[0] <= 2]
        last = [d for d in depth if d[0] >= 18]
        line = {"rate_rps": rate,
                "queue_first_tenth": mean(first, 1),
                "queue_last_tenth": mean(last, 1),
                "running_first_tenth": mean(first, 2),
                "running_last_tenth": mean(last, 2),
                **run_cell.kv_live(rec, sv),
                "ttft_p50_ms": percentile(results.ttft_ms(rec), 50),
                "ttft_p95_ms": percentile(results.ttft_ms(rec), 95),
                **results.end_to_end(rec, ("itl_p95_ms", "tokens_s")),
                "attempted_failed": results.attempted_failed(rec)}
        print(json.dumps(line), flush=True)
        out.append(line)
        for t in rec.tracked:           # clear the backlog for the next rate
            if not t.retired:
                engine.cancel(t.req.rid)
        while engine.scheduler.running or engine.scheduler.waiting:
            engine.step()
        engine.collect()
    return out


if __name__ == "__main__":
    main()
    sys.exit(0)

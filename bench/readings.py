"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/readings.py --workload qwen2-0.5b.chat \\
        --seeds 11,12,13 --seconds 10 --controls fp8,int8

For each seed, in one process: the cell's window at its own load (as
``run_cell.py`` runs it), then over the served sample each number that the
reference can compare (``reference.STATS``), for the served tokens and for
each control precision put in the program's place (the tokens it puts
first at the same positions).  One JSON line per seed; the benchmark's
own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run_cell


def main(argv=None, require_tpu: bool = True, bench_file=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="fp8,int8")
    args = ap.parse_args(argv)
    run_cell.setup_paths()
    from harness import reference, results, spec

    cell = spec.load_cell(args.workload, bench_file)
    controls = [c for c in args.controls.split(",") if c]
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rec, _, dev, setup_s, extras = run_cell.serve_window(
            cell, seed, args.seconds, False, require_tpu)
        t0 = time.perf_counter()
        seqs = results.sample(rec, seed,
                              cell.traffic["check"]["served_tokens"],
                              cell.traffic["check"]["max_requests"])
        if not seqs:
            raise SystemExit(f"readings: no request finished (seed {seed})")
        gaps = reference.gap_readings(
            cell.config["model"], seed, seqs, cell.serving["max_ctx"],
            cell.traffic["output"]["max"], controls)
        line = {"workload": args.workload, "seed": seed,
                "setup_s": setup_s, "check_s": time.perf_counter() - t0,
                "device": dev, **extras,
                "checked_tokens": len(gaps["served"])}
        for name, g in gaps.items():
            for stat, fn in reference.STATS.items():
                line[f"{name}.{stat}"] = fn(g)
            line[f"{name}.flips"] = int((g > 0).sum())
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


if __name__ == "__main__":
    main()
    sys.exit(0)

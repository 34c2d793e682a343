"""Continuous-batching serving driver over the paper's packed-int4 weights.

Drives the repro.serving engine with synthetic Poisson traffic (mixed
prompt/generation lengths) and prints a JSON report with tokens/s and
p50/p95 per-request latency.  `--layout compare` runs the same trace through
three attention paths — contiguous KV, paged KV with the gather
(`paged_read`-then-attend) baseline, and paged KV with the fused
paged-attention kernel — and verifies the generated tokens are
bit-identical across all three; with the prefix cache on it adds a fourth
`paged_nocache` cold twin, proving cache-hit runs token-identical to cold
runs, and always a fifth `ragged` path: the token-major engine that packs
mixed prefill chunks + decode tokens into one fused launch per step
(`--step ragged` selects it for single-layout runs).  `--scenario
shared_prefix` swaps the traffic for a shared-system-prompt fleet (the
prefix cache's target workload) and the report carries `prefix_hit_rate` /
`tokens_prefilled_saved`; `mixed` churns batch composition every step and
`bursty` groups arrivals — the ragged step's stress workloads.

Mixed precision: `--quant-plan <name|path|inline>` serves under any
site-addressable QuantPlan (core.quant_plan).  `--quantized-ckpt` proves the
quantized-checkpoint path end-to-end: save packed nibbles + scales + plan,
restore with no float master, serve from the restored tree, and verify
bit-identical logits/tokens against the same plan applied to float masters.
`--sweep` adds the per-site sensitivity table to the report.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --layout compare --requests 8 --rate 0.5 --quant w4a4_packed \
        --out BENCH_serve.json
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --layers 2 --quant-plan mixed_sensitive --quantized-ckpt --sweep \
        --out BENCH_quantized.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import Runtime, ServingConfig, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.observability import Telemetry, global_registry
from repro.serving.api import (
    bursty_trace,
    mixed_trace,
    poisson_trace,
    run_trace,
    shared_prefix_trace,
)
from repro.serving.engine import InferenceEngine, build_params


def _dir_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
    return total


def _quantized_ckpt_report(cfg, rt, ckpt_dir, seed):
    """Save a quantized checkpoint from fresh float masters, restore it, and
    verify it against the same plan applied directly to the masters.
    Returns (serving_params_from_ckpt, report_dict)."""
    from repro.checkpoint import save_checkpoint, save_quantized, \
        restore_quantized
    from repro.core.quant_plan import (
        CKPT_PACKED, active_plan, plan_pack_tree,
    )
    from repro.kernels import ops
    from repro.core.qlinear import prepack_tree
    from repro.models import forward, init_model

    masters = init_model(jax.random.PRNGKey(seed), cfg)
    plan = active_plan(cfg, rt)

    t0 = time.perf_counter()
    save_quantized(os.path.join(ckpt_dir, "q"), 0, masters, cfg, plan=plan)
    save_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    restored, manifest = restore_quantized(os.path.join(ckpt_dir, "q"),
                                           cfg=cfg, rt=rt)
    load_s = time.perf_counter() - t0
    # float-master baseline checkpoint, for the size/load-time comparison
    t0 = time.perf_counter()
    save_checkpoint(os.path.join(ckpt_dir, "f"), 0, masters)
    float_save_s = time.perf_counter() - t0

    # the float-master path: the same plan packed at load time
    reference = plan_pack_tree(masters, cfg, plan, backends=CKPT_PACKED,
                               scale_dtype=jnp.bfloat16)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, 8),
                                0, cfg.vocab, dtype=jnp.int32)
    la = np.asarray(forward(restored, tokens, cfg, rt)[0], np.float32)
    lb = np.asarray(forward(reference, tokens, cfg, rt)[0], np.float32)
    report = {
        "plan": plan.name or "inline",
        "manifest_format": manifest.get("format"),
        "bit_identical_logits": bool(np.array_equal(la, lb)),
        "quantized_bytes": _dir_bytes(os.path.join(ckpt_dir, "q")),
        "float_master_bytes": _dir_bytes(os.path.join(ckpt_dir, "f")),
        "save_s": round(save_s, 3),
        "load_s": round(load_s, 3),
        "float_save_s": round(float_save_s, 3),
    }
    if ops.use_pallas():
        restored = prepack_tree(restored)
        reference = prepack_tree(reference)
    return restored, reference, report


def serving_runtime(max_ctx: int, *, attn_impl: str = "flash",
                    quant_backend="w4a4_packed", quant_plan=None,
                    cache_dtype="bfloat16") -> Runtime:
    """The Runtime the server runs: flash prefill and the fused paged decode
    kernel by default, under `quant_plan` if given, else the uniform
    `quant_backend`."""
    return Runtime(scan_layers=True, attn_impl=attn_impl,
                   attn_chunk_q=min(512, max_ctx), loss_chunk=0,
                   quant_backend=None if quant_plan else quant_backend,
                   quant_plan=quant_plan, cache_dtype=cache_dtype,
                   remat="none")


def serve(arch: str, *, reduced=True, layers=None, layout=None, max_batch=4,
          page_size=16, num_pages=48, max_ctx=128, requests=8, rate=0.5,
          prompt_lens=(8, 16, 32), gen_lens=(8, 16), scenario="poisson",
          sys_len=32, prefix_cache=True, step="bucketed", token_budget=0,
          burst=4, period=8,
          quant_backend="w4a4_packed", quant_plan=None, cache_dtype="bfloat16",
          quantized_ckpt=False, ckpt_dir=None, sweep=False, seed=0,
          chaos_seed=0, max_queue=0,
          trace_out=None, metrics=True):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced(**({"n_layers": layers} if layers else {}))

    if scenario in ("chaos", "cancel_storm"):
        # deterministic fault-injection harness (serving.chaos): seeded
        # cancel/deadline storms + allocator failures + step exceptions +
        # mid-run stop/resume, run in BOTH step modes against one fault-
        # free reference.  Exact-softmax prefill ("chunked") so the
        # ragged-vs-bucketed survivor-identity assertion compares
        # identical math (same reason compare mode uses it).
        from repro.serving.chaos import (
            CANCEL_STORM, ChaosConfig, chaos_report,
        )
        rt = serving_runtime(max_ctx, attn_impl="chunked",
                             quant_backend=quant_backend,
                             quant_plan=quant_plan, cache_dtype=cache_dtype)
        base = CANCEL_STORM if scenario == "cancel_storm" else ChaosConfig()
        chaos = dataclasses.replace(
            base, seed=chaos_seed, n_requests=requests, rate_per_step=rate,
            prompt_lens=tuple(prompt_lens), gen_lens=tuple(gen_lens),
            stop_resume_at=(max(2, requests // 2),))
        # chaos runs always bound the admission queue so load shedding is
        # exercised (still deterministic: queue depth at submit time is a
        # pure function of the seed)
        sv = ServingConfig(layout="paged", max_batch=max_batch,
                           page_size=page_size, num_pages=num_pages,
                           max_ctx=max_ctx, prefix_cache=prefix_cache,
                           token_budget=token_budget,
                           max_queue=max_queue or 2 * max_batch)
        return {"arch": arch, "reduced": reduced, "scenario": scenario,
                "quant": quant_plan or quant_backend,
                "cache_dtype": cache_dtype,
                **chaos_report(cfg, rt, sv, chaos)}
    if layout is None:   # paged needs a pure-attention stack (SSM doesn't page)
        blocks = tuple(cfg.pattern) + tuple(cfg.tail)
        layout = "paged" if all(bt == "A" for bt in blocks) else "contiguous"
    # perf runs prefill through the flash kernel; the compare harness uses
    # exact-softmax prefill ("chunked") so the token-identity assertion
    # compares identical math — flash's online-softmax rescaling rounds
    # differently from the ragged step's page-grouped exact softmax, and on
    # a random-init model that can flip an argmax tie in the prompt logits
    rt = serving_runtime(
        max_ctx, attn_impl="chunked" if layout == "compare" else "flash",
        quant_backend=quant_backend, quant_plan=quant_plan,
        cache_dtype=cache_dtype)
    if scenario == "shared_prefix":
        trace = shared_prefix_trace(requests, rate, sys_len, prompt_lens,
                                    gen_lens, cfg.vocab, seed=seed)
        # warm both the cold full prompts (sys + user suffix) and the tail
        # buckets a prefix hit leaves behind, so no engine absorbs a
        # mid-window jit compile
        warm_lens = tuple(prompt_lens) + tuple(sys_len + p
                                               for p in prompt_lens)
    elif scenario == "mixed":
        # one arrival per step, lengths cycling: batch composition changes
        # every step — the ragged step's target workload
        trace = mixed_trace(requests, prompt_lens, gen_lens, cfg.vocab,
                            seed=seed)
        warm_lens = tuple(prompt_lens)
    elif scenario == "bursty":
        trace = bursty_trace(requests, burst, period, prompt_lens, gen_lens,
                             cfg.vocab, seed=seed)
        warm_lens = tuple(prompt_lens)
    else:
        trace = poisson_trace(requests, rate, prompt_lens, gen_lens,
                              cfg.vocab, seed=seed)
        warm_lens = tuple(prompt_lens)
    # "paged" serves through the fused paged-attention kernel;
    # "paged_gather" is the same layout through the paged_read baseline.
    # In compare mode with the prefix cache on, "paged_nocache" adds the
    # cold twin: the same fused path with prefix_cache=off, which must be
    # token-identical to the cache-hit runs (contiguous is a second cold
    # reference — it never prefix-caches).
    # compare mode always includes the ragged token-major engine as a fifth
    # path: same trace, same paged pool, one fused launch per step — its
    # tokens must match every bucketed path
    layouts = (["paged", "paged_gather", "contiguous"]
               + (["paged_nocache"] if prefix_cache else []) + ["ragged"]
               if layout == "compare" else [layout])

    report = {"arch": arch, "reduced": reduced,
              "quant": quant_plan or quant_backend, "cache_dtype": cache_dtype,
              "requests": requests, "rate_per_step": rate,
              "scenario": scenario, "prefix_cache": bool(prefix_cache),
              **({"sys_len": sys_len} if scenario == "shared_prefix" else {})}
    params_ref = None
    if quantized_ckpt:
        # serve from a quantized checkpoint; keep the plan-on-masters twin
        # around to verify the generated tokens match end-to-end
        def with_dir(d):
            return _quantized_ckpt_report(cfg, rt, d, seed)

        if ckpt_dir:
            os.makedirs(ckpt_dir, exist_ok=True)
            params, params_ref, report["quantized_ckpt"] = with_dir(ckpt_dir)
        else:
            with tempfile.TemporaryDirectory() as d:
                params, params_ref, report["quantized_ckpt"] = with_dir(d)
    else:
        params = build_params(cfg, rt, seed)

    tokens_by_layout = {}
    for lay in layouts:
        kv_layout = "contiguous" if lay == "contiguous" else "paged"
        rt_lay = (dataclasses.replace(rt, paged_attn="gather")
                  if lay == "paged_gather" else rt)
        step_mode = ("ragged" if lay == "ragged"
                     else step if layout != "compare"
                     and kv_layout == "paged" else "bucketed")
        sv = ServingConfig(layout=kv_layout, max_batch=max_batch,
                           page_size=page_size, num_pages=num_pages,
                           max_ctx=max_ctx, step=step_mode,
                           token_budget=token_budget,
                           prefix_cache=(prefix_cache
                                         and lay != "paged_nocache"))
        # per-engine telemetry (compare-mode engines keep separate
        # registries); the Perfetto timeline records the primary layout
        tm = Telemetry(metrics=metrics,
                       trace=bool(trace_out) and lay == layouts[0])
        engine = InferenceEngine(cfg, rt_lay, sv, params=params,
                                 telemetry=tm)
        engine.warmup(warm_lens)       # compiles excluded from the stats
        stats, finished = run_trace(engine, trace)
        stats["profile"] = engine.profile()   # attn vs GEMM attribution
        stats["profile_at_step"] = stats["profile"].get("at_step")
        report[lay] = stats
        tokens_by_layout[lay] = [r.tokens for r in finished]
        if tm.trace.enabled:
            tm.trace.save(trace_out)
            report["trace_out"] = trace_out

    if params_ref is not None:
        # end-to-end: the restored-checkpoint engine must generate exactly
        # the tokens of the plan-applied-to-float-masters engine
        sv = ServingConfig(layout=layouts[0], max_batch=max_batch,
                           page_size=page_size, num_pages=num_pages,
                           max_ctx=max_ctx)
        engine_ref = InferenceEngine(cfg, rt, sv, params=params_ref)
        engine_ref.warmup(warm_lens)
        _, finished_ref = run_trace(engine_ref, trace)
        report["quantized_ckpt"]["tokens_match"] = bool(
            tokens_by_layout[layouts[0]] == [r.tokens for r in finished_ref])

    if sweep:
        from repro.launch.sensitivity import sensitivity_sweep

        report["sensitivity"] = sensitivity_sweep(cfg, seed=seed)

    if layout == "compare":
        ref_tokens = tokens_by_layout[layouts[0]]
        same = all(tokens_by_layout[lay] == ref_tokens for lay in layouts[1:])
        report["bit_identical"] = bool(same)
        if not same:
            # only the paged layouts preempt, and only they take prefix-
            # cache hits; with a lossy KV dtype recompute-resume (and a hit
            # prefill) attends dequantized state where the cold path attends
            # full precision, so argmax can legitimately diverge
            # (EXPERIMENTS.md §Serving / §Prefix caching)
            diverged = [lay for lay in layouts[1:]
                        if tokens_by_layout[lay] != ref_tokens]
            lossy_paths = (report["paged"]["requests_preempted"] > 0
                           or report["paged"]["tokens_prefilled_saved"] > 0
                           # ragged chunked prefill always attends the
                           # (dequantized) page pool, where bucketed fresh
                           # prefill attends in-flight full-precision K/V
                           or "ragged" in diverged)
            if cache_dtype in ("int8", "int4") and lossy_paths:
                report["note"] = ("paged/ragged diverged after preemption, a "
                                  "prefix-cache hit, or a chunked prefill "
                                  "with a lossy KV-cache dtype: the other "
                                  "path attends those prefixes in full "
                                  "precision — expected")
            else:
                raise SystemExit(
                    f"FAIL: decode diverged across attention paths "
                    f"({layouts[0]} vs {diverged})")
    # headline numbers from the primary layout
    primary = report[layouts[0]]
    report["tokens_per_s"] = primary["decode_tok_per_s"]
    report["latency_p50_s"] = primary["latency_p50_s"]
    report["latency_p95_s"] = primary["latency_p95_s"]
    report["prefix_hit_rate"] = primary.get("prefix_hit_rate", 0.0)
    report["tokens_prefilled_saved"] = primary.get("tokens_prefilled_saved", 0)
    report["padding_tokens_wasted"] = primary.get("padding_tokens_wasted", 0)
    report["token_utilization"] = primary.get("token_utilization")
    # telemetry headlines: steady-state recompiles (should be 0 — see
    # observability.jit_watch) and the process-wide kernel dispatch mix.
    # Compare mode takes the MAX over every engine, so a single path
    # recompiling mid-window fails the zero-steady-state gate.
    if layout == "compare":
        report["recompiles_steady_state"] = max(
            report[lay].get("recompiles", {}).get("steady_state", 0)
            for lay in layouts)
    else:
        report["recompiles_steady_state"] = (
            primary.get("recompiles", {}).get("steady_state", 0))
    report["kernel_dispatch"] = (
        global_registry().snapshot()["counters"])
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    grp = ap.add_mutually_exclusive_group()
    grp.add_argument("--reduced", action="store_true", default=True)
    grp.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--layers", type=int, default=None,
                    help="override layer count of the reduced config (e.g. 2 "
                         "so block-indexed plan rules have layers to differ on)")
    ap.add_argument("--layout", default=None,
                    choices=["paged", "contiguous", "compare"],
                    help="default: paged for attention archs, else contiguous")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=48)
    ap.add_argument("--max-ctx", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate in requests per decode step")
    ap.add_argument("--prompt-lens", default="8,16,32")
    ap.add_argument("--gen-lens", default="8,16")
    ap.add_argument("--scenario", default="poisson",
                    choices=["poisson", "shared_prefix", "mixed", "bursty",
                             "chaos", "cancel_storm"],
                    help="shared_prefix: every prompt = one shared system "
                         "prefix (--sys-len) + a unique user suffix drawn "
                         "from --prompt-lens; mixed: one arrival per step "
                         "with cycling lengths (batch composition changes "
                         "every step); bursty: --burst arrivals every "
                         "--period steps; chaos: seeded fault-injection "
                         "harness (cancels, deadlines, allocator failures, "
                         "step exceptions, stop/resume) with survivor "
                         "token-identity vs a fault-free run; cancel_storm: "
                         "chaos preset with only a high-rate cancel storm")
    ap.add_argument("--sys-len", type=int, default=32,
                    help="shared system-prompt length (shared_prefix)")
    ap.add_argument("--step", default="bucketed",
                    choices=["bucketed", "ragged"],
                    help="serving step: classic bucketed prefill/decode "
                         "jits, or the ragged token-major single launch "
                         "(paged layout; compare mode always adds a ragged "
                         "path)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="ragged step's padded token capacity per step "
                         "(0 = auto from max_batch/page_size)")
    ap.add_argument("--burst", type=int, default=4,
                    help="arrivals per burst (bursty scenario)")
    ap.add_argument("--period", type=int, default=8,
                    help="steps between bursts (bursty scenario)")
    ap.add_argument("--prefix-cache", default="on", choices=["on", "off"],
                    help="shared-prefix KV page reuse (paged layout); "
                         "compare mode adds a paged_nocache cold twin "
                         "when on")
    ap.add_argument("--quant", default="w4a4_packed",
                    help="uniform backend (deprecated in favor of "
                         "--quant-plan; kept working via a uniform plan)")
    ap.add_argument("--quant-plan", default=None,
                    help="mixed-precision plan: preset name | json path | "
                         "inline pattern=backend rules (core.quant_plan)")
    ap.add_argument("--cache-dtype", default="bfloat16")
    ap.add_argument("--quantized-ckpt", action="store_true",
                    help="serve from a quantized checkpoint (save+restore, "
                         "verify bit-identical vs plan-on-float-masters)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="keep the quantized checkpoint here (default: tmp)")
    ap.add_argument("--sweep", action="store_true",
                    help="add the per-site sensitivity table to the report")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the chaos scenarios' trace + fault "
                         "stream (independent of --seed, which picks the "
                         "model weights)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue: submissions past this "
                         "many waiting requests shed with a typed error "
                         "(0 = unbounded; chaos scenarios default to "
                         "2*max_batch)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace_event JSON timeline "
                         "of the primary layout's run (open at "
                         "ui.perfetto.dev)")
    ap.add_argument("--metrics", default="on", choices=["on", "off"],
                    help="per-engine telemetry registries (off: stats() "
                         "reports empty metrics/recompiles)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON report to this path")
    args = ap.parse_args()

    enable_compile_cache()
    out = serve(
        args.arch, reduced=args.reduced, layers=args.layers,
        layout=args.layout,
        max_batch=args.max_batch, page_size=args.page_size,
        num_pages=args.num_pages, max_ctx=args.max_ctx,
        requests=args.requests, rate=args.rate,
        prompt_lens=tuple(int(x) for x in args.prompt_lens.split(",")),
        gen_lens=tuple(int(x) for x in args.gen_lens.split(",")),
        scenario=args.scenario, sys_len=args.sys_len,
        prefix_cache=args.prefix_cache == "on",
        step=args.step, token_budget=args.token_budget,
        burst=args.burst, period=args.period,
        quant_backend=args.quant, quant_plan=args.quant_plan,
        cache_dtype=args.cache_dtype,
        quantized_ckpt=args.quantized_ckpt, ckpt_dir=args.ckpt_dir,
        sweep=args.sweep, seed=args.seed,
        chaos_seed=args.chaos_seed, max_queue=args.max_queue,
        trace_out=args.trace_out, metrics=args.metrics == "on",
    )
    text = json.dumps(out, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.  For every (architecture x input shape x mesh) cell this lowers and
compiles the real step function against ShapeDtypeStruct inputs on the
production mesh (single-pod 16x16 = 256 chips; multi-pod 2x16x16 = 512),
prints memory/cost analyses, parses collective traffic from the post-SPMD
HLO, and writes a JSON report consumed by EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out reports/dryrun]
  python -m repro.launch.dryrun --arch ... --devices 8 --mesh 2,4   (tests)
"""

# The first two executable lines MUST set XLA_FLAGS before any jax import:
# jax locks the device count on first initialization.
import os
import sys

_DEV = "512"
if "--devices" in sys.argv:
    _DEV = sys.argv[sys.argv.index("--devices") + 1]
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_DEV} "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (  # noqa: E402
    REGISTRY, Runtime, SHAPES, get_config, runnable,
)
from repro.core.quant_plan import pack_for_serving  # noqa: E402
from repro.distributed.sharding import (  # noqa: E402
    make_param_shardings, mesh_context, specs_to_shardings,
)
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    init_train_state, input_specs, make_decode_step, make_prefill_step,
    make_train_step, state_specs,
)
from repro.models import init_model  # noqa: E402


def production_runtime(shape_kind: str, serve_packed: bool = True,
                       **overrides) -> Runtime:
    """Production execution knobs per step kind (§Perf baselines)."""
    base = dict(scan_layers=True, attn_impl="chunked", attn_chunk_q=512,
                loss_chunk=4096, remat="dots")
    if shape_kind == "train":
        base.update(quant_backend="fake_quant")
    else:
        # serving: pre-packed int4 weights + int4 KV cache (the paper's
        # 4-bit format applied to both weight and cache traffic)
        base.update(quant_backend="w4a4_packed" if serve_packed else "float",
                    cache_dtype="int4" if serve_packed else "bfloat16",
                    remat="none")
    base.update(overrides)
    return Runtime(**base)


def probe_runtime(rt: Runtime) -> Runtime:
    """Loop-free cost-probe variant: unrolled layers, materialized attention,
    unchunked loss (HLO contains every FLOP exactly once)."""
    return dataclasses.replace(rt, scan_layers=False, attn_impl="full",
                               loss_chunk=0, remat="none")


def _serve_params_sds(cfg, rt: Runtime, mesh):
    """ShapeDtypeStruct tree (+shardings) for serving params, packed per the
    active QuantPlan (legacy uniform backends map to uniform plans)."""
    def build():
        p = init_model(jax.random.PRNGKey(0), cfg)
        return pack_for_serving(p, cfg, rt)

    sds = jax.eval_shape(build)
    specs = make_param_shardings(sds, mesh)
    shardings = specs_to_shardings(specs, mesh)
    sds = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        sds, shardings)
    return sds, shardings


def lower_cell(
    arch: str,
    shape_name: str,
    mesh,
    *,
    repeats_override: Optional[int] = None,
    probe: bool = False,
    rt_overrides: Optional[Dict] = None,
    serve_packed: bool = True,
):
    """Lower+compile one cell; returns (compiled, lowered, cfg, rt)."""
    cfg = get_config(arch)
    if repeats_override is not None:
        cfg = dataclasses.replace(
            cfg,
            n_layers=repeats_override * len(cfg.pattern) + len(cfg.tail),
        )
    shape = SHAPES[shape_name]
    rt = production_runtime(shape.kind, serve_packed=serve_packed,
                            **(rt_overrides or {}))
    if probe:
        rt = probe_runtime(rt)

    with mesh_context(mesh):
        specs = input_specs(cfg, shape, mesh, rt)
        if shape.kind == "train":
            state_sds, state_shard = state_specs(cfg, mesh)
            fn = make_train_step(cfg, rt)
            lowered = jax.jit(fn, donate_argnums=(0,)).lower(
                state_sds, specs["batch"])
        elif shape.kind == "prefill":
            params_sds, _ = _serve_params_sds(cfg, rt, mesh)
            fn = make_prefill_step(cfg, rt)
            lowered = jax.jit(fn, donate_argnums=(2,)).lower(
                params_sds, specs["tokens"], specs["caches"])
        else:
            params_sds, _ = _serve_params_sds(cfg, rt, mesh)
            fn = make_decode_step(cfg, rt)
            lowered = jax.jit(fn, donate_argnums=(2,)).lower(
                params_sds, specs["token"], specs["caches"],
                specs["positions"])
        compiled = lowered.compile()
    return compiled, lowered, cfg, rt


def _mem_fields(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    out = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    out["total_hbm_bytes"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0)
    )
    return out


def _cost_fields(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, mesh=None,
             probes=(2, 4), rt_overrides=None, serve_packed=True,
             skip_probes=False) -> Dict:
    t0 = time.time()
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    pod_size = n_dev // mesh.shape.get("pod", 1)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    report: Dict = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(mesh.shape), "devices": n_dev,
        "multi_pod": multi_pod,
    }
    if not runnable(cfg, shape):
        report["status"] = "skipped"
        report["reason"] = ("long_500k requires sub-quadratic attention; "
                            f"{arch} is full-attention (DESIGN.md §4)")
        return report

    # ---- 1. production compile (scan-over-layers): memory analysis --------
    compiled, lowered, cfg_full, rt = lower_cell(
        arch, shape_name, mesh, rt_overrides=rt_overrides,
        serve_packed=serve_packed)
    report["memory"] = _mem_fields(compiled)
    report["cost_scanned_body_once"] = _cost_fields(compiled)
    report["status"] = "ok"

    # ---- 2. cost probes (unrolled, loop-free), linear extrapolation -------
    if not skip_probes:
        probe_data = {}
        for r in probes:
            c_p, l_p, _, _ = lower_cell(
                arch, shape_name, mesh, repeats_override=r, probe=True,
                rt_overrides=rt_overrides, serve_packed=serve_packed)
            cf = _cost_fields(c_p)
            coll = rl.parse_collectives(c_p.as_text(), pod_size=pod_size)
            probe_data[r] = {
                **cf,
                "collective_bytes": coll.total(),
                "collective_by_kind": coll.bytes_by_kind,
                "cross_pod_bytes": coll.cross_pod_bytes,
                "collective_count": coll.count,
            }
        report["probes"] = probe_data
        r_lo, r_hi = min(probes), max(probes)
        R = cfg.n_repeats
        scale = (R - r_lo) / (r_hi - r_lo)

        def extrap(field):
            lo, hi = probe_data[r_lo][field], probe_data[r_hi][field]
            return lo + (hi - lo) * scale

        flops = extrap("flops")
        bytes_acc = extrap("bytes_accessed")
        coll_bytes = extrap("collective_bytes")
        cross_pod = extrap("cross_pod_bytes")

        # ---- 3. roofline terms --------------------------------------------
        mf = rl.model_flops(cfg, shape)
        terms = rl.roofline_terms(flops, bytes_acc, coll_bytes)
        report["roofline"] = {
            **terms,
            "flops_per_dev": flops,
            "bytes_per_dev": bytes_acc,
            "collective_bytes_per_dev": coll_bytes,
            "cross_pod_bytes_per_dev": cross_pod,
            "model_flops_global": mf,
            "model_flops_per_dev": mf / n_dev,
            "useful_flop_ratio": (mf / n_dev) / flops if flops else None,
        }
    report["elapsed_s"] = round(time.time() - t0, 1)
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--devices", type=str, default="512")  # parsed pre-import
    ap.add_argument("--mesh", type=str, default=None,
                    help="override mesh, e.g. '2,4' => data=2, model=4")
    ap.add_argument("--out", type=str, default="reports/dryrun")
    ap.add_argument("--skip-probes", action="store_true")
    ap.add_argument("--serve-float", action="store_true",
                    help="serving cells use bf16 weights (baseline)")
    ap.add_argument("--quant-plan", default=None,
                    help="mixed-precision plan for serving cells: preset "
                         "name | json path | inline pattern=backend rules "
                         "(see core.quant_plan) — cost-model any plan")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    archs = sorted(REGISTRY) if (args.all or args.arch is None) else [args.arch]
    shapes = (sorted(SHAPES) if (args.all or args.shape is None)
              else [args.shape])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    custom_mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        custom_mesh = make_mesh(dims, axes)
        meshes = [len(dims) == 3]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}"
                # the plan override models *serving* deployments; train
                # cells keep their QAT runtime (fake_quant)
                serve_cell = SHAPES[shape].kind != "train"
                try:
                    rep = run_cell(
                        arch, shape, multi_pod=mp, mesh=custom_mesh,
                        skip_probes=args.skip_probes,
                        serve_packed=not args.serve_float,
                        rt_overrides=(
                            {"quant_plan": args.quant_plan}
                            if args.quant_plan and serve_cell else None))
                except Exception as e:  # noqa: BLE001
                    rep = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "FAILED", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                    failures += 1
                with open(os.path.join(args.out, key + ".json"), "w") as f:
                    json.dump(rep, f, indent=1, default=str)
                status = rep["status"]
                extra = ""
                if "roofline" in rep:
                    r = rep["roofline"]
                    extra = (f" bound={r['bound']}"
                             f" t=({r['compute_s']:.2e},{r['memory_s']:.2e},"
                             f"{r['collective_s']:.2e})s"
                             f" useful={r['useful_flop_ratio']:.2f}"
                             if r.get("useful_flop_ratio") else "")
                if "memory" in rep:
                    extra += f" hbm/dev={rep['memory']['total_hbm_bytes']/2**30:.2f}GiB"
                print(f"[{status:7s}] {key}{extra}", flush=True)
    print(f"done; {failures} failures")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

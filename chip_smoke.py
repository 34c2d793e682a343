"""Chip smoke run: the serving main path, end to end, on one TPU.

Serves full-width qwen2-0.5b (24 layers, d 896, 14 query / 2 KV heads,
d_ff 4864, vocab 151,936; random weights from --seed) under the default
plan (w4a4_packed -> int4_matmul_fused) on the paged KV layout, through
the entry points a user calls: ``build_params``, ``InferenceEngine`` and
``run_trace``.  Phases, in order, each failure fatal:

  a. build the full-width serving params;
  b. serve 8 requests (prompts of 128-1024 tokens, 32 new tokens each)
     with the bucketed step and flash prefill;
  c. serve the same trace with the ragged step;
     after b and c, every op those two served must have gone through its
     compiled Pallas kernel;
  d. compare the kernel path's prefill logits and a few decode steps with
     a pure-XLA path: under the default plan against the path that
     computes the same integers (plan int_sim, chunked attention, gathered
     paged decode), and under the weight-only plan w4a16_packed against
     its dequantized weights in a plain float GEMM; then each main-path
     kernel against its XLA reference at the served shapes.

It refuses to run anywhere but a TPU, and under ``REPRO_PALLAS_INTERPRET``:
a run that silently fell back to the CPU or the interpreter proves nothing.
Times it prints come from a smoke run, not a benchmark.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # 2x2 host: DP x TP training vs 1 chip

The last line of standard output is one JSON object, printed only when
every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from typing import Dict, Optional, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ArchConfig, Runtime, ServingConfig, get_config  # noqa: E402,E501
from repro.core.quant import pack_int4, unpack_int4  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.dispatch import ENV_INTERPRET  # noqa: E402
from repro.kernels.ragged_attention import ragged_attention_xla  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import serving_runtime as served_runtime  # noqa: E402,E501
from repro.models.attention import attention_core  # noqa: E402
from repro.models.transformer import decode_step, forward  # noqa: E402
from repro.observability import global_registry  # noqa: E402
from repro.serving.api import TraceItem, run_trace  # noqa: E402
from repro.serving.engine import InferenceEngine, build_params  # noqa: E402
from repro.serving.kv_pages import (  # noqa: E402
    init_paged_caches, paged_read, with_block_tables)
from repro.serving.scheduler import OK  # noqa: E402

#: Kernel path vs pure-XLA path, end to end: relative RMS error of the
#: logits over the vocab.  Both paths compute the same int4 GEMM integers,
#: but the XLA attention rounds its probabilities to bf16 where the kernels
#: keep f32 (a 0.25% difference in one attention output), and every
#: dynamic int4 activation quantization after it amplifies such a
#: difference.  Measured on CPU with the kernels' XLA twins: 0.31 after one
#: full-width layer, 0.62-0.69 at 8-24 layers.  Logits that share nothing
#: with the reference sit at sqrt(2) (measured 1.39-1.44 with the prompt
#: reversed).  1.0 lies between the two: this check only tells a right
#: model from an unrelated one.  The weight-only check below is the tight
#: end-to-end one.
LOGITS_REL_RMS_TOL = 1.0

#: The same comparison under the weight-only plan w4a16_packed, against the
#: dequantized weights in a float GEMM.  No activation is quantized, so a
#: rounding difference is not amplified: what remains is the XLA
#: attention's bf16 probabilities and the reference's bf16 weights.
#: Measured 0.020-0.023 on a v5e at full width (0.013-0.015 on CPU at 8
#: layers).  Faults planted in the kernel path at 8 layers on CPU: decode
#: one position off 0.33, one of 16 context pages unwritten 0.37, one
#: layer's output projection wrong 0.70.
W4A16_LOGITS_REL_RMS_TOL = 5e-2

#: Each main-path kernel against its pure-XLA reference on the same inputs
#: at the served shapes, relative RMS error.  GEMM integers are exact; only
#: a division tie can move one int4 activation code.  The w4a16 kernel
#: contracts in bf16 against an f32 reference (1.7e-3 on a v5e).  The XLA
#: attention rounds probabilities to bf16 (0.25% measured on CPU).
KERNEL_REL_RMS_TOL = 1e-2

#: Ops each served step mode must dispatch, each through a compiled Pallas
#: kernel.  An op missing here means a silent route to plain XLA (such a
#: route calls no op at all, so only the served phases' own counts show it).
SERVED_OPS = {
    "bucketed": ("int4_matmul_fused_kmajor", "flash_prefill",
                 "paged_decode_attention"),
    "ragged": ("int4_matmul_fused_kmajor", "ragged_paged_attention"),
}

#: Four-chip training: the DP x TP run and the one-chip run see the same
#: params and batches; only the order of the bf16/f32 reductions differs.
#: The QAT forward fake-quantizes activations to int4, which amplifies that
#: difference: 1.4e-3 to 3.4e-3 relative on a 2x2 v5e at full width (4e-5
#: at reduced width on CPU).  The loss sees a fault in the forward (a
#: skipped TP reduction); the warmup learning rate is too small for the
#: update to move it, so it cannot see a fault in the gradient.
TRAIN_LOSS_RTOL = 1e-2

#: The pre-clip global gradient norm of each step, mesh vs one chip: it
#: sees the DP gradient reduction.  Measured 9.3e-4 to 6.6e-3 relative on a
#: 2x2 v5e at full width (4.4e-4 at reduced width on 4 virtual CPU
#: devices).  A gradient from half the batch moved it by 0.13 to 0.31 on
#: one v5e at full width, and by 0.13 to 0.59 at reduced width on CPU.
TRAIN_GNORM_RTOL = 5e-2


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """The run's shapes. The defaults are the one-chip run; a CPU test
    passes a reduced `cfg` and smaller values."""

    cfg: ArchConfig
    prompt_lens: Tuple[int, ...] = (128, 1024, 200, 700, 512, 900, 333, 1000)
    gen_len: int = 32
    max_batch: int = 8
    page_size: int = 16
    max_ctx: int = 2048
    check_len: int = 512           # prompt tokens of the logits check
    check_decode_steps: int = 4
    seed: int = 0

    @property
    def num_pages(self) -> int:
        """A pool that holds every request of the trace at once."""
        ps = self.page_size
        return sum(-(-(L + self.gen_len) // ps) for L in self.prompt_lens)


def serving_runtime(sc: SmokeConfig, plan: str = "w4a4_packed") -> Runtime:
    """What ``launch/serve.py`` serves with: flash prefill, fused paged
    decode, the default plan unless another is named."""
    return served_runtime(sc.max_ctx, quant_backend=plan)


def reference_runtime(sc: SmokeConfig) -> Runtime:
    """The pure-XLA path over the same packed weights and integers."""
    return dataclasses.replace(serving_runtime(sc), quant_backend="int_sim",
                               attn_impl="chunked", paged_attn="gather")


def float_runtime(sc: SmokeConfig) -> Runtime:
    """Plain XLA over float weights: the reference of the weight-only
    plan, given its weights dequantized (`dequantize_packed`)."""
    return dataclasses.replace(serving_runtime(sc), quant_backend="float",
                               attn_impl="chunked", paged_attn="gather")


def dequantize_packed(params):
    """Every packed weight of a serving tree as the float weight it
    encodes (per-channel scales)."""
    def dequant(w):
        if not (isinstance(w, dict) and "packed" in w):
            return w
        assert w["scale"].shape[-2] == 1, "per-channel scales only"
        return (unpack_int4(w["packed"], axis=-1).astype(jnp.float32)
                * w["scale"].astype(jnp.float32))

    return jax.tree.map(dequant, params,
                        is_leaf=lambda t: isinstance(t, dict) and "packed" in t)


# ------------------------------------------------------------- phases ----
def phase_build(sc: SmokeConfig, plan: str = "w4a4_packed"):
    """(a) Full-width serving params: init, pack, K-major twins."""
    return jax.block_until_ready(
        build_params(sc.cfg, serving_runtime(sc, plan), sc.seed))


def make_trace(sc: SmokeConfig):
    rng = np.random.default_rng(sc.seed)
    return [TraceItem(arrival_step=i,
                      prompt=rng.integers(0, sc.cfg.vocab, L, dtype=np.int32),
                      max_new=sc.gen_len)
            for i, L in enumerate(sc.prompt_lens)]


def phase_serve(sc: SmokeConfig, params, step: str,
                rt: Optional[Runtime] = None) -> Dict:
    """(b)/(c) Serve the trace through the engine with one step mode (the
    served runtime unless `rt` is given).  `dispatch` holds the kernel
    dispatches this phase alone made."""
    sv = ServingConfig(layout="paged", max_batch=sc.max_batch,
                       page_size=sc.page_size, num_pages=sc.num_pages,
                       max_ctx=sc.max_ctx, step=step)
    before = kernel_dispatch()
    engine = InferenceEngine(sc.cfg, rt or serving_runtime(sc), sv,
                             params=params)
    t0 = time.perf_counter()
    engine.warmup(sc.prompt_lens)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    stats, finished = run_trace(engine, make_trace(sc))
    serve_s = time.perf_counter() - t0
    return {
        "step": step,
        "compile_s": compile_s,
        "serve_s": serve_s,
        "requests": len(finished),
        "ok": sum(r.outcome == OK for r in finished),
        "tokens": sum(len(r.tokens) for r in finished),
        "recompiles_steady_state": stats["recompiles"]["steady_state"],
        "dispatch": {key: n - before.get(key, 0)
                     for key, n in kernel_dispatch().items()
                     if n != before.get(key, 0)},
    }


def _rel_rms(a, b) -> float:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _compare(a, b, vocab: int) -> Dict:
    """Relative RMS error and top-1 agreement of logits a vs b [..., V]."""
    a, b = a[..., :vocab], b[..., :vocab]
    top1 = jnp.mean(jnp.argmax(a, -1) == jnp.argmax(b, -1))
    return {"rel_rms": _rel_rms(a, b), "top1": float(top1)}


def phase_check_logits(sc: SmokeConfig, params, rt_k: Runtime,
                       params_x, rt_x: Runtime) -> Dict:
    """(d) Kernel path (`params`, `rt_k`) vs pure-XLA path (`params_x`,
    `rt_x`) on the served model: full prefill logits of one prompt, then
    teacher-forced decode steps over the paged cache."""
    cfg = sc.cfg
    L, n = sc.check_len, sc.check_decode_steps
    sv = ServingConfig(layout="paged", max_batch=1, page_size=sc.page_size,
                       num_pages=sc.max_ctx // sc.page_size,
                       max_ctx=sc.max_ctx)
    tbl = jnp.arange(sv.pages_per_seq, dtype=jnp.int32)[None]
    rng = np.random.default_rng(sc.seed + 1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (1, L), dtype=np.int32))
    positions = jnp.arange(L, dtype=jnp.int32)[None]

    def prefill_and_cache(p, rt):
        caches = with_block_tables(init_paged_caches(cfg, rt, 0, sv), tbl)
        fn = jax.jit(lambda p, t, c: forward(p, t, cfg, rt, positions, c,
                                             update_cache=True)[:2])
        return fn(p, tokens, caches)

    logits_k, cache_k = prefill_and_cache(params, rt_k)
    logits_x, cache_x = prefill_and_cache(params_x, rt_x)
    out = {"prefill": _compare(logits_k, logits_x, cfg.vocab)}

    dec_k = jax.jit(lambda p, t, c, pos: decode_step(p, t, cfg, rt_k, c, pos))
    dec_x = jax.jit(lambda p, t, c, pos: decode_step(p, t, cfg, rt_x, c, pos))
    tok = jnp.argmax(logits_k[:, -1, :cfg.vocab], -1)[:, None]
    steps_k, steps_x = [], []
    for i in range(n):
        pos = jnp.full((1, 1), L + i, jnp.int32)
        lk, cache_k = dec_k(params, tok, cache_k, pos)
        lx, cache_x = dec_x(params_x, tok, cache_x, pos)
        steps_k.append(lk)
        steps_x.append(lx)
        tok = jnp.argmax(lk[:, :cfg.vocab], -1)[:, None]   # teacher-forced
    out["decode"] = _compare(jnp.stack(steps_k), jnp.stack(steps_x),
                             cfg.vocab)
    finite = bool(jnp.isfinite(logits_k).all()
                  & jnp.isfinite(jnp.stack(steps_k)).all())
    out["finite"] = finite
    return out


def phase_check_kernels(sc: SmokeConfig) -> Dict[str, float]:
    """(d) Each main-path kernel at the served widths (decode batch, full
    context, one full-length prompt) against its pure-XLA reference on the
    same random inputs: relative RMS error per op."""
    cfg, B, ps = sc.cfg, sc.max_batch, sc.page_size
    H, KV, hd, D, F = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model, \
        cfg.d_ff
    pps = sc.max_ctx // ps
    k = jax.random.split(jax.random.PRNGKey(sc.seed + 2), 8)
    out = {}

    x = jax.random.normal(k[0], (B, D), jnp.bfloat16)
    w = pack_int4(jax.random.randint(k[1], (D, F), -8, 8, jnp.int8), axis=-1)
    w_scale = jax.random.uniform(k[2], (1, F), jnp.float32, 1e-3, 1e-2)
    out["int4_matmul_fused"] = _rel_rms(
        ops.int4_matmul_fused(x, w, w_scale),
        ref.int4_matmul_fused_ref(x, w, w_scale))
    out["w4a16_matmul"] = _rel_rms(
        ops.w4a16_matmul(x, w, w_scale, D),
        ref.w4a16_matmul_ref(x, w, w_scale, D))

    pool = (B * pps, ps, KV, hd)
    k_pool = jax.random.normal(k[3], pool, jnp.bfloat16)
    v_pool = jax.random.normal(k[4], pool, jnp.bfloat16)
    tbl = jax.random.permutation(k[5], B * pps).reshape(B, pps)
    last = jnp.linspace(0, sc.max_ctx - 1, B).astype(jnp.int32)
    q = jax.random.normal(k[6], (B, H, hd), jnp.bfloat16)
    kf, vf, kpos = paged_read({"k": k_pool, "v": v_pool, "tbl": tbl}, last)
    gathered = attention_core(q[:, None], kf, vf, q_positions=last[:, None],
                              k_positions=kpos, window=0, impl="full",
                              chunk_q=sc.max_ctx)[:, 0]
    out["paged_decode_attention"] = _rel_rms(
        ops.paged_decode_attention(q, k_pool, v_pool, tbl, last), gathered)
    slots = jnp.arange(B, dtype=jnp.int32)
    out["ragged_paged_attention"] = _rel_rms(
        ops.ragged_paged_attention(q, k_pool, v_pool, tbl, slots, last),
        ragged_attention_xla(q, k_pool, v_pool, tbl, slots, last))

    S = sc.check_len
    qkv = jax.random.normal(k[7], (3, 1, S, H, hd), jnp.bfloat16)
    kk, vv = qkv[1][:, :, :KV], qkv[2][:, :, :KV]
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    out["flash_prefill"] = _rel_rms(
        ops.flash_prefill(qkv[0], kk, vv, pos, pos),
        attention_core(qkv[0], kk, vv, q_positions=pos, k_positions=pos,
                       window=0, impl="chunked", chunk_q=S))
    return out


def kernel_dispatch() -> Dict[Tuple[str, str], float]:
    """{(op, mode): count} from the process-wide dispatch counters."""
    out = {}
    for key, val in global_registry().snapshot()["counters"].items():
        if key.startswith("kernel_dispatch_total{"):
            labels = dict(re.findall(r'(\w+)="([^"]*)"', key))
            out[(labels["op"], labels["mode"])] = val
    return out


def check_dispatch(counts: Dict[Tuple[str, str], float],
                   expected: Tuple[str, ...] = (),
                   mode: str = "pallas") -> None:
    """Every dispatch in `counts` went to a kernel in `mode` (compiled
    Pallas on the chip), and each `expected` op was dispatched at all."""
    off = sorted(f"{op}={m}" for op, m in counts if m != mode)
    if off:
        raise AssertionError(f"ops dispatched off the {mode} kernels: {off}")
    missing = [op for op in expected if (op, mode) not in counts]
    if missing:
        raise AssertionError(f"ops never dispatched to a kernel: {missing}")


def check_serve(res: Dict) -> None:
    if res["ok"] != res["requests"]:
        raise AssertionError(f"{res['step']}: {res['ok']}/{res['requests']} "
                             "requests finished ok")
    if res["recompiles_steady_state"]:
        raise AssertionError(f"{res['step']}: "
                             f"{res['recompiles_steady_state']} "
                             "steady-state recompiles")


def check_logits(res: Dict, tol: float) -> None:
    if not res["finite"]:
        raise AssertionError("kernel path emitted non-finite logits")
    for phase in ("prefill", "decode"):
        if not res[phase]["rel_rms"] <= tol:
            raise AssertionError(
                f"{phase} logits: relative RMS error "
                f"{res[phase]['rel_rms']} > {tol} "
                "(kernel path vs pure-XLA path)")


def check_kernels(res: Dict[str, float]) -> None:
    bad = {op: r for op, r in res.items() if not r <= KERNEL_REL_RMS_TOL}
    if bad:
        raise AssertionError(f"kernels off their XLA references beyond "
                             f"{KERNEL_REL_RMS_TOL} relative RMS: {bad}")


def phase_train_mesh(arch: str, *, reduced: bool, steps: int, batch: int,
                     seq: int, mesh: str = "2,2", seed: int = 0) -> Dict:
    """DP x TP training through ``launch.train`` on a mesh, against the same
    batches on one device."""
    from repro.launch.train import train

    g_mesh, g_one = [], []
    t0 = time.perf_counter()
    _, h_mesh = train(arch, steps=steps, batch=batch, seq=seq,
                      reduced=reduced, ckpt_dir=None, mesh_spec=mesh,
                      seed=seed, grad_norms=g_mesh)
    mesh_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, h_one = train(arch, steps=steps, batch=batch, seq=seq,
                     reduced=reduced, ckpt_dir=None, seed=seed,
                     grad_norms=g_one)
    return {"loss_mesh": h_mesh, "loss_one": h_one, "gnorm_mesh": g_mesh,
            "gnorm_one": g_one, "mesh_s": mesh_s,
            "one_s": time.perf_counter() - t0}


def _max_rel(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a) / np.asarray(b) - 1)))


def check_train(res: Dict) -> None:
    for key, tol in (("loss", TRAIN_LOSS_RTOL), ("gnorm", TRAIN_GNORM_RTOL)):
        rel = _max_rel(res[f"{key}_mesh"], res[f"{key}_one"])
        if not rel <= tol:
            raise AssertionError(f"{key}: mesh vs one chip differ by {rel} "
                                 f"relative > {tol}")


# --------------------------------------------------------------- main ----
def _say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _device() -> Dict:
    if os.environ.get(ENV_INTERPRET) is not None:
        sys.exit(f"chip_smoke: {ENV_INTERPRET} is set; the smoke run "
                 "proves the compiled kernels and refuses the interpreter")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
                 "refusing to run on anything else")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _dispatch_text(counts) -> str:
    return ", ".join(f"{op}[{mode}]={int(v)}"
                     for (op, mode), v in sorted(counts.items()))


def _say_logits(name: str, res: Dict, secs: float, tol: float) -> None:
    _say(f"d logits {name}, kernel path vs pure-XLA path ({secs:.3f} s): "
         f"prefill rel_rms {res['prefill']['rel_rms']:.6f} top1 "
         f"{res['prefill']['top1']:.4f}; decode rel_rms "
         f"{res['decode']['rel_rms']:.6f} top1 {res['decode']['top1']:.4f}"
         f"; tolerance rel_rms <= {tol}")


def main_one_chip(sc: SmokeConfig) -> None:
    t0 = time.perf_counter()
    params = phase_build(sc)
    _say(f"a build_params: {time.perf_counter() - t0:.3f} s")
    for step in ("bucketed", "ragged"):
        res = phase_serve(sc, params, step)
        _say(f"{'b' if step == 'bucketed' else 'c'} serve step={step}: "
             f"cold compile {res['compile_s']:.3f} s, serve "
             f"{res['serve_s']:.3f} s, {res['ok']}/{res['requests']} ok, "
             f"{res['tokens']} tokens generated, recompiles_steady_state "
             f"{res['recompiles_steady_state']}, kernel_dispatch_total "
             f"{_dispatch_text(res['dispatch'])}")
        check_serve(res)
        check_dispatch(res["dispatch"], SERVED_OPS[step])
    t0 = time.perf_counter()
    res = phase_check_logits(sc, params, serving_runtime(sc), params,
                             reference_runtime(sc))
    _say_logits("w4a4_packed vs int_sim", res, time.perf_counter() - t0,
                LOGITS_REL_RMS_TOL)
    check_logits(res, LOGITS_REL_RMS_TOL)
    del params
    t0 = time.perf_counter()
    params = phase_build(sc, "w4a16_packed")
    res = phase_check_logits(sc, params, serving_runtime(sc, "w4a16_packed"),
                             dequantize_packed(params), float_runtime(sc))
    _say_logits("w4a16_packed vs dequantized float", res,
                time.perf_counter() - t0, W4A16_LOGITS_REL_RMS_TOL)
    check_logits(res, W4A16_LOGITS_REL_RMS_TOL)
    del params
    res = phase_check_kernels(sc)
    _say("d kernels vs XLA references, rel_rms: " + ", ".join(
        f"{op} {r:.3e}" for op, r in res.items())
        + f"; tolerance <= {KERNEL_REL_RMS_TOL}")
    check_kernels(res)
    counts = kernel_dispatch()
    _say(f"kernel_dispatch_total (whole run) {_dispatch_text(counts)}")
    check_dispatch(counts)
    _say(f"peak_bytes_in_use {_peak_bytes()}")


def main_four_chips(seed: int) -> None:
    res = phase_train_mesh("qwen2-0.5b", reduced=False, steps=3, batch=8,
                           seq=256, seed=seed)
    _say(f"train 2x2 DP x TP: {res['mesh_s']:.3f} s, losses "
         f"{res['loss_mesh']}, grad norms {res['gnorm_mesh']}; one chip: "
         f"{res['one_s']:.3f} s, losses {res['loss_one']}, grad norms "
         f"{res['gnorm_one']}; rtol loss {TRAIN_LOSS_RTOL}, grad norm "
         f"{TRAIN_GNORM_RTOL}")
    check_train(res)
    _say(f"peak_bytes_in_use (device 0) {_peak_bytes()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only DP x TP training on a 2x2 mesh against "
                         "one chip (needs a four-chip host)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = _device()
    if args.four_chips and device["count"] != 4:
        sys.exit(f"chip_smoke: --four-chips needs 4 chips, "
                 f"found {device['count']}")
    _say(f"device {device['kind']} x{device['count']} "
         "(smoke run: times are not a benchmark)")
    _say(f"compile cache {enable_compile_cache()}")
    if args.four_chips:
        main_four_chips(args.seed)
    else:
        main_one_chip(SmokeConfig(cfg=get_config("qwen2-0.5b"),
                                  seed=args.seed))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

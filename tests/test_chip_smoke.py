"""chip_smoke.py off the chip: its phase functions at reduced size (the
Pallas kernels through the interpreter), its refusal to run without a TPU
or under the interpreter override, and its four-chip training phase on 4
virtual CPU devices."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402
from repro.configs import get_config  # noqa: E402


@pytest.fixture
def small(monkeypatch):
    """qwen2-0.5b's head grouping (G = 7) at toy widths, kernels
    interpreted."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    cfg = get_config("qwen2-0.5b").reduced(
        n_layers=2, n_heads=14, n_kv_heads=2, head_dim=16, d_model=112)
    return cs.SmokeConfig(cfg=cfg, prompt_lens=(8, 20, 13), gen_len=3,
                          max_batch=4, page_size=8, max_ctx=64,
                          check_len=24, check_decode_steps=2)


def test_phases_at_reduced_size(small):
    params = cs.phase_build(small)
    for step in ("bucketed", "ragged"):
        res = cs.phase_serve(small, params, step)
        cs.check_serve(res)
        assert res["ok"] == 3 and res["tokens"] == 3 * small.gen_len
        # off the chip the kernels run in the interpreter: each op the
        # served path needs went through a kernel, none through its twin
        cs.check_dispatch(res["dispatch"], cs.SERVED_OPS[step],
                          mode="interpret")
    res = cs.phase_check_logits(small, params, cs.serving_runtime(small),
                                params, cs.reference_runtime(small))
    cs.check_logits(res, cs.LOGITS_REL_RMS_TOL)
    assert 0 <= res["prefill"]["top1"] <= 1
    params = cs.phase_build(small, "w4a16_packed")
    res = cs.phase_check_logits(
        small, params, cs.serving_runtime(small, "w4a16_packed"),
        cs.dequantize_packed(params), cs.float_runtime(small))
    cs.check_logits(res, cs.W4A16_LOGITS_REL_RMS_TOL)
    res = cs.phase_check_kernels(small)
    cs.check_kernels(res)
    assert res["int4_matmul_fused"] == 0.0      # the same integers, exactly


def test_served_route_off_the_kernels_fails_the_dispatch_check(small):
    """A served path whose GEMMs silently take plain XLA (the int_sim
    route over packed weights) calls no GEMM op at all: only the served
    phase's own dispatch counts show the hole."""
    params = cs.phase_build(small)
    res = cs.phase_serve(small, params, "bucketed",
                         rt=cs.reference_runtime(small))
    cs.check_serve(res)
    with pytest.raises(AssertionError,
                       match="never dispatched.*int4_matmul_fused"):
        cs.check_dispatch(res["dispatch"], cs.SERVED_OPS["bucketed"],
                          mode="interpret")


def test_w4a16_logits_check_catches_a_wrong_layer(small):
    """The weight-only end-to-end check fails when one layer of the kernel
    path serves wrong weights (the w4a4 check cannot be this tight)."""
    params = cs.phase_build(small, "w4a16_packed")
    other = cs.phase_build(dataclasses.replace(small, seed=7),
                           "w4a16_packed")
    attn = params["layers"]["u0"]["attn"]
    wo = jax.tree.map(lambda a, b: a.at[1].set(b[1]), attn["wo"],
                      other["layers"]["u0"]["attn"]["wo"])
    faulty = {**params, "layers": {**params["layers"], "u0": {
        **params["layers"]["u0"], "attn": {**attn, "wo": wo}}}}
    res = cs.phase_check_logits(
        small, faulty, cs.serving_runtime(small, "w4a16_packed"),
        cs.dequantize_packed(params), cs.float_runtime(small))
    with pytest.raises(AssertionError, match="relative RMS error"):
        cs.check_logits(res, cs.W4A16_LOGITS_REL_RMS_TOL)


def test_check_dispatch_catches_a_route_off_the_kernels():
    expected = cs.SERVED_OPS["bucketed"]
    counts = {(op, "pallas"): 1 for op in expected}
    cs.check_dispatch(counts, expected)
    with pytest.raises(AssertionError, match="off the pallas kernels"):
        cs.check_dispatch({**counts, ("flash_prefill", "xla"): 1}, expected)
    del counts[("paged_decode_attention", "pallas")]
    with pytest.raises(AssertionError, match="never dispatched"):
        cs.check_dispatch(counts, expected)


def _run(env_extra, *args, n_devices=None, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k != "REPRO_PALLAS_INTERPRET"}
    env.update(env_extra)
    if n_devices:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{n_devices} " + env.get("XLA_FLAGS", ""))
    return subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("env_extra", [
    {"JAX_PLATFORMS": "cpu"},
    {"JAX_PLATFORMS": "cpu", "REPRO_PALLAS_INTERPRET": "1"},
], ids=["no_tpu", "interpret_set"])
def test_refuses_to_run_off_the_chip(env_extra):
    out = _run(env_extra, os.path.join(REPO, "chip_smoke.py"))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_four_chip_phase_on_virtual_devices():
    """The --four-chips path (DP x TP training through launch.train on a
    2x2 mesh vs one device) at reduced size on 4 virtual CPU devices."""
    script = (
        "import json, chip_smoke as cs\n"
        "res = cs.phase_train_mesh('qwen2-0.5b', reduced=True, steps=2,"
        " batch=4, seq=32)\n"
        "cs.check_train(res)\n"
        "print(json.dumps(res))\n")
    out = _run({"JAX_PLATFORMS": "cpu"}, "-c", script, n_devices=4)
    assert out.returncode == 0, out.stdout + out.stderr
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(res["loss_mesh"]) == len(res["loss_one"]) == 2


#: Faults planted in the mesh run only, each with the numbers a lost
#: collective would give replica 0: "dp" takes the gradient of its half of
#: the global batch (the DP gradient reduction never ran; the forward, and
#: so the loss, is right), "tp" keeps only the first TP shard's partial
#: sum of the FFN down-projection (the TP all-reduce after it never ran).
_FAULTS = r"""
import jax.numpy as jnp
import repro.launch.train as T
from repro.distributed.sharding import current_mesh
from repro.models import lm_loss

real = T.make_train_step

def planted(cfg, rt, **kw):
    step = real(cfg, rt, **kw)

    def train_step(state, batch):
        if current_mesh() is None:
            return step(state, batch)
        if FAULT == "dp":
            new, out = step(state, batch[: batch.shape[0] // 2])
            out["loss"] = lm_loss(state["params"], batch, cfg, rt)[0]
            return new, out
        p = state["params"]
        u = p["layers"]["u0"]
        w = u["ffn"]["w_out"]
        w = w.at[:, w.shape[1] // 2:].set(0)
        p = {**p, "layers": {**p["layers"], "u0": {
            **u, "ffn": {**u["ffn"], "w_out": w}}}}
        return step({**state, "params": p}, batch)

    return train_step

T.make_train_step = planted
"""


@pytest.mark.parametrize("fault", ["dp", "tp"])
def test_four_chip_check_catches_planted_faults(fault):
    """check_train fails on a DP x TP run with a lost collective; the lost
    DP gradient reduction leaves the loss right, and only the gradient
    norm sees it."""
    script = (
        "import json, chip_smoke as cs\n"
        f"FAULT = {fault!r}\n" + _FAULTS +
        "res = cs.phase_train_mesh('qwen2-0.5b', reduced=True, steps=2,"
        " batch=4, seq=32)\n"
        "try:\n"
        "    cs.check_train(res)\n"
        "    res['caught'] = ''\n"
        "except AssertionError as e:\n"
        "    res['caught'] = str(e)\n"
        "print(json.dumps(res))\n")
    out = _run({"JAX_PLATFORMS": "cpu"}, "-c", script, n_devices=4)
    assert out.returncode == 0, out.stdout + out.stderr
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["caught"], res
    if fault == "dp":
        assert res["caught"].startswith("gnorm"), res
        assert cs._max_rel(res["loss_mesh"], res["loss_one"]) \
            <= cs.TRAIN_LOSS_RTOL

"""Distribution-layer tests on 8 fake host devices (subprocess: the device
count must be fixed before jax initializes, so each test execs a script).

Covers: DP x TP train-step numerical equivalence vs single device, MoE
shard_map path vs local path, pipeline parallelism, elastic checkpoint
restore across mesh shapes, and dry-run machinery on a small mesh.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_fake_devices(script: str, n: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=timeout, cwd=REPO,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_dp_tp_train_step_matches_single_device():
    """Global loss/grads on a (2,4) mesh == single-device values."""
    run_fake_devices(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, Runtime
from repro.distributed.sharding import (
    make_param_shardings, mesh_context, specs_to_shardings)
from repro.launch.mesh import make_mesh
from repro.models import init_model, lm_loss

cfg = get_config("qwen3-4b").reduced(n_layers=2, d_model=64, n_heads=4,
                                     n_kv_heads=2, d_ff=128, vocab=256)
rt = Runtime(loss_chunk=0, compute_dtype="float32", quant_backend="float")
params = init_model(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab)

l_single = float(lm_loss(params, toks, cfg, rt)[0])

mesh = make_mesh((2, 4), ("data", "model"))
with mesh_context(mesh):
    specs = make_param_shardings(params, mesh)
    p_sharded = jax.device_put(params, specs_to_shardings(specs, mesh))
    loss_fn = jax.jit(lambda p, t: lm_loss(p, t, cfg, rt)[0])
    l_mesh = float(loss_fn(p_sharded, toks))
np.testing.assert_allclose(l_mesh, l_single, rtol=1e-5)
print("OK", l_single, l_mesh)
""")


def test_moe_shard_map_matches_local():
    """MoE through shard_map (EP over model + FSDP gather) == local path."""
    run_fake_devices(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, Runtime
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_mesh
from repro.models.moe import apply_moe, init_moe

cfg = get_config("arctic-480b").reduced(
    n_experts=8, d_model=64, d_ff_expert=64, capacity_factor=64.0)
rt = Runtime(quant_backend="float", compute_dtype="float32")
p = init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))

y_local, aux_local = apply_moe(p, x, cfg, rt)

mesh = make_mesh((2, 4), ("data", "model"))
with mesh_context(mesh):
    fn = jax.jit(lambda p, x: apply_moe(p, x, cfg, rt))
    y_mesh, aux_mesh = fn(p, x)
np.testing.assert_allclose(np.asarray(y_mesh), np.asarray(y_local),
                           rtol=2e-4, atol=2e-5)
# aux is a per-data-shard estimator (Switch-style): close, not identical
np.testing.assert_allclose(float(aux_mesh), float(aux_local), rtol=0.1)
print("OK")
""")


def test_moe_shard_map_packed_experts_match_local():
    """Packed int4 experts (planar K-major: w_in [E, D/2, F], w_out
    [E, F/2, D]) through shard_map, FSDP-gathered along their packed rows,
    serve the same output as the local path."""
    run_fake_devices(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, Runtime
from repro.core.qlinear import QuantConfig, pack_weight_nd
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_mesh
from repro.models.moe import apply_moe, init_moe

cfg = get_config("arctic-480b").reduced(
    n_experts=8, d_model=64, d_ff_expert=64, capacity_factor=64.0)
rt = Runtime(quant_backend="w4a4_packed", compute_dtype="float32")
p = init_moe(jax.random.PRNGKey(0), cfg)
qc = QuantConfig(backend="w4a4_packed")
p["experts"] = {k: pack_weight_nd(w, qc) for k, w in p["experts"].items()}
assert p["experts"]["w_out"]["packed"].shape == (8, 32, 64)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64))

y_local, _ = apply_moe(p, x, cfg, rt)

mesh = make_mesh((2, 4), ("data", "model"))
with mesh_context(mesh):
    fn = jax.jit(lambda p, x: apply_moe(p, x, cfg, rt))
    y_mesh, _ = fn(p, x)
np.testing.assert_allclose(np.asarray(y_mesh), np.asarray(y_local),
                           rtol=2e-4, atol=2e-5)
print("OK")
""")


def test_moe_shard_map_gradients_match_local():
    run_fake_devices(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, Runtime
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_mesh
from repro.models.moe import apply_moe, init_moe

cfg = get_config("llama4-maverick-400b-a17b").reduced(
    n_experts=8, d_model=64, d_ff_expert=64, capacity_factor=64.0)
rt = Runtime(quant_backend="float", compute_dtype="float32")
p = init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64))

def loss(p, x):
    # y-only loss: the aux estimator is per-shard (see matches_local test)
    y, aux = apply_moe(p, x, cfg, rt)
    return jnp.sum(y ** 2)

g_local = jax.grad(loss)(p, x)
mesh = make_mesh((2, 4), ("data", "model"))
with mesh_context(mesh):
    g_mesh = jax.jit(jax.grad(loss))(p, x)
for a, b in zip(jax.tree.leaves(g_local), jax.tree.leaves(g_mesh)):
    np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                               rtol=5e-4, atol=1e-5)
print("OK")
""")


def test_pipeline_parallel_stages():
    """GPipe pipeline over a 4-stage mesh == sequential application."""
    run_fake_devices(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_apply
from repro.launch.mesh import make_mesh

mesh = make_mesh((4,), ("stage",))
n_stages, n_micro, mb, d = 4, 8, 4, 16
ws = jax.random.normal(jax.random.PRNGKey(0), (n_stages, d, d)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (n_micro * mb, d))

def stage_fn(w, xb):
    return jnp.tanh(xb @ w)

y_ref = x
for s in range(n_stages):
    y_ref = stage_fn(ws[s], y_ref)

y = pipeline_apply(stage_fn, ws, x, mesh=mesh, n_micro=n_micro)
np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-5,
                           atol=2e-6)
print("OK")
""")


def test_elastic_checkpoint_across_mesh_shapes(tmp_path):
    """Save params sharded on (2,4); restore onto (4,2) and single device."""
    run_fake_devices(rf"""
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.distributed.sharding import (
    make_param_shardings, mesh_context, specs_to_shardings)
from repro.launch.mesh import make_mesh
from repro.models import init_model

cfg = get_config("qwen3-4b").reduced(n_layers=2, d_model=64, n_heads=4,
                                     n_kv_heads=2, d_ff=128, vocab=256)
params = init_model(jax.random.PRNGKey(0), cfg)
mesh_a = make_mesh((2, 4), ("data", "model"))
sh_a = specs_to_shardings(make_param_shardings(params, mesh_a), mesh_a)
p_a = jax.device_put(params, sh_a)

mgr = CheckpointManager(r"{tmp_path}", save_every=1)
mgr.maybe_save(1, p_a, force=True)

mesh_b = make_mesh((4, 2), ("data", "model"))
sh_b = specs_to_shardings(make_param_shardings(params, mesh_b), mesh_b)
p_b, step = mgr.restore(params, shardings=sh_b)
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p_b)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32))
p_c, _ = mgr.restore(params)          # plain single-device restore
print("OK", step)
""")


def test_dryrun_machinery_small_mesh():
    """The dry-run entry point end-to-end on a 2x4 fake mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "qwen2-0.5b", "--shape", "decode_32k",
         "--devices", "8", "--mesh", "2,4", "--out", "/tmp/dryrun_pytest"],
        env=env, capture_output=True, text=True, timeout=900, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    rep = json.load(
        open("/tmp/dryrun_pytest/qwen2-0.5b__decode_32k__pod1.json"))
    assert rep["status"] == "ok"
    assert rep["memory"]["total_hbm_bytes"] > 0
    assert rep["roofline"]["bound"] in ("compute", "memory", "collective")


def test_train_preemption_restart_bitexact(tmp_path):
    """Kill training mid-run; resume must continue from the checkpoint and
    reach the identical final state as an uninterrupted run."""
    script = rf"""
import numpy as np, jax
from repro.launch.train import train

state1, h1 = train("qwen2-0.5b", steps=6, batch=2, seq=32,
                   ckpt_dir=r"{tmp_path}/a", save_every=3, seed=7)

# interrupted run: first 3 steps, then a fresh process restores and finishes
state2a, _ = train("qwen2-0.5b", steps=3, batch=2, seq=32,
                   ckpt_dir=r"{tmp_path}/b", save_every=3, seed=7)
state2b, h2 = train("qwen2-0.5b", steps=6, batch=2, seq=32,
                    ckpt_dir=r"{tmp_path}/b", save_every=3, seed=7)

for a, b in zip(jax.tree.leaves(state1["params"]),
                jax.tree.leaves(state2b["params"])):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=1e-6)
print("OK")
"""
    run_fake_devices(script, n=1, timeout=900)

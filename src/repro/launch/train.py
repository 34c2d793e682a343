"""End-to-end trainer with fault tolerance.

Runs the same `make_train_step` the dry-run lowers, over the deterministic
synthetic pipeline, with: atomic checkpoint/resume, per-step watchdog
(straggler/hang detection), bounded retry, optional mesh (single device on
CPU; DP x TP on real slices / fake devices).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --reduced --steps 300 --batch 8 --seq 128 --ckpt /tmp/run1
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import Runtime, get_config
from repro.data import SyntheticLMDataset, make_batch_iterator
from repro.distributed.fault_tolerance import StepTimer, Watchdog, run_with_retries
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_mesh
from repro.launch.steps import init_train_state, make_train_step, state_specs

log = logging.getLogger("repro.train")


def train(
    arch: str,
    *,
    steps: int = 100,
    batch: int = 8,
    seq: int = 128,
    reduced: bool = True,
    ckpt_dir: Optional[str] = "/tmp/repro_ckpt",
    save_every: int = 50,
    mesh_spec: str = "",
    peak_lr: float = 3e-4,
    quant_backend: str = None,
    step_deadline_s: float = 600.0,
    log_every: int = 10,
    seed: int = 0,
    grad_norms: Optional[list] = None,
):
    """Train `steps` steps; returns (final state, per-step losses).  Each
    step's pre-clip global gradient norm is appended to `grad_norms` when a
    list is given."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    rt = Runtime(scan_layers=True, attn_impl="chunked",
                 attn_chunk_q=min(512, seq), loss_chunk=0,
                 quant_backend=quant_backend)
    mesh = None
    if mesh_spec:
        dims = tuple(int(x) for x in mesh_spec.split(","))
        mesh = make_mesh(dims, ("data", "model")[:len(dims)] if len(dims) == 2
                         else ("pod", "data", "model"))

    ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                            seed=seed)
    # ckpt_dir=None trains without checkpoints (a full-width train state is
    # three times the params: a smoke run has no use for writing it)
    mgr = (CheckpointManager(ckpt_dir, save_every=save_every, keep=3)
           if ckpt_dir else None)

    with mesh_context(mesh):
        state = init_train_state(jax.random.PRNGKey(seed), cfg)
        start_step = 0
        latest = mgr.latest() if mgr is not None else None
        if latest is not None:
            state, start_step = mgr.restore(state)
            log.info("resumed from step %d", start_step)
        if mesh is not None:
            # params over TP and the Adam moments over the whole mesh: the
            # optimizer state is twice the params, and left unsharded it
            # would all sit on the first device
            _, shardings = state_specs(cfg, mesh)
            state = jax.device_put(state, shardings)

        step_fn = jax.jit(make_train_step(cfg, rt, peak_lr=peak_lr,
                                          total_steps=max(steps, 1)),
                          donate_argnums=(0,))
        it = make_batch_iterator(ds, start_step=start_step)
        timer = StepTimer()
        history = []
        wd = Watchdog(deadline_s=step_deadline_s)
        for step in range(start_step, steps):
            batch_np = next(it)

            def one_step():
                with wd:
                    return step_fn(state, jnp.asarray(batch_np))

            timer.start()
            state, metrics = run_with_retries(one_step, max_retries=2)
            dt = timer.stop()
            loss, gnorm = float(metrics["loss"]), float(metrics["grad_norm"])
            history.append(loss)
            if grad_norms is not None:
                grad_norms.append(gnorm)
            if step % log_every == 0 or step == steps - 1:
                log.info("step %5d loss %.4f gnorm %.3f lr %.2e %.0f ms",
                         step, loss, gnorm, float(metrics["lr"]), dt * 1e3)
            if mgr is not None:
                mgr.maybe_save(step + 1, state)
        it.close()
        if mgr is not None:
            mgr.maybe_save(steps, state, force=True)
    return state, history


def main():
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full (non-reduced) config — real-hardware scale")
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--mesh", default="", help="e.g. '2,4' (data,model)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--quant", default=None,
                    help="override quant backend (float|fake_quant|int_sim)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    _, history = train(
        args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        reduced=not args.full, ckpt_dir=args.ckpt, save_every=args.save_every,
        mesh_spec=args.mesh, peak_lr=args.lr, quant_backend=args.quant,
        seed=args.seed,
    )
    print(json.dumps({"first_loss": history[0], "last_loss": history[-1],
                      "steps": len(history)}))


if __name__ == "__main__":
    main()

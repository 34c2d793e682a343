"""Atomic, multihost-aware, elastic checkpointing.

Layout:  <dir>/step_<N>/
             manifest.json          (step, tree structure, shapes, dtypes,
                                     mesh metadata, process count)
             shard_<p>.npz          (one file per host: that host's
                                     addressable param shards, fully
                                     replicated params only on host 0)

Properties needed at 1000+-node scale and tested here:

  * **atomicity** -- writes go to `step_<N>.tmp_<uuid>` then `os.replace`
    into place; a crash mid-save never corrupts the latest checkpoint;
  * **resume-from-latest** -- `latest_step` scans for complete manifests
    (incomplete/tmp dirs are ignored and garbage-collected);
  * **elastic restore** -- arrays are saved logically (full value per leaf,
    assembled host-side); `restore_checkpoint` re-`device_put`s them under
    *any* new mesh/sharding, so a job may restart on a different pod count;
  * **retention** -- keep-last-k garbage collection.

On multi-host runs each host saves only `jax.process_index()` files; in this
single-process container that degenerates to one shard file, but the code
paths are written for N processes.
"""

from __future__ import annotations

import json
import os
import shutil
import time
import uuid
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _flatten(tree) -> Dict[str, Any]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for kp, leaf in flat:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        out[key] = leaf
    return out


def save_checkpoint(directory: str, step: int, tree: Any,
                    extra_meta: Optional[Dict] = None) -> str:
    """Atomic save. Returns the final checkpoint path."""
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + f".tmp_{uuid.uuid4().hex[:8]}"
    os.makedirs(tmp, exist_ok=True)

    flat = _flatten(tree)
    arrays, dtypes = {}, {}
    for k, v in flat.items():
        a = np.asarray(jax.device_get(v))
        dtypes[k] = a.dtype.name            # logical dtype (pre-conversion)
        if a.dtype.name == "bfloat16":      # npz has no bf16: store f32 (lossless)
            a = a.astype(np.float32)
        arrays[k] = a
    pidx = jax.process_index()
    np.savez(os.path.join(tmp, f"shard_{pidx}.npz"), **arrays)

    if pidx == 0:
        manifest = {
            "step": step,
            "time": time.time(),
            "process_count": jax.process_count(),
            "keys": sorted(arrays.keys()),
            "shapes": {k: list(v.shape) for k, v in arrays.items()},
            "dtypes": dtypes,
        }
        manifest.update(extra_meta or {})
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
    os.replace(tmp, final) if not os.path.exists(final) else shutil.rmtree(tmp)
    return final


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        path = os.path.join(directory, name)
        if name.startswith("step_") and ".tmp_" in name:
            shutil.rmtree(path, ignore_errors=True)      # gc partial saves
            continue
        if name.startswith("step_") and \
                os.path.exists(os.path.join(path, "manifest.json")):
            steps.append(int(name.split("_")[1]))
    return max(steps) if steps else None


def _load_shards(path: str, manifest: Dict) -> Dict[str, np.ndarray]:
    """Assemble every host's shard file into one {key: array} map."""
    data: Dict[str, np.ndarray] = {}
    for p in range(manifest["process_count"]):
        fn = os.path.join(path, f"shard_{p}.npz")
        if os.path.exists(fn):
            with np.load(fn) as z:
                for k in z.files:
                    data[k] = z[k]
    return data


def restore_checkpoint(directory: str, step: int, like: Any,
                       shardings: Optional[Any] = None) -> Any:
    """Restore into the structure of `like`; if `shardings` (a pytree of
    jax.sharding.Sharding) is given, device_put each leaf accordingly --
    this is the elastic-resharding path (new mesh shape is fine)."""
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = _load_shards(path, manifest)

    flat_like, treedef = jax.tree_util.tree_flatten_with_path(like)
    flat_shard = (jax.tree_util.tree_leaves(shardings)
                  if shardings is not None else [None] * len(flat_like))
    leaves = []
    for (kp, leaf), shd in zip(flat_like, flat_shard):
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        arr = data[key]
        assert tuple(arr.shape) == tuple(leaf.shape), (key, arr.shape, leaf.shape)
        if shd is not None:
            leaves.append(jax.device_put(arr, shd))
        else:
            leaves.append(jnp.asarray(arr, dtype=leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ----------------------------------------------------------- quantized ----
#: manifest tag identifying serving-ready packed checkpoints.  v2 holds the
#: planar K-major weight layout (kernels/packing.py); v1 held the retired
#: N-major layout (adjacent output columns per byte).
QUANTIZED_FORMAT = "quantized-v2"


def save_quantized(directory: str, step: int, params, cfg, rt=None,
                   plan=None, min_size: int = 1 << 12) -> str:
    """Quantize-and-save: pack float-master `params` per the active
    QuantPlan (every quantized-serving site becomes uint8 planar K-major
    nibbles + bf16 scales — ~4x smaller artifacts than float masters) and
    store the plan itself in the manifest.  Reuses the atomic `.tmp_` +
    os.replace machinery of `save_checkpoint`.

    Pass either `plan` (a QuantPlan) or `rt` (a Runtime whose
    quant_plan/quant_backend selects one).  Returns the checkpoint path.
    """
    from repro.core.quant_plan import (
        CKPT_PACKED, active_plan, plan_pack_tree, plan_to_dict,
    )

    if plan is None:
        assert rt is not None, "save_quantized needs a plan or a Runtime"
        plan = active_plan(cfg, rt)
    site_backends: Dict[str, str] = {}
    packed = plan_pack_tree(params, cfg, plan, min_size=min_size,
                            backends=CKPT_PACKED, scale_dtype=jnp.bfloat16,
                            site_log=site_backends)
    # per-site backend record: which kernel family each packed site's
    # nibbles were laid out for.  restore_quantized checks it against the
    # serving plan so e.g. a lut4 site rebuilds table-lookup serving
    # instead of silently dropping to nibble-unpack w4a4.
    return save_checkpoint(
        directory, step, packed,
        extra_meta={"format": QUANTIZED_FORMAT, "arch": cfg.name,
                    "plan": plan_to_dict(plan),
                    "site_backends": site_backends})


def restore_quantized(directory: str, step: Optional[int] = None,
                      *, cfg=None, rt=None):
    """Restore a quantized checkpoint into a serving-ready packed tree —
    no float master, no `like` template, no re-pack at load.  The tree is
    rebuilt directly from the manifest keys (uint8 nibbles stay uint8;
    bf16 leaves round-trip bit-exactly through the f32 npz encoding).

    The restored tree only serves correctly under the plan it was saved
    with — per-site backends and the packed/float split are baked into the
    weights.  Pass the serving `cfg` + `rt` to assert their active plan
    matches the stored one (strongly recommended: a mismatched Runtime
    would silently route packed sites through the wrong backend math).

    Returns (params_tree, manifest); the stored plan is
    `quant_plan.plan_from_dict(manifest["plan"])`.
    """
    if step is None:
        step = latest_step(directory)
        assert step is not None, f"no checkpoint in {directory}"
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    if manifest.get("format") == "quantized-v1":
        raise ValueError(
            f"{path} holds the old N-major int4 layout (quantized-v1), "
            f"which this version no longer reads; re-pack it from float "
            f"masters with save_quantized")
    assert manifest.get("format") == QUANTIZED_FORMAT, (
        f"{path} is not a quantized checkpoint "
        f"(format={manifest.get('format')!r}); use restore_checkpoint")
    if cfg is not None and rt is not None:
        from repro.core.quant_plan import active_plan, plan_from_dict

        stored = plan_from_dict(manifest["plan"])
        live = active_plan(cfg, rt)
        # per-site first: when the plans diverge, name the exact site and
        # backend pair that would serve wrong-kernel math (the manifest's
        # site_backends map was recorded at pack time; older checkpoints
        # without it fall through to the whole-plan rules check)
        for site, saved_be in manifest.get("site_backends", {}).items():
            live_be = live.resolve(site).backend
            assert live_be == saved_be, (
                f"site {site!r} does not match the plan this checkpoint was "
                f"saved with: packed for backend {saved_be!r} but the "
                f"runtime plan {live.name!r} resolves it to {live_be!r}; "
                f"restoring would serve the wrong kernel math — set "
                f"Runtime.quant_plan to the stored plan ({stored.name!r})")
        assert live.rules == stored.rules, (
            f"runtime plan {live.name!r} does not match the plan this "
            f"checkpoint was saved with ({stored.name!r}); set "
            f"Runtime.quant_plan to the stored plan")
    data = _load_shards(path, manifest)

    tree: Dict[str, Any] = {}
    for key in manifest["keys"]:
        leaf = jnp.asarray(data[key],
                           dtype=jnp.dtype(manifest["dtypes"][key]))
        node = tree
        parts = key.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return tree, manifest


class CheckpointManager:
    """save-every-N + keep-last-k + resume; preemption-safe."""

    def __init__(self, directory: str, save_every: int = 100, keep: int = 3):
        self.directory = directory
        self.save_every = save_every
        self.keep = keep
        os.makedirs(directory, exist_ok=True)

    def maybe_save(self, step: int, tree: Any, force: bool = False,
                   extra_meta: Optional[Dict] = None):
        if force or (step > 0 and step % self.save_every == 0):
            save_checkpoint(self.directory, step, tree, extra_meta)
            self._gc()
            return True
        return False

    def _gc(self):
        steps = sorted(
            int(n.split("_")[1]) for n in os.listdir(self.directory)
            if n.startswith("step_") and ".tmp_" not in n
        )
        for s in steps[: -self.keep]:
            shutil.rmtree(
                os.path.join(self.directory, f"step_{s:08d}"),
                ignore_errors=True,
            )

    def latest(self) -> Optional[int]:
        return latest_step(self.directory)

    def restore(self, like: Any, shardings=None, step: Optional[int] = None):
        step = step if step is not None else self.latest()
        assert step is not None, "no checkpoint to restore"
        return restore_checkpoint(self.directory, step, like, shardings), step

"""Block-size (bm, bn, bk) autotuner for the quantized-GEMM Pallas kernels.

The paper's FPGA argument — the same exact multiplier, specialized to the
fabric — translates on TPU to tile shapes specialized per deployment GEMM
shape.  This module owns that specialization:

  * ``get_blocks(op, M, K, N, ...)`` — the lookup every call site (qdense,
    and through it models/ffn.py, models/attention.py and the serving
    engine) goes through instead of hard-coded tiles.  Returns the tuned
    entry when one exists, else a shape-clipped heuristic default.  Never
    triggers a search by itself: lookups happen inside jit traces and must
    stay cheap and deterministic.
  * ``tune(...)`` — the timed search.  Run explicitly (``benchmarks/run.py
    kernels`` on a TPU host, or ``REPRO_AUTOTUNE=1``); results persist to an
    on-disk JSON cache keyed by (op, shape, dtype, group size, backend).

Cache location: ``$REPRO_AUTOTUNE_CACHE`` if set, else
``~/.cache/repro/autotune.json``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np

from repro.observability.metrics import global_registry

ENV_CACHE_PATH = "REPRO_AUTOTUNE_CACHE"
ENV_AUTOTUNE = "REPRO_AUTOTUNE"

# What a rejected candidate tile may legitimately raise: bad block/grid
# shapes (ValueError, or AssertionError from the wrappers' divisibility
# contracts), a kernel with no lowering on this backend
# (NotImplementedError), or an XLA compile/runtime failure.  The tuner
# skips these; real programming errors propagate.
_TILE_REJECT_ERRORS = (ValueError, AssertionError, NotImplementedError,
                       jax.errors.JaxRuntimeError)

# in-memory mirror of the on-disk cache: key -> {"bm","bn","bk","us"}
_CACHE: Dict[str, Dict] = {}
_LOADED_FROM: Optional[str] = None


def cache_path() -> str:
    env = os.environ.get(ENV_CACHE_PATH)
    if env:
        return os.path.expanduser(env)
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def cache_key(op: str, M: int, K: int, N: int, dtype: str,
              group_size: int = 0, backend: str = "", tag: str = "") -> str:
    backend = backend or jax.default_backend()
    key = f"{op}|m{M}|k{K}|n{N}|{dtype}|g{group_size}|{backend}"
    return f"{key}|{tag}" if tag else key


def reset() -> None:
    """Drop in-memory state (tests; cache file is untouched)."""
    global _LOADED_FROM
    _CACHE.clear()
    _LOADED_FROM = None


def load_cache(path: Optional[str] = None) -> int:
    """Merge the on-disk cache into memory; returns #entries loaded.
    A missing or corrupt file is an empty cache, never an error."""
    global _LOADED_FROM
    path = path or cache_path()
    _LOADED_FROM = path
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return 0
    if not isinstance(data, dict):
        return 0
    n = 0
    for key, entry in data.items():
        if isinstance(entry, dict) and {"bm", "bn", "bk"} <= set(entry):
            _CACHE[key] = entry
            n += 1
    return n


def save_cache(path: Optional[str] = None) -> str:
    path = path or cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(_CACHE, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def ensure_loaded() -> None:
    if _LOADED_FROM is None:
        load_cache()


# ----------------------------------------------------------- heuristics ----
def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


#: attention ops reuse the (bm, bn, bk) entry format with attention
#: semantics — bk = kv tokens per program (for ``attn.paged_decode`` and
#: ``attn.ragged`` that is pages_per_program * page_size, with page_size
#: riding in the key's group_size slot), bn = KV-head tile (0 = all heads,
#: kernels self-heal to a divisor), bm = q tile (prefill only; decode and
#: ragged rows carry one query token each).
ATTN_OPS = ("attn.paged_decode", "attn.prefill", "attn.ragged")

#: page-walking ops share the paged-decode heuristics (and therefore, on
#: untuned hosts, the same pages-per-program — which keeps ragged decode
#: rows bit-identical to the bucketed decode path's blocked XLA twin)
_PAGED_ATTN_OPS = ("attn.paged_decode", "attn.ragged")


def attn_default_blocks(op: str, M: int, K: int, N: int,
                        group_size: int = 0) -> Dict[str, int]:
    """Heuristic tiles for the attention ops (shapes: M = batch rows, q
    length or packed token rows, K = kv context length, N = H * hd)."""
    if op in _PAGED_ATTN_OPS:
        ps = max(1, group_size)
        # small pages pay per-page gather overhead: cap the block at ~256
        # tokens so the XLA twin's page index stays narrow; larger pages
        # amortize and take 512-token blocks
        target = 256 if ps < 8 else 512
        bk = max(ps, min(_round_up(K, ps), _round_up(target, ps)))
        return {"bm": 1, "bn": 0, "bk": bk}
    bq = 128 if M >= 128 else max(8, _round_up(M, 8))
    bk = 128 if K >= 128 else max(8, _round_up(K, 8))
    return {"bm": bq, "bn": 0, "bk": bk}


def attn_candidate_blocks(op: str, M: int, K: int, N: int,
                          group_size: int = 0) -> List[Dict[str, int]]:
    """Search space for the attention ops: kv-tokens-per-program x KV-head
    tiling (and q tiling for prefill)."""
    out, seen = [], set()
    if op in _PAGED_ATTN_OPS:
        ps = max(1, group_size)
        bks = sorted({max(ps, min(_round_up(K, ps), ps * pp))
                      for pp in (1, 4, 8, 32, 128)})
        bms = [1]
    else:
        bks = sorted({min(_round_up(K, 8), b) for b in (64, 128, 256)})
        bms = sorted({min(_round_up(max(M, 8), 8), b) for b in (64, 128, 256)})
    for bm in bms:
        for bn in (0, 2, 4):                       # head tile: all, 2, 4
            for bk in bks:
                key = (bm, bn, bk)
                if key not in seen:
                    seen.add(key)
                    out.append({"bm": bm, "bn": bn, "bk": bk})
    return out


#: the table-lookup GEMM has no MXU dot: per k-step cost is a fori_loop of
#: bk/2 two-level takes, so its sweet spot is smaller bk (shorter in-kernel
#: loop, more grid-level parallelism) and lane-wide bn (each take is a
#: full-width [bm, bn] vector op).  It gets its own candidate set.
LUT4_OP = "gemm.lut4"


def lut4_default_blocks(M: int, K: int, N: int) -> Dict[str, int]:
    bm = 128 if M >= 128 else max(8, _round_up(M, 8))
    bn = min(256, _round_up(N, 128)) if N >= 256 else 128
    bk = min(256, _round_up(K, 2))
    return {"bm": bm, "bn": bn, "bk": max(2, bk)}


def lut4_candidate_blocks(M: int, K: int, N: int) -> List[Dict[str, int]]:
    bms = sorted({b for b in (8, 32, 128) if b <= _round_up(max(M, 8), 8)}
                 | {lut4_default_blocks(M, K, N)["bm"]})
    bns = [b for b in (128, 256) if b <= _round_up(N, 128)] or [128]
    bks = sorted({max(2, _round_up(min(b, K), 2)) for b in (64, 128, 256, 512)})
    out, seen = [], set()
    for bm in bms:
        for bn in bns:
            for bk in bks:
                key = (bm, bn, bk)
                if key not in seen:
                    seen.add(key)
                    out.append({"bm": bm, "bn": bn, "bk": bk})
    return out


def default_blocks(M: int, K: int, N: int, group_size: int = 0) -> Dict[str, int]:
    """Shape-clipped MXU-aligned defaults.

    Constraints the kernels require: bk even (planar halves), and for
    grouped w4a16 scales bk a multiple of 2*group_size (each planar half of
    a k-step covers whole scale groups).  bm tracks small M (decode is
    M=1..batch; a 128-row tile would be >90% padding).
    """
    bm = 128 if M >= 128 else max(8, _round_up(M, 8))
    bn = 128
    step = 2 * group_size if group_size else 2
    bk = min(512, _round_up(K, step))
    bk = max(step, _round_up(bk, step))
    return {"bm": bm, "bn": bn, "bk": bk}


def candidate_blocks(M: int, K: int, N: int, group_size: int = 0
                     ) -> List[Dict[str, int]]:
    """Small MXU-aligned search space, constraint-filtered and deduped."""
    step = 2 * group_size if group_size else 2
    bms = sorted({b for b in (32, 64, 128, 256) if b <= _round_up(max(M, 8), 8)}
                 | {default_blocks(M, K, N, group_size)["bm"]})
    bns = [b for b in (128, 256) if b <= _round_up(N, 128)] or [128]
    bks = sorted({max(step, _round_up(min(b, K), step))
                  for b in (128, 256, 512, 1024)})
    out, seen = [], set()
    for bm in bms:
        for bn in bns:
            for bk in bks:
                key = (bm, bn, bk)
                if key not in seen:
                    seen.add(key)
                    out.append({"bm": bm, "bn": bn, "bk": bk})
    return out


# --------------------------------------------------------------- lookup ----
def get_blocks(op: str, M: int, K: int, N: int, dtype: str,
               group_size: int = 0, tag: str = "") -> Dict[str, int]:
    """Tuned blocks for this GEMM if cached (site-tagged entry first, then
    the shape-generic one), else heuristic defaults.  Cheap + pure: safe to
    call during jit tracing."""
    ensure_loaded()
    for key in ((cache_key(op, M, K, N, dtype, group_size, tag=tag),)
                if tag else ()) + (cache_key(op, M, K, N, dtype, group_size),):
        hit = _CACHE.get(key)
        if hit is not None:
            return {"bm": int(hit["bm"]), "bn": int(hit["bn"]),
                    "bk": int(hit["bk"])}
    if op in ATTN_OPS:
        return attn_default_blocks(op, M, K, N, group_size)
    if op == LUT4_OP:
        return lut4_default_blocks(M, K, N)
    return default_blocks(M, K, N, group_size)


def should_tune() -> bool:
    """Opt-in gate for implicit tuning: TPU hosts or REPRO_AUTOTUNE=1."""
    env = os.environ.get(ENV_AUTOTUNE)
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------- search ----
def _default_timer(fn: Callable[[], object], reps: int = 3,
                   warmup: int = 1) -> float:
    """Median wall-time per call in microseconds."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def tune(op: str, make_call: Callable[[Dict[str, int]], Callable[[], object]],
         M: int, K: int, N: int, dtype: str, *,
         group_size: int = 0, tag: str = "",
         candidates: Optional[Iterable[Dict[str, int]]] = None,
         timer: Callable[[Callable[[], object]], float] = _default_timer,
         path: Optional[str] = None, save: bool = True
         ) -> Tuple[Dict[str, int], float]:
    """Time `make_call(blocks)()` over the candidate set, persist the best.

    `make_call` binds the kernel arguments and returns a zero-arg callable
    (one jit signature per block shape).  A candidate that fails to compile
    or run is skipped; when every candidate fails, raises RuntimeError.
    Returns (best_blocks, best_us).
    """
    ensure_loaded()
    if candidates is not None:
        cands = list(candidates)
    elif op in ATTN_OPS:
        cands = attn_candidate_blocks(op, M, K, N, group_size)
    elif op == LUT4_OP:
        cands = lut4_candidate_blocks(M, K, N)
    else:
        cands = candidate_blocks(M, K, N, group_size)
    best, best_us, last_err = None, float("inf"), None
    for blocks in cands:
        try:
            us = timer(make_call(blocks))
        except _TILE_REJECT_ERRORS as e:
            # unsupported tile on this backend: bad block/grid shape
            # (ValueError / AssertionError from the wrapper contracts),
            # no Mosaic lowering (NotImplementedError), or a compile/run
            # failure (XlaRuntimeError).  Anything else — TypeError,
            # KeyboardInterrupt, a typo in make_call — propagates.
            global_registry().counter(
                "autotune_tiles_rejected_total",
                "autotune candidates skipped on lowering/compile failure",
                op=op).inc()
            last_err = e
            continue
        if us < best_us:
            best, best_us = blocks, us
    if best is None:
        # every candidate failed: the kernel does not compile or run at
        # this shape on this backend.  Handing back the defaults would hide
        # that until the first serving step fails on the same tiles.
        raise RuntimeError(
            f"autotune {op} M={M} K={K} N={N} {dtype}: all {len(cands)} "
            f"candidate tiles failed on {jax.default_backend()}; last "
            f"error: {type(last_err).__name__}: {last_err}") from last_err
    entry = {**best, "us": best_us}
    _CACHE[cache_key(op, M, K, N, dtype, group_size, tag=tag)] = entry
    if tag:                                # untagged key serves other sites
        _CACHE.setdefault(cache_key(op, M, K, N, dtype, group_size), entry)
    if save:
        save_cache(path)
    return best, best_us

"""Paged KV cache: fixed-size pages from a preallocated pool + block tables.

Device side, the attention layers that share block tables share one cache
dict (a scan-friendly pytree) whose pool leaves are stacked over those
layers, each page lane-dense (a token's KV heads side by side):

    {"tbl": [B, pages_per_seq] int32,            # logical -> physical page
     "k":   [L, num_pages, page_size, KV·hd],    # layer-stacked pool
     "v":   [L, num_pages, page_size, KV·hd],
     (int4: [..., KV·hd/2] packed nibble pairs;
      + "k_scale"/"v_scale" [L, num_pages, page_size, KV] when quantized)}

A float pool holds its values' bits (uint16 for bfloat16, see
``pool_storage``), so that every backend scatters a write into the stack in
place.  A layer's call adds its index, ``"layer"`` (an int32 scalar), to
the dict it hands the attention module (``models.transformer.forward``):
the writes scatter into the stack at that index and the decode kernels
read the stack there, so no layer's slice of the pool is ever copied out
or back.  The attention module dispatches on the ``"tbl"`` key, so the
same model code consumes the contiguous ring cache and the paged pool.
Logical slot ``j`` of a sequence lives in page ``tbl[j // page_size]`` at
slot ``j % page_size``; a gather of a layer's pages reconstructs exactly
the [B, max_ctx, KV, hd] layout of the contiguous cache, which is what
makes paged and contiguous decode bit-identical.

Host side, ``PagedKVCacheManager`` owns the page pool and per-request page
lists; ``ContinuousKVCache`` wraps the static-slot layout behind the same
manager interface (its "pages" are whole cache rows, so `ensure` only checks
the context bound).

Prefix caching (``ServingConfig.prefix_cache``) turns the manager into a
refcounted, content-addressed pool:

  * **Identity.**  Every *full* page is identified by a chained block hash
    (vLLM-style): ``h_i = H(h_{i-1}, tokens[i*ps:(i+1)*ps])``, so a page's
    hash pins the entire token prefix behind it, not just its own tokens.
    Pages are registered in the index the moment they fill (end of prefill
    for prompt pages, decode-step page-boundary crossings for generated
    ones).
  * **Sharing.**  Admission matches the longest indexed page-aligned prefix
    and hands the request those physical pages with ``refcount += 1``; only
    the uncached tail is prefilled.  The hit is capped *below* the full
    prefix so at least one token is always recomputed (its logits seed the
    next token).
  * **Copy-on-write discipline.**  Shared pages are immutable: only full
    pages are ever indexed, hits are page-aligned, and the tail prefill
    starts at the page boundary past the hit — so a writer's positions can
    never land in a page with ``refcount > 1``.  The "partially-filled last
    page" case (a hit that would cover the whole prompt) is resolved by
    capping the hit one page down and re-prefilling that page's tokens into
    a *fresh private page* — copy-on-write implemented as recompute-on-
    write-into-private, which costs at most ``page_size - 1`` tokens and
    needs no device-side page copy.
  * **Eviction.**  ``release`` drops a page's refcount; at zero a registered
    page parks in an LRU of warm pages (still indexed, still hittable —
    this is what makes preempt→resume and repeated system prompts near-
    free) while unregistered pages return to the blank free list.  New
    allocations prefer blank pages and evict the LRU-oldest warm page only
    when the blank list runs dry (``prefix_lru=False`` forgets content at
    release instead).

The device side needs no changes for sharing: block tables simply point
several requests at the same physical pages, and ``paged_write`` routes the
unused table slots' sentinel (page index == num_pages) out of bounds where
writes drop and reads gather zeros.
"""

from __future__ import annotations

from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, Runtime, ServingConfig
from repro.kernels.paged_attention import (
    BITS_OF_FLOAT,
    FLOAT_OF_BITS,
    pool_values,
)
from repro.models.attention import dequantize_kv, quantize_kv
from repro.observability.metrics import NULL_REGISTRY


# ------------------------------------------------------- device-side cache --
def pool_storage(cache_dtype: str):
    """The dtype of the pool leaves for a ``Runtime.cache_dtype``: int8, or
    packed int4 pairs in uint8, beside float32 scales; a float cache holds
    its values' bits (``kernels.paged_attention.FLOAT_OF_BITS``)."""
    if cache_dtype == "int4":
        return jnp.dtype(jnp.uint8)
    if cache_dtype == "int8":
        return jnp.dtype(jnp.int8)
    return BITS_OF_FLOAT[jnp.dtype(jnp.bfloat16 if cache_dtype == "bfloat16"
                                   else jnp.float32)]


def encode_kv(val, storage) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """K or V values [..., KV, hd] as a pool of dtype `storage` holds them:
    lane-dense [..., KV·hd] (packed int4: KV·hd/2) and, when quantized,
    the per-(token, head) scales [..., KV] (else None)."""
    def dense(x):
        return x.reshape(*x.shape[:-2], -1)

    if storage in (jnp.int8, jnp.uint8):
        q, scale = quantize_kv(val, storage == jnp.uint8)
        return dense(q), dense(scale)
    return dense(jax.lax.bitcast_convert_type(
        val.astype(FLOAT_OF_BITS[storage]), storage)), None


def init_paged_attn_cache(cfg: ArchConfig, rt: Runtime, batch: int,
                          sv: ServingConfig, layers: int = 1) -> Dict:
    """The paged cache of `layers` attention layers: one block table
    [batch, pages_per_seq] and the pool stacked over the layers."""
    kv, hd = cfg.n_kv_heads, cfg.hd
    page = (layers, sv.num_pages, sv.page_size)
    storage = pool_storage(rt.cache_dtype)
    width = kv * hd // 2 if storage == jnp.uint8 else kv * hd
    cache = {"tbl": jnp.zeros((batch, sv.pages_per_seq), jnp.int32)}
    for name in ("k", "v"):
        cache[name] = jnp.zeros(page + (width,), storage)
        if rt.cache_dtype in ("int8", "int4"):
            cache[name + "_scale"] = jnp.zeros(page + (kv,), jnp.float32)
    return cache


def init_paged_caches(cfg: ArchConfig, rt: Runtime, batch: int,
                      sv: ServingConfig) -> Dict:
    """Full-model paged caches, mirroring transformer.init_caches' structure
    ({"rep": stacked-over-repeats, "tail": per-layer}): each block of the
    repeat unit holds one pool stacked over the repeats (its block table
    stacked likewise, [n_repeats, B, pages_per_seq]), each tail block a
    pool of one layer.  Paged serving only supports pure-attention stacks
    (SSM/LRU states are O(1) and don't page).
    """
    blocks = tuple(cfg.pattern) + tuple(cfg.tail)
    assert all(bt == "A" for bt in blocks), (
        f"paged KV serving requires an all-attention arch, got {blocks}")
    R = cfg.n_repeats

    def stacked():
        cache = init_paged_attn_cache(cfg, rt, batch, sv, layers=R)
        cache["tbl"] = jnp.zeros((R,) + cache["tbl"].shape, jnp.int32)
        return {"attn": cache}

    return {"rep": {f"u{j}": stacked() for j in range(len(cfg.pattern))},
            "tail": {f"tail{t}": {"attn": init_paged_attn_cache(cfg, rt,
                                                                batch, sv)}
                     for t in range(len(cfg.tail))}}


def _write_stack(cache: Dict, k, v, page, slot) -> Dict:
    """Scatter each token's K and V [..., KV, hd], encoded as the pool holds
    them (`encode_kv`), into the stacked pool at (cache["layer"], page,
    slot), index arrays shaped like the tokens.  A page of num_pages is out
    of bounds and the write drops."""
    out = dict(cache)
    for name, val in (("k", k), ("v", v)):
        for leaf, enc in zip((name, name + "_scale"),
                             encode_kv(val, cache[name].dtype)):
            if enc is not None:
                out[leaf] = cache[leaf].at[cache["layer"], page, slot].set(
                    enc, mode="drop")
    return out


def paged_write(cache: Dict, k, v, abs_pos) -> Dict:
    """Write k/v [B, n, KV, hd] at absolute positions abs_pos [B, n] through
    the block table into layer ``cache["layer"]`` of the stacked pool.
    Negative positions (left-pad / inactive rows) are routed to an
    out-of-bounds page index and dropped.

    One batched scatter per pool leaf straight into the donated stack — no
    per-row host loop, no flat reshape round-trip and no copy of the
    layer's slice — so a step holds the pool once."""
    P, ps = cache["k"].shape[1:3]
    tbl = cache["tbl"]
    logical = jnp.clip(abs_pos // ps, 0, tbl.shape[1] - 1)       # [B, n]
    phys = jnp.take_along_axis(tbl, logical, axis=1)
    page = jnp.where(abs_pos >= 0, phys, P)                      # P => dropped
    return _write_stack(cache, k, v, page, abs_pos % ps)


def ragged_paged_write(cache: Dict, k, v, abs_pos) -> Dict:
    """Token-major twin of ``paged_write``: k/v [1, T, KV, hd] packed rows,
    each routed through the table row its token belongs to
    (``cache["slots"]`` [T], bound by ``with_token_slots``) at absolute
    position ``abs_pos`` [1, T].  Padding rows (slot or position -1) go to
    the out-of-bounds page and are dropped.  Quantization is per token —
    the identical ``quantize_kv`` math to the bucketed writes, so a pool
    filled by chunked ragged steps is bit-identical to one filled by
    bucketed prefill + decode."""
    P, ps = cache["k"].shape[1:3]
    tbl, slots = cache["tbl"], cache["slots"]       # [max_batch, pps], [T]
    pos = abs_pos.reshape(-1)                       # [T]
    logical = jnp.clip(pos // ps, 0, tbl.shape[1] - 1)
    phys = tbl[jnp.clip(slots, 0, tbl.shape[0] - 1), logical]
    page = jnp.where((pos >= 0) & (slots >= 0), phys, P)   # P => dropped
    return _write_stack(cache, k[0], v[0], page, pos % ps)


def paged_read(cache: Dict, last_pos, kv_heads: int):
    """Gather each row's pages of layer ``cache["layer"]`` back into the
    contiguous [B, max_ctx, KV, hd] layout (`kv_heads` KV heads a token).
    last_pos [B] is the newest valid absolute position per row (-1 =
    inactive row); returns (k, v, kpos) with kpos[b, j] = j for valid
    slots, -1 otherwise — the same masking contract as the contiguous
    cache.

    Table slots holding the out-of-bounds sentinel (page index == num_pages,
    the unallocated-slot marker `table_row` writes) gather exact zeros via
    fill-mode indexing — stale pool data behind a dead table entry can never
    leak into the dense layout (attention masks those slots, but a NaN in a
    recycled page would still poison `0 * NaN` in the PV contraction)."""
    ps = cache["k"].shape[2]
    tbl = cache["tbl"]
    B, pps = tbl.shape
    max_ctx = pps * ps

    def gather(pool):                       # [B, max_ctx, KV, w]
        pages = pool.at[cache["layer"], tbl].get(mode="fill", fill_value=0)
        return pages.reshape(B, max_ctx, kv_heads, -1)

    if "k_scale" in cache:
        k = dequantize_kv(gather(cache["k"]), gather(cache["k_scale"]))
        v = dequantize_kv(gather(cache["v"]), gather(cache["v_scale"]))
    else:                                   # bits -> values (zero -> 0.0)
        k, v = (pool_values(gather(cache[name]), False) for name in "kv")
    j = jnp.arange(max_ctx, dtype=jnp.int32)[None, :]
    valid = (j <= last_pos[:, None]) & (last_pos[:, None] >= 0)
    return k, v, jnp.where(valid, j, -1)


# -------------------------------------------------- cache-tree manipulation --
def with_block_tables(caches: Dict, tbl) -> Dict:
    """Rebind every layer's block table to `tbl` [B, pages_per_seq] (the same
    positions are cached in every layer, so tables are shared).  Pool leaves
    are passed through untouched; the batch dim of the result follows `tbl`.
    """
    tbl = jnp.asarray(tbl, jnp.int32)

    def walk(node, stacked):
        out = {}
        for key, val in node.items():
            if isinstance(val, dict):
                out[key] = walk(val, stacked)
            elif key == "tbl":
                out[key] = (jnp.broadcast_to(tbl[None],
                                             (val.shape[0],) + tbl.shape)
                            if stacked else tbl)
            else:
                out[key] = val
        return out

    return {"rep": walk(caches["rep"], True),
            "tail": walk(caches["tail"], False)}


def with_token_slots(caches: Dict, tbl, slots) -> Dict:
    """Bind the ragged step's routing scalars into every attention cache:
    the *whole* block-table matrix `tbl` [max_batch, pages_per_seq] plus a
    per-token table-row vector `slots` [T] (-1 = padding row).  Presence of
    the "slots" leaf is what switches ``models.attention.apply_attention``
    onto the ragged token-major path."""
    tbl = jnp.asarray(tbl, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)

    def walk(node, stacked):
        out = {}
        for key, val in node.items():
            if isinstance(val, dict):
                out[key] = walk(val, stacked)
            elif key == "tbl":
                reps = (val.shape[0],) if stacked else ()
                out[key] = jnp.broadcast_to(tbl[None] if stacked else tbl,
                                            reps + tbl.shape)
                out["slots"] = jnp.broadcast_to(
                    slots[None] if stacked else slots, reps + slots.shape)
            elif key == "slots":
                continue                            # rebound alongside tbl
            else:
                out[key] = val
        return out

    return {"rep": walk(caches["rep"], True),
            "tail": walk(caches["tail"], False)}


def gather_rows(caches: Dict, rows) -> Dict:
    """Slice batch rows out of a contiguous cache tree (rep leaves carry the
    batch at dim 1 under the repeat stacking, tail leaves at dim 0)."""
    r = jnp.asarray(rows, jnp.int32)
    return {"rep": jax.tree.map(lambda l: l[:, r], caches["rep"]),
            "tail": jax.tree.map(lambda l: l[r], caches["tail"])}


def scatter_rows(caches: Dict, sub: Dict, rows) -> Dict:
    """Write a gathered/fresh sub-cache back into the full tree's rows."""
    r = jnp.asarray(rows, jnp.int32)
    return {
        "rep": jax.tree.map(lambda l, s: l.at[:, r].set(s.astype(l.dtype)),
                            caches["rep"], sub["rep"]),
        "tail": jax.tree.map(lambda l, s: l.at[r].set(s.astype(l.dtype)),
                             caches["tail"], sub["tail"]),
    }


# --------------------------------------------------------- host-side managers --
_HASH_SEED = 0x9E3779B97F4A7C15


def _chain_hash(prev: int, tokens: np.ndarray) -> int:
    """Chained block hash: pins the whole prefix behind a page, not just
    the page's own tokens."""
    return hash((prev, np.asarray(tokens, np.int32).tobytes()))


class PagedKVCacheManager:
    """Refcounted, content-addressed page pool + per-request block tables.

    Page ids index the device pool directly.  `ensure(rid, n)` grows rid's
    page list to cover `n` cached tokens and reports whether the pool could
    satisfy it — the scheduler turns a False into a preemption.  With
    ``sv.prefix_cache`` on, `admit_request` shares already-filled pages
    (see the module docstring for the sharing/COW/eviction design); every
    page is always in exactly one of three states:

      blank    -- on `self.blank`, contents meaningless, refcount 0
      warm     -- refcount 0 but still registered in the prefix index
                  (`self.warm`, LRU order); allocatable after blanks run dry
      in use   -- refcount >= 1, owned by that many requests

    which is the invariant the allocator property test asserts.
    """

    def __init__(self, sv: ServingConfig, metrics=None):
        self.sv = sv
        # telemetry registry (observability.metrics); the manager bumps
        # event counters at its natural seams, the engine samples occupancy
        # gauges at step boundaries
        self.metrics = metrics if metrics is not None else NULL_REGISTRY
        self.blank: deque = deque(range(sv.num_pages))
        self.warm: "OrderedDict[int, None]" = OrderedDict()  # refcount-0, indexed
        self.pages: Dict[int, List[int]] = {}
        self.refcount: Dict[int, int] = {}
        self.index: Dict[int, int] = {}        # chain hash -> page
        self.page_hash: Dict[int, int] = {}    # page -> chain hash
        self._chain: Dict[int, Tuple[int, int]] = {}  # rid -> (pages hashed, h)
        self.high_water = 0
        # prefix-cache counters (engine stats surface these)
        self.n_lookups = 0
        self.n_hit_tokens = 0
        self.n_evictions = 0
        # chaos hook (serving/chaos.py): the next N admissions that would
        # allocate pages report capacity failure instead — exercising the
        # all-or-nothing admission path without real pool pressure.  Host-
        # side only; never touches device state.
        self.fail_next_admits = 0

    # -- capacity ---------------------------------------------------------
    @property
    def free(self) -> List[int]:
        """Allocatable pages, blank first then warm in eviction order (kept
        as a property for callers/tests that inspect the free pool)."""
        return list(self.blank) + list(self.warm)

    @property
    def available(self) -> int:
        return len(self.blank) + len(self.warm)

    @property
    def in_use(self) -> int:
        return self.sv.num_pages - self.available

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.sv.page_size)

    def fits_alone(self, n_tokens: int) -> bool:
        """Can a request of this total length run with the whole pool?"""
        return (self.pages_for(n_tokens) <= self.sv.num_pages
                and n_tokens <= self.sv.max_ctx)

    def capacity_desc(self) -> str:
        return (f"max_ctx={self.sv.max_ctx}, "
                f"pool={self.sv.num_pages} pages "
                f"of {self.sv.page_size} tokens")

    # -- allocation -------------------------------------------------------
    def _alloc_page(self) -> Optional[int]:
        """One blank-or-evicted page with no index entry left behind."""
        if self.blank:
            return self.blank.popleft()
        if self.warm:
            page, _ = self.warm.popitem(last=False)      # LRU-oldest
            h = self.page_hash.pop(page)
            del self.index[h]
            self.n_evictions += 1
            self.metrics.counter("prefix_evictions_total",
                                 "warm pages evicted to blank").inc()
            return page
        return None

    def ensure(self, rid: int, n_tokens: int) -> bool:
        """Grow rid's allocation to cover n_tokens cached slots.  New pages
        are private (refcount 1); shared pages arrive via admit_request."""
        if n_tokens > self.sv.max_ctx:
            return False
        have = self.pages.setdefault(rid, [])
        need = self.pages_for(n_tokens) - len(have)
        if need > self.available:
            return False                                  # all-or-nothing
        for _ in range(need):
            page = self._alloc_page()
            self.refcount[page] = 1
            have.append(page)
        self.high_water = max(self.high_water, self.in_use)
        return True

    def release(self, rid: int) -> None:
        """Drop rid's hold on its pages.  Registered pages whose refcount
        hits zero stay warm (indexed, LRU-evictable); unregistered ones
        go blank immediately, as does everything when prefix_lru is off."""
        for p in self.pages.pop(rid, []):
            self.refcount[p] -= 1
            if self.refcount[p]:
                continue
            del self.refcount[p]
            if p in self.page_hash and self.sv.prefix_lru:
                self.warm[p] = None                       # most-recently freed
                self.warm.move_to_end(p)
            else:
                h = self.page_hash.pop(p, None)
                if h is not None:
                    del self.index[h]
                self.blank.append(p)
        self._chain.pop(rid, None)

    # -- prefix cache ------------------------------------------------------
    def _match(self, tokens: np.ndarray) -> Tuple[List[int], int]:
        """Pure longest-indexed-prefix walk: (matched pages, chain hash at
        the match point).  Capped strictly below len(tokens) so a caller
        always recomputes at least the final token (whose logits produce
        the next token) — and therefore never writes a shared page: the
        capped page is re-prefilled into a fresh private one instead
        (recompute-style copy-on-write)."""
        ps = self.sv.page_size
        max_full = max(len(tokens) - 1, 0) // ps
        h = _HASH_SEED
        shared: List[int] = []
        for i in range(max_full):
            h_next = _chain_hash(h, tokens[i * ps:(i + 1) * ps])
            page = self.index.get(h_next)
            if page is None:
                break
            shared.append(page)
            h = h_next
        return shared, h

    def admit_request(self, rid: int, tokens: np.ndarray,
                      n_tokens: int) -> Optional[int]:
        """Admission-time allocation, all-or-nothing: match the prefix
        cache, take shared holds (refcount++) on the matched pages, and
        allocate private pages for the remainder of `n_tokens` slots.
        Returns the hit length in tokens, or None when the request doesn't
        fit — in which case *nothing* changed: no refcounts, no LRU
        touches, no hit counters (a queue head blocked on capacity retries
        every step and must not inflate stats or churn eviction order)."""
        assert rid not in self.pages, f"rid {rid} already holds pages"
        if n_tokens > self.sv.max_ctx:
            return None
        if self.fail_next_admits:
            # injected allocator failure: behave exactly like a capacity
            # miss — nothing held, nothing counted, the request waits
            self.fail_next_admits -= 1
            self.metrics.counter(
                "chaos_alloc_failures_total",
                "admissions failed by the chaos allocator hook").inc()
            return None
        shared, h = self._match(tokens) if self.sv.prefix_cache \
            else ([], _HASH_SEED)
        # shared pages currently warm stop being allocatable once held
        warm_shared = sum(1 for p in shared if not self.refcount.get(p))
        need = self.pages_for(n_tokens) - len(shared)
        if need > self.available - warm_shared:
            return None
        for p in shared:
            if not self.refcount.get(p):
                del self.warm[p]                          # warm -> in use
            self.refcount[p] = self.refcount.get(p, 0) + 1
        have = self.pages[rid] = list(shared)
        for _ in range(max(need, 0)):
            page = self._alloc_page()
            self.refcount[page] = 1
            have.append(page)
        self._chain[rid] = (len(shared), h)
        self.high_water = max(self.high_water, self.in_use)
        if self.sv.prefix_cache:
            self.n_lookups += 1
            self.n_hit_tokens += len(shared) * self.sv.page_size
            self.metrics.counter("prefix_lookups_total",
                                 "admission prefix-cache lookups").inc()
            if shared:
                self.metrics.counter("prefix_hits_total",
                                     "admissions that matched >=1 page").inc()
                self.metrics.counter("prefix_hit_pages_total",
                                     "pages served from the cache").inc(
                                         len(shared))
        return len(shared) * self.sv.page_size

    def register_upto(self, rid: int, tokens: np.ndarray, n_valid: int) -> None:
        """Index every full page of rid's prefix whose contents are written
        (tokens[:n_valid] are cached device-side).  Idempotent and
        incremental: the per-rid chain state resumes where the last call
        stopped.  First-writer-wins — if another page already owns a hash,
        ours stays private (duplicate content, freed back to blank later)."""
        if not self.sv.prefix_cache:
            return
        ps = self.sv.page_size
        have = self.pages.get(rid, [])
        done, h = self._chain.get(rid, (0, _HASH_SEED))
        full = min(n_valid // ps, len(have))
        for i in range(done, full):
            h = _chain_hash(h, tokens[i * ps:(i + 1) * ps])
            page = have[i]
            if h not in self.index and page not in self.page_hash:
                self.index[h] = page
                self.page_hash[page] = h
        self._chain[rid] = (full, h)

    # -- block tables ------------------------------------------------------
    def table_row(self, rid: int) -> np.ndarray:
        """Unallocated logical slots carry the out-of-bounds sentinel
        (== num_pages): `paged_write` drops writes through it and
        `paged_read` gathers zeros — a dead slot can never alias physical
        page 0 and silently resurface another request's data."""
        row = np.full((self.sv.pages_per_seq,), self.sv.num_pages, np.int32)
        have = self.pages.get(rid, [])
        row[: len(have)] = have
        return row

    # -- invariants --------------------------------------------------------
    def check_invariants(self) -> None:
        """Allocator invariants, assertable after any event.  This is the
        single checker shared by the hypothesis allocator property test and
        the chaos harness:

          * blank / warm / in-use partition the pool exactly once
          * refcounts are >= 1 and equal the per-request ownership multiset
          * ``available`` + sum of 1/refcount ownership shares == pool size
          * no request holds the same page twice
          * only registered (sealed, immutable) pages are ever shared
          * warm pages are exactly the registered refcount-0 pages
          * index and page_hash are inverse maps
        """
        blank, warm = set(self.blank), set(self.warm)
        in_use = set(self.refcount)
        assert len(blank) == len(self.blank), "blank list holds duplicates"
        assert not (blank & warm) and not (blank & in_use) \
            and not (warm & in_use), "pool state overlap"
        assert blank | warm | in_use == set(range(self.sv.num_pages)), \
            "pool partition incomplete"
        assert all(c >= 1 for c in self.refcount.values())
        shares = sum(1.0 / self.refcount[p]
                     for pages in self.pages.values() for p in pages)
        assert abs(self.available + shares - self.sv.num_pages) < 1e-9, \
            "ownership shares + free pages != pool"
        owners: Dict[int, int] = {}
        for rid, pages in self.pages.items():
            assert len(set(pages)) == len(pages), \
                f"rid {rid} holds a page twice"
            for p in pages:
                owners[p] = owners.get(p, 0) + 1
        assert owners == self.refcount, "refcounts disagree with ownership"
        for p, c in self.refcount.items():
            if c > 1:
                assert p in self.page_hash, f"unsealed page {p} shared"
        assert all(p in self.page_hash for p in warm), \
            "warm page lost its registration"
        assert self.index == {h: p for p, h in self.page_hash.items()}, \
            "index/page_hash out of sync"

    # -- snapshot ----------------------------------------------------------
    def state(self) -> Dict:
        """Host-side allocator state for engine.snapshot(): everything
        needed to resume page accounting exactly.  Note the prefix-index
        keys are Python hashes — stable within a process (the chaos
        stop/resume path), but a snapshot restored in a *different* process
        needs PYTHONHASHSEED pinned for warm-page hits to survive; shared
        in-use page structure restores correctly regardless."""
        return {
            "blank": list(self.blank),
            "warm": list(self.warm),
            "pages": {rid: list(p) for rid, p in self.pages.items()},
            "refcount": dict(self.refcount),
            "index": dict(self.index),
            "page_hash": dict(self.page_hash),
            "chain": dict(self._chain),
            "high_water": self.high_water,
            "n_lookups": self.n_lookups,
            "n_hit_tokens": self.n_hit_tokens,
            "n_evictions": self.n_evictions,
        }

    def load_state(self, st: Dict) -> None:
        self.blank = deque(st["blank"])
        self.warm = OrderedDict((p, None) for p in st["warm"])
        self.pages = {rid: list(p) for rid, p in st["pages"].items()}
        self.refcount = dict(st["refcount"])
        self.index = dict(st["index"])
        self.page_hash = dict(st["page_hash"])
        self._chain = dict(st["chain"])
        self.high_water = st["high_water"]
        self.n_lookups = st["n_lookups"]
        self.n_hit_tokens = st["n_hit_tokens"]
        self.n_evictions = st["n_evictions"]
        self.check_invariants()


class ContinuousKVCache:
    """The contiguous (static-slot) layout behind the same manager interface:
    each batch slot owns a full max_ctx cache row, so `ensure` only checks
    the context bound and there is nothing to allocate, share, or preempt."""

    def __init__(self, sv: ServingConfig, metrics=None):
        self.sv = sv
        self.metrics = metrics if metrics is not None else NULL_REGISTRY
        self.high_water = 0
        self.n_lookups = 0
        self.n_hit_tokens = 0
        self.n_evictions = 0

    @property
    def available(self) -> int:
        return 1 << 30

    def pages_for(self, n_tokens: int) -> int:
        return 0

    def fits_alone(self, n_tokens: int) -> bool:
        return n_tokens <= self.sv.max_ctx

    def capacity_desc(self) -> str:
        return f"max_ctx={self.sv.max_ctx}"

    def ensure(self, rid: int, n_tokens: int) -> bool:
        return n_tokens <= self.sv.max_ctx

    def release(self, rid: int) -> None:
        pass

    def admit_request(self, rid: int, tokens, n_tokens: int) -> Optional[int]:
        return 0 if n_tokens <= self.sv.max_ctx else None

    def register_upto(self, rid: int, tokens, n_valid: int) -> None:
        pass

    def table_row(self, rid: int) -> Optional[np.ndarray]:
        return None

    def check_invariants(self) -> None:
        pass                       # nothing allocated, nothing to violate

    def state(self) -> Dict:
        return {}

    def load_state(self, st: Dict) -> None:
        pass

"""Inference engine: drives jit'd prefill/decode steps over the scheduled
batch with per-request state tracking and latency/throughput stats.

One `step()` is a decode-step boundary: admit (+prefill) newly-arrived
requests, preempt if the page pool is dry, run one decode step for the
running set, retire finished requests.  Greedy decoding (argmax), which is
what the bit-exactness harness compares across KV layouts.

Batch construction is identical for both layouts — running requests compacted
in slot order, padded to the nearest bucket with inactive rows (position -1:
attention masks them and their cache writes are dropped) — so paged and
contiguous runs of the same trace execute the same program shapes and the
same per-row math.  The layouts differ only in where KV bytes live:

  * paged      -- pool + block tables live on device; the whole decode step
                  is one jit (table gather + forward + fused paged attention
                  + greedy argmax) with the cache pool donated through it.
                  Block-table rows move host->device only when a request is
                  admitted or its page allocation grows — never per step.
  * contiguous -- each slot owns a max_ctx row; admission scatters a freshly
                  prefilled row into the full cache (an O(cache) copy that the
                  paged layout exists to avoid — see EXPERIMENTS.md §Serving).

`profile()` attributes one decode step's cost: the attention op is timed
standalone (the same kernels.ops dispatch the model executes) against the
full step time, so perf PRs can tell attention regressions from GEMM ones.
The result is stamped with the step counter at capture time
(``profile_at_step`` in ``stats()``), so a report can't silently pair a
warmup-window profile with end-of-run stats.

Telemetry (``repro.observability``): every engine owns a `Telemetry`
bundle — a metrics registry fed at the natural seams (TTFT/ITL histograms
at retire time, queue/pool gauges at step boundaries, token counters at
prefill/decode), ``engine.*`` spans at each phase boundary of a step
(``observability/trace.py`` lists them), recorded on the profiler's clock
whenever a profiler runs, and a jit recompile sentinel polled after every
prefill/decode call.  All of it is host-side bookkeeping off the traced
path: telemetry on vs off is token-identical by construction.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, Runtime, ServingConfig
from repro.core.quant_plan import pack_for_serving
from repro.distributed.fault_tolerance import StepDeadlineExceeded, Watchdog
from repro.kernels import autotune
from repro.launch.steps import make_ragged_step, make_serving_steps
from repro.observability import COUNT_BUCKETS, Telemetry
from repro.models import init_caches, init_model
from repro.serving.kv_pages import (
    ContinuousKVCache,
    PagedKVCacheManager,
    gather_rows,
    init_paged_caches,
    scatter_rows,
    with_block_tables,
    with_token_slots,
)
from repro.serving.scheduler import (
    CANCELLED,
    ERROR,
    OK,
    SHED,
    TIMEOUT,
    Request,
    Scheduler,
    ShedError,
)


class EngineStuckError(RuntimeError):
    """run_until_idle() exhausted its step budget with work still queued or
    running — a wedged engine must be loud, not a silent return.  Carries
    the stuck state so an operator (or the chaos harness) can see *what*
    is wedged without re-running under a debugger."""

    def __init__(self, max_steps: int, queued, running,
                 pool_in_use: int, pool_pages: int):
        self.max_steps = max_steps
        self.queued = list(queued)
        self.running = list(running)
        self.pool_in_use = pool_in_use
        self.pool_pages = pool_pages
        super().__init__(
            f"engine not idle after {max_steps} steps: "
            f"queued rids {self.queued}, running rids {self.running}, "
            f"pool {pool_in_use}/{pool_pages} pages in use")


def build_params(cfg: ArchConfig, rt: Runtime, seed: int = 0):
    """Init (and, for pre-packing sites of the active QuantPlan, pack)
    serving weights.

    Packing is per-site: the plan decides which call sites pre-pack into
    the int4 nibble format (legacy uniform `--quant w4a4_packed` maps to a
    uniform plan).  To serve from a quantized checkpoint instead, pass
    `checkpoint.restore_quantized(dir, cfg=cfg, rt=rt)[0]` as `params` to
    the engine — the cfg/rt arguments assert the runtime's active plan
    matches the plan the checkpoint was saved with."""
    params = init_model(jax.random.PRNGKey(seed), cfg)
    return pack_for_serving(params, cfg, rt)


class InferenceEngine:
    """submit() requests, step() the world, collect() finished requests."""

    def __init__(self, cfg: ArchConfig, rt: Runtime, sv: ServingConfig,
                 params=None, seed: int = 0, clock=time.time,
                 telemetry: Optional[Telemetry] = None):
        # continuous batching puts rows at different positions: cache writes
        # must scatter per-row, never assume step-aligned DUS
        rt = dataclasses.replace(rt, aligned_decode=False)
        blocks = tuple(cfg.pattern) + tuple(cfg.tail)
        # SSM/LRU state integrates every input token, so left-padded prefill
        # would pollute it: non-attention archs serve through the contiguous
        # layout with exact-length (per-L compiled) prefill instead.
        self._all_attention = all(bt == "A" for bt in blocks)
        assert self._all_attention or sv.layout == "contiguous", (
            f"paged KV serving requires an all-attention arch (got {blocks});"
            " use layout='contiguous'")
        self.cfg, self.rt, self.sv = cfg, rt, sv
        self.clock = clock
        # telemetry bundle: per-engine registry (compare-mode engines don't
        # share counters), step spans, recompile sentinel
        self.tm = telemetry if telemetry is not None else Telemetry()
        self.metrics = self.tm.registry
        self.span = self.tm.trace.span
        self.params = params if params is not None \
            else build_params(cfg, rt, seed)

        if sv.layout == "paged":
            self.kv = PagedKVCacheManager(sv, metrics=self.metrics)
            # batch=0 template: pool leaves are batch-independent; block
            # tables are rebound per call (inside the jit'd steps) from the
            # device-resident [max_batch, pages_per_seq] table pool.  Rows
            # start at the out-of-bounds sentinel (== num_pages): writes
            # through an unassigned slot drop, reads gather zeros — never
            # physical page 0.
            self.caches = init_paged_caches(cfg, rt, 0, sv)
            self._tbl = jnp.full((sv.max_batch, sv.pages_per_seq),
                                 sv.num_pages, jnp.int32)
            self._tbl0 = np.zeros((0, sv.pages_per_seq), np.int32)
            # rid -> (slot, uploaded page ids): a row re-uploads only when
            # the allocation actually changed.  Keyed on the page-id tuple,
            # not the count — a resumed request re-acquiring refcount-held
            # pages may come back with the same *number* of pages but must
            # still re-upload if the ids (or its slot) differ.
            self._tbl_ver: Dict[int, tuple] = {}
        else:
            self.kv = ContinuousKVCache(sv, metrics=self.metrics)
            self.caches = init_caches(cfg, rt, batch=sv.max_batch,
                                      seq=sv.max_ctx)
        self.scheduler = Scheduler(self.kv, sv.max_batch,
                                   metrics=self.metrics,
                                   max_queue=sv.max_queue)
        # step watchdog (ServingConfig.step_deadline_s): a hung or
        # straggling step becomes a counter, and an exception in strict
        # mode — the same Watchdog the training loop arms
        self._watchdog = (Watchdog(sv.step_deadline_s)
                          if sv.step_deadline_s > 0 else None)
        # chaos hook: an exception planted here is raised at the top of the
        # next step(), before any state mutation, so a retry wrapper
        # (distributed.fault_tolerance.run_with_retries) sees a clean retry
        self._inject_fault: Optional[Exception] = None
        # tuned (bm, bn, bk) tiles for every prefill/decode GEMM and for the
        # fused paged-attention kernels: qdense and kernels.ops resolve
        # blocks through kernels.autotune at trace time, so loading the
        # cache before the first compile is all the wiring needed
        autotune.ensure_loaded()
        self._prefill, self._prefill_tail, self._decode = make_serving_steps(
            cfg, rt, paged=sv.layout == "paged")
        # recompile sentinel: every step function is polled after each call
        # (warmup included), so a compile is always attributed to the
        # bucket shape that triggered it
        self.tm.jit_watch.register("prefill", self._prefill)
        self.tm.jit_watch.register("prefill_tail", self._prefill_tail)
        self.tm.jit_watch.register("decode", self._decode)

        # ragged token-major step: ONE jit whose signature depends only on
        # the padded token budget — batch composition (how many rows are
        # prefill chunks vs decode tokens) never recompiles
        self._ragged = None
        if sv.step == "ragged":
            self._ragged = make_ragged_step(cfg, rt)
            self._budget = sv.budget
            self._slots0 = np.zeros((0,), np.int32)
            # bind zero-length routing leaves now so the cache pytree
            # structure (tbl + slots) is identical on every ragged call
            self.caches = with_token_slots(self.caches, self._tbl0,
                                           self._slots0)
            self.tm.jit_watch.register("ragged", self._ragged)

        self._next_rid = 0
        self._finished: List[Request] = []
        self._all: Dict[int, Request] = {}
        # stats
        self.n_steps = 0
        self.n_decode_tokens = 0
        self.n_prefill_tokens = 0        # tokens actually pushed through prefill
        self.n_prefix_hit_tokens = 0     # prompt/resume tokens served from cache
        # padded-capacity accounting (both step modes): packed = useful rows
        # computed, wasted = padding rows computed and discarded
        self.n_tokens_packed = 0
        self.n_tokens_wasted = 0
        self._last_packed = 0
        self._last_wasted = 0
        self.t_start = None
        # device memory, set by warmup: the KV cache's bytes and, per step
        # jit, the largest temporary allocation over its compiled buckets
        self.kv_pool_bytes = 0
        self.step_temp_bytes: Dict[str, int] = {}
        self._profile: Optional[Dict] = None
        self._profile_step: Optional[int] = None

    # -------------------------------------------------------------- api --
    def submit(self, prompt, max_new: int, arrival: Optional[float] = None,
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None) -> int:
        """Queue a request.  ``deadline_s`` is a TTL relative to now: the
        step-boundary sweep retires the request with outcome=timeout once
        it passes, whether it is still queued or mid-decode.  Raises a
        typed ``ShedError`` when the bounded admission queue
        (``ServingConfig.max_queue``) is full — the retired request is
        still collectable with outcome=shed."""
        rid = self._next_rid
        self._next_rid += 1
        now = self.clock()
        req = Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                      max_new=max_new,
                      arrival=now if arrival is None else arrival,
                      eos_id=eos_id,
                      deadline=(now + deadline_s
                                if deadline_s is not None else None))
        req.t_visible = now
        self._all[rid] = req
        try:
            self.scheduler.submit(req)
        except ShedError:
            req.state, req.outcome, req.t_finish = "finished", SHED, now
            self._finished.append(req)
            self._observe_retire(req)
            raise
        except ValueError:
            # capacity validation failure: a typed `error` retirement, so
            # the outcome taxonomy covers rejected-as-malformed too
            req.state, req.outcome, req.t_finish = "finished", ERROR, now
            self._finished.append(req)
            self._observe_retire(req)
            raise
        self.metrics.counter("requests_submitted_total",
                             "requests accepted into the queue").inc()
        return rid

    def cancel(self, rid: int, outcome: str = CANCELLED) -> bool:
        """Cancel a queued, prefilling, or decoding request.  Its
        refcounted pages are released (shared prefix pages stay warm in the
        pool), the batch slot frees at this step boundary, and the request
        retires with the given outcome, collectable via collect().
        Returns False when rid is unknown or already retired."""
        req = self._all.get(rid)
        if req is None or req.t_finish is not None:
            return False
        retired = self.scheduler.cancel(rid, self.clock(), outcome)
        if retired is None:
            return False
        self._finish_aborted(retired)
        return True

    def collect(self) -> List[Request]:
        out, self._finished = self._finished, []
        return out

    def warmup(self, prompt_lens=()) -> None:
        """Compile every expected step signature (one prefill per prompt
        bucket, one decode per batch bucket) before the measured window, so
        latency/throughput stats don't absorb multi-second jit compiles.
        Dummy calls use position -1 everywhere: every cache write is dropped
        and pool/cache state is untouched.  Resumed prefixes can still hit a
        new prompt bucket mid-run; that compile is attributed to the run.

        Ragged mode has exactly ONE signature — the token budget — so
        warmup is one dummy call regardless of the trace's prompt mix.

        Warmup also records the device memory the engine holds: the KV
        cache's bytes (``kv_pool_bytes``) and each step jit's largest
        temporary allocation over its buckets (``step_temp_bytes``)."""
        self.kv_pool_bytes = sum(a.nbytes
                                 for a in jax.tree.leaves(self.caches))
        self.metrics.gauge(
            "kv_pool_bytes", "device bytes of the KV cache (every layer)"
        ).set(self.kv_pool_bytes)
        if self._ragged is not None:
            self._warm_ragged()
            return
        for L in sorted({self._prompt_pad(len_) for len_ in prompt_lens}):
            tokens = jnp.zeros((1, L), jnp.int32)
            positions = jnp.full((1, L), -1, jnp.int32)
            if self.sv.layout == "paged":
                _, self.caches = self._warm_call(
                    "prefill", self._prefill, self.params, tokens,
                    self.caches, positions, self._tbl,
                    jnp.zeros((1,), jnp.int32))
                self._strip_tables()
                self._poll_jit("prefill", (1, L))
                if self.sv.prefix_cache:
                    # prefix hits run the tail-prefill step over the same
                    # bucket set (a tail can also land in a smaller bucket
                    # mid-run; that compile is attributed to the run)
                    _, self.caches = self._warm_call(
                        "prefill_tail", self._prefill_tail, self.params,
                        tokens, self.caches, positions, self._tbl,
                        jnp.zeros((1,), jnp.int32))
                    self._strip_tables()
                    self._poll_jit("prefill_tail", (1, L))
            else:
                row = init_caches(self.cfg, self.rt, batch=1,
                                  seq=self.sv.max_ctx)
                self._warm_call("prefill", self._prefill, self.params,
                                tokens, row, positions)
                self._poll_jit("prefill", (1, L))
        for nb in self.sv.buckets:
            tok = jnp.zeros((nb, 1), jnp.int32)
            pos = jnp.full((nb, 1), -1, jnp.int32)
            if self.sv.layout == "paged":
                _, self.caches = self._warm_call(
                    "decode", self._decode, self.params, tok, self.caches,
                    pos, self._tbl, jnp.zeros((nb,), jnp.int32))
                self._strip_tables()
            else:
                sub = gather_rows(self.caches, [0] * nb)
                self._warm_call("decode", self._decode, self.params, tok,
                                sub, pos)
            self._poll_jit("decode", (nb, 1))

    def _warm_call(self, step: str, fn, *args):
        """One warmup call of a step jit, then the temporary bytes of the
        program it compiled: lowering the same arguments again hits the
        jit's caches, so nothing compiles twice.  ``step_temp_bytes`` keeps
        the largest over the step's buckets."""
        out = fn(*args)
        lower = getattr(fn, "lower", None)      # None: a wrapped step
        mem = lower(*args).compile().memory_analysis() if lower else None
        if mem is not None:
            most = max(self.step_temp_bytes.get(step, 0),
                       int(mem.temp_size_in_bytes))
            self.step_temp_bytes[step] = most
            self.metrics.gauge(
                "step_temp_bytes",
                "largest temporary allocation of a step's compiled buckets",
                step=step).set(most)
        return out

    def _warm_ragged(self) -> None:
        """Compile the single ragged signature at the current budget.  All
        positions/slots are -1 (pure padding): writes drop, pool untouched."""
        T = self._budget
        _, self.caches = self._warm_call(
            "ragged", self._ragged, self.params,
            jnp.zeros((1, T), jnp.int32), self.caches,
            jnp.full((1, T), -1, jnp.int32), self._tbl,
            jnp.full((T,), -1, jnp.int32),
            jnp.full((self.sv.max_batch,), -1, jnp.int32))
        self._strip_tables()
        self._poll_jit("ragged", (1, T))

    def _grow_budget(self, need: int) -> None:
        """The running set's decode tokens alone exceed the budget (only
        possible with an explicit tiny token_budget): double to fit, compile
        the new signature, and re-baseline the sentinel.  The growth lands
        in the `compiles` count — never in steady_state, which stays the
        zero-recompiles guarantee the ragged mode exists for."""
        new = self._budget
        while new < need:
            new *= 2
        self._budget = new
        self.metrics.counter(
            "ragged_budget_grows_total",
            "token-budget doublings (one fresh compile each)").inc()
        self._warm_ragged()
        self.tm.jit_watch.absorb("ragged")

    def inject_step_fault(self, exc: Exception) -> None:
        """Chaos hook: raise `exc` at the top of the next step(), before
        any scheduler/pool mutation — so wrapping step() in
        ``run_with_retries`` retries against unchanged state."""
        self._inject_fault = exc

    def step(self) -> int:
        """One decode-step boundary; returns the number of running requests
        after the step (0 = idle).

        Lifecycle work happens here, outside the jit'd bodies: injected
        faults fire before any mutation (clean retries), the deadline sweep
        retires overdue requests with outcome=timeout before admission can
        spend pages on them, and the optional step watchdog
        (``ServingConfig.step_deadline_s``) turns a hung/straggling step
        into a counter — or a typed ``StepDeadlineExceeded`` in strict
        mode.  All of it is host-side: the donated single-signature jits
        and the zero-steady-state-recompile guarantee are untouched."""
        if self._inject_fault is not None:
            exc, self._inject_fault = self._inject_fault, None
            raise exc
        sch = self.scheduler
        with self.span("engine.step", waiting=len(sch.waiting),
                       running=len(sch.running)):
            with self.span("engine.schedule"):
                expired = sch.expire(self.clock())
            for req in expired:
                self._finish_aborted(req)
            wd = self._watchdog
            if wd is not None:
                wd.arm()
            try:
                n = (self._step_ragged() if self._ragged is not None
                     else self._step_bucketed())
            finally:
                if wd is not None:
                    wd.disarm()
        if wd is not None and wd.fired.is_set():
            self.metrics.counter(
                "serving_step_deadline_exceeded_total",
                "engine steps that overran the watchdog deadline").inc()
            if self.sv.step_deadline_strict:
                raise StepDeadlineExceeded(
                    f"serving step {self.n_steps - 1} exceeded "
                    f"{self.sv.step_deadline_s:.3f}s deadline")
        return n

    def _step_bucketed(self) -> int:
        t0 = time.perf_counter()
        now = self.clock()
        if self.t_start is None:
            self.t_start = now
        with self.span("engine.schedule"):
            admitted = self.scheduler.admit(now)
        for req in admitted:
            self._prefill_request(req)
        self._retire()                 # a 1-token request is done at prefill
        with self.span("engine.schedule"):
            self.scheduler.ensure_decode()
            batch = self.scheduler.batch()
        if batch:
            self._decode_batch(batch)
        self.n_steps += 1
        self._retire()
        self._observe_step(t0, batch)
        return len(self.scheduler.running)

    def _step_ragged(self) -> int:
        """One ragged token-major step: admit, plan a token budget's worth of
        work (decode tokens first, then prefill chunks), run ONE jit over the
        flat pack, apply emissions.  Unlike the bucketed path there is no
        per-admission prefill call — an admitted request's prefix simply
        drains through the planner as chunks, possibly across several steps,
        interleaved with everyone else's decode tokens."""
        t0 = time.perf_counter()
        now = self.clock()
        if self.t_start is None:
            self.t_start = now
        with self.span("engine.schedule"):
            admitted = self.scheduler.admit(now)
        for req in admitted:
            # prefix-cache hits are realized at admission (the planner only
            # ever feeds prefix[n_cached:]) — account them here, where the
            # bucketed path accounts them inside _prefill_request
            hit = req.n_cached
            self.n_prefix_hit_tokens += hit
            self.metrics.counter(
                "prefix_hit_tokens_total",
                "prompt/resume tokens served from cached pages").inc(hit)
        with self.span("engine.schedule"):
            self.scheduler.ensure_decode()
        # the budget must cover every decode token plus one prefill-chunk
        # slot whenever a prefill-phase request is running — a saturated
        # decode set would otherwise starve later slots indefinitely (the
        # planner serves decode tokens in slot order, so the same requests
        # win every step).  Only reachable with an explicit token_budget
        # below max_batch: grow, compile the new signature once, and
        # re-baseline the sentinel so steady_state stays zero.
        running = self.scheduler.running.values()
        n_decoding = sum(1 for r in running if r.decoding)
        need = n_decoding + (1 if any(not r.decoding for r in running) else 0)
        if need > self._budget:
            self._grow_budget(need)
        with self.span("engine.schedule"):
            plan = self.scheduler.plan_tokens(self._budget)
        if plan:
            with self.span("engine.ragged",
                           tokens=sum(n for _, _, n in plan)):
                self._ragged_exec(plan)
        self.n_steps += 1
        self._retire()
        self._observe_step(t0, [r for r, _, _ in plan if r.decoding])
        return len(self.scheduler.running)

    def _ragged_exec(self, plan) -> None:
        """Pack the planned (req, start, n) chunks into the flat [1, T]
        buffers and run the ragged step.  Every row's KV is written through
        its block table *before* attention (write-then-attend), so one mask
        rule — key position <= query position — is exactly causal for
        prefill chunks and exactly last-token for decode rows."""
        span = self.span
        T = self._budget
        mb = self.sv.max_batch
        with span("engine.ragged.build"):
            tokens = np.zeros((1, T), np.int32)
            positions = np.full((1, T), -1, np.int32)  # -1 = pad: writes drop
            slots = np.full((T,), -1, np.int32)
            emit_rows = np.full((mb,), -1, np.int32)
            used = 0
            for req, start, n in plan:
                tokens[0, used:used + n] = req.prefix[start:start + n]
                positions[0, used:used + n] = np.arange(start, start + n)
                slots[used:used + n] = req.slot
                if start + n == len(req.prefix):
                    # chunk reaches the prefix end: this row's logits emit
                    # the request's next token (decode rows: n == 1, always)
                    emit_rows[req.slot] = used + n - 1
                used += n
        self._observe_packing(used, T)
        self._sync_tables([r for r, _, _ in plan])
        with span("engine.ragged.dispatch"):
            nxt, self.caches = self._ragged(
                self.params, jnp.asarray(tokens), self.caches,
                jnp.asarray(positions), self._tbl, jnp.asarray(slots),
                jnp.asarray(emit_rows))
            self._strip_tables()
            self._poll_jit("ragged", (1, T))
        with span("engine.ragged.readback"):
            # the step's ONE sanctioned device->host sync: token readback
            nxt = np.asarray(nxt)  # repro: ignore[host-sync-in-hot-path]
        with span("engine.ragged.apply"):
            ps = self.sv.page_size
            for req, start, n in plan:
                end = start + n
                if req.decoding:
                    self.n_decode_tokens += 1
                    self.metrics.counter(
                        "decode_tokens_total",
                        "tokens emitted by decode steps").inc()
                else:
                    self.n_prefill_tokens += n
                    self.metrics.counter(
                        "prefill_tokens_total",
                        "tokens pushed through prefill").inc(n)
                req.n_cached = end
                if emit_rows[req.slot] >= 0:
                    if not req.decoding:
                        # prefill just completed: register its full pages
                        # before the emitted token joins the prefix (mirrors
                        # the bucketed engine's post-prefill registration)
                        self.kv.register_upto(req.rid, req.prefix, end)
                    req.tokens.append(int(nxt[req.slot]))
                    if req.t_first is None:
                        req.t_first = self.clock()
                    req.decoding = True
                    if end % ps == 0 and len(req.tokens) > 1:
                        # a generated-token page just filled (decode rows
                        # only — end counts the token written this step)
                        self.kv.register_upto(req.rid, req.prefix, end)

    def _observe_step(self, t0: float, batch: List[Request]) -> None:
        """Per-step telemetry: wall time + batch composition into the
        registry, occupancy gauges sampled at the step boundary."""
        with self.span("engine.observe"):
            m = self.metrics
            m.counter("steps_total", "engine decode-step boundaries").inc()
            m.histogram("step_wall_us",
                        "wall time per engine step").observe(
                            (time.perf_counter() - t0) * 1e6)
            if batch:
                m.histogram("decode_batch_size",
                            "running rows per decode step",
                            buckets=COUNT_BUCKETS).observe(len(batch))
            m.gauge("queue_depth",
                    "requests waiting for admission").set(
                        len(self.scheduler.waiting))
            m.gauge("running_requests",
                    "requests in the decode batch").set(
                        len(self.scheduler.running))
            # token utilization of this step's padded capacity (both step
            # modes): useful rows over useful+padding rows computed since the
            # previous boundary
            du = self.n_tokens_packed - self._last_packed
            dw = self.n_tokens_wasted - self._last_wasted
            self._last_packed = self.n_tokens_packed
            self._last_wasted = self.n_tokens_wasted
            if du + dw:
                m.gauge("token_utilization",
                        "useful fraction of the step's padded token capacity"
                        ).set(du / (du + dw))
            if self.sv.layout == "paged":
                m.gauge("kv_pool_in_use_pages",
                        "pages held by running requests").set(self.kv.in_use)
                m.gauge("kv_pool_warm_pages",
                        "refcount-0 pages still indexed").set(
                            len(self.kv.warm))
                m.gauge("kv_pool_blank_pages",
                        "free pages with no content").set(len(self.kv.blank))
                m.gauge("kv_pool_occupancy",
                        "in-use fraction of the page pool").set(
                            self.kv.in_use / self.sv.num_pages)
                m.gauge("kv_pool_high_water_pages",
                        "peak concurrent in-use pages").set(
                            self.kv.high_water)

    def _retire(self) -> None:
        with self.span("engine.retire"):
            now = self.clock()
            for req in list(self.scheduler.running.values()):
                if req.done:
                    self.scheduler.finish(req, now)
                    self._finished.append(req)
                    self._observe_retire(req)

    def _finish_aborted(self, req: Request) -> None:
        """Land a scheduler-aborted request (cancel, deadline expiry) in the
        collect() queue with its outcome telemetry — the same retirement
        path a clean finish takes, minus scheduler.finish (the scheduler
        already evicted it)."""
        self._finished.append(req)
        self._observe_retire(req)

    def _observe_retire(self, req: Request) -> None:
        """Per-request retirement telemetry, recorded the moment t_finish is
        stamped.  Latency histograms and the retire counter carry the typed
        ``outcome`` label (ok|cancelled|timeout|shed|error) so dashboards
        separate clean finishes from lifecycle aborts without a second
        registry; ``requests_finished_total`` stays ok-only (it means what
        it always meant).  TTFT/ITL — the histograms the SLO scheduler and
        autoscaling signal (ROADMAP item 3) will consume — record for any
        outcome that got far enough to have the timestamps."""
        m = self.metrics
        out = req.outcome or ERROR
        m.counter("requests_retired_total", "requests retired, any outcome",
                  outcome=out).inc()
        if out == OK:
            m.counter("requests_finished_total",
                      "requests fully decoded").inc()
        elif out == CANCELLED:
            m.counter("serving_cancelled_total",
                      "requests cancelled before finishing").inc()
        elif out == TIMEOUT:
            m.counter("serving_timeout_total",
                      "requests retired past their deadline").inc()
        elif out == SHED:
            m.counter("serving_shed_total",
                      "requests shed by the bounded admission queue").inc()
        m.histogram("request_latency_us",
                    "submit-to-retire wall time", outcome=out).observe(
                        (req.t_finish - req.t_visible) * 1e6)
        if req.t_first is not None:
            m.histogram("ttft_us", "time to first token",
                        outcome=out).observe(
                            (req.t_first - req.t_visible) * 1e6)
            if len(req.tokens) > 1:
                m.histogram("itl_us",
                            "mean inter-token latency per request",
                            outcome=out).observe(
                                (req.t_finish - req.t_first) * 1e6
                                / (len(req.tokens) - 1))

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and self.scheduler.idle:
                return
        self.metrics.counter(
            "serving_engine_stuck_total",
            "run_until_idle step-budget exhaustions").inc()
        raise EngineStuckError(
            max_steps,
            [r.rid for r in self.scheduler.waiting],
            list(self.scheduler.running),
            getattr(self.kv, "in_use", 0),
            self.sv.num_pages if self.sv.layout == "paged" else 0)

    # -------------------------------------------------------- internals --
    def _observe_packing(self, used: int, capacity: int) -> None:
        """Account one padded launch: `used` useful token rows out of
        `capacity` computed.  The delta feeds the per-step
        ``token_utilization`` gauge; the counter is the cumulative padding
        bill a budget/bucket tuning pass wants to shrink."""
        wasted = max(capacity - used, 0)
        self.n_tokens_packed += used
        self.n_tokens_wasted += wasted
        self.metrics.counter(
            "padding_tokens_wasted_total",
            "padding token rows computed and discarded").inc(wasted)

    def _poll_jit(self, name: str, shape) -> None:
        """Poll the recompile sentinel right after a step-function call,
        attributing any jit cache growth to `shape` (the bucket signature
        of the call that just ran)."""
        self.tm.jit_watch.after_call(name, shape, step=self.n_steps)

    def _prompt_pad(self, L: int) -> int:
        """Prompt lengths are bucketed (fewer compiles) for attention archs;
        SSM/LRU state integrates pad tokens, so those prefill at exact L."""
        return self.sv.prompt_bucket(L) if self._all_attention else L

    def _strip_tables(self) -> None:
        """Rebind the batch-0 table template after a paged step so the
        stored cache tree's signature never depends on the last bucket.
        Ragged mode also carries zero-length token-slot leaves — strip both
        so every ragged call sees the identical cache pytree."""
        if self._ragged is not None:
            self.caches = with_token_slots(self.caches, self._tbl0,
                                           self._slots0)
        else:
            self.caches = with_block_tables(self.caches, self._tbl0)

    def _sync_tables(self, batch: List[Request]) -> None:
        """Upload block-table rows whose page allocation changed since the
        last upload (admission, page growth).  This is the only host->device
        block-table traffic — steady-state decode uploads nothing."""
        with self.span("engine.sync_tables", rows=len(batch)):
            for req in batch:
                ver = (req.slot, tuple(self.kv.pages.get(req.rid, ())))
                if self._tbl_ver.get(req.rid) != ver:
                    self._tbl = self._tbl.at[req.slot].set(
                        jnp.asarray(self.kv.table_row(req.rid)))
                    self._tbl_ver[req.rid] = ver
                    self.metrics.counter(
                        "block_table_uploads_total",
                        "host->device block-table row uploads").inc()
            # drop versions of finished/preempted requests so dead entries
            # don't accumulate.  Correctness doesn't ride on this prune:
            # versions key on (slot, page ids), so a resumed rid re-admitting
            # with the very same refcount-held pages into the same slot
            # genuinely needs no re-upload, and any change in slot or ids
            # forces one.
            running = self.scheduler.running
            for rid in [r for r in self._tbl_ver if r not in running]:
                del self._tbl_ver[rid]

    def _prefill_request(self, req: Request) -> None:
        """Prefill a (re-)admitted request's uncached prefix tail (batch of
        one, left-padded to a power-of-two bucket) and emit its first token
        from the prefill logits.

        The scheduler's admission set ``req.n_cached`` to the prefix-cache
        hit length (0 without a hit): the cached prefix already lives in
        shared pages, so only ``prefix[hit:]`` flows through the model —
        via the tail-prefill step, whose suffix queries attend over the
        gathered page pool instead of just the in-flight K/V."""
        prefix = req.prefix
        L = len(prefix)
        hit = req.n_cached                     # page-aligned, < L by design
        tail = prefix[hit:]
        n = len(tail)
        Lb = self._prompt_pad(n)
        span = self.span
        with span("engine.prefill", rid=req.rid, tokens=n, bucket=Lb,
                  hit=hit):
            with span("engine.prefill.build"):
                tokens = np.zeros((1, Lb), np.int32)
                tokens[0, Lb - n:] = tail
                base = np.arange(Lb, dtype=np.int32) - (Lb - n)
                # pad rows must stay negative (dropped writes / masked
                # queries) even after the hit offset shifts the real tail
                # to hit..L-1
                positions = np.where(base >= 0, base + hit, -1)[None, :]
            if self.sv.layout == "paged":
                self._sync_tables([req])
                with span("engine.prefill.dispatch"):
                    step = self._prefill_tail if hit else self._prefill
                    tok, self.caches = step(
                        self.params, jnp.asarray(tokens), self.caches,
                        jnp.asarray(positions), self._tbl,
                        jnp.asarray([req.slot], jnp.int32))
                    self._strip_tables()
                    self._poll_jit("prefill_tail" if hit else "prefill",
                                   (1, Lb))
            else:
                with span("engine.prefill.dispatch"):
                    # a fresh init row IS the reset: prefill into it, then
                    # scatter the row into the slot (evicting any previous
                    # tenant's state)
                    row = init_caches(self.cfg, self.rt, batch=1,
                                      seq=self.sv.max_ctx)
                    tok, row = self._prefill(
                        self.params, jnp.asarray(tokens), row,
                        jnp.asarray(positions))
                    self.caches = scatter_rows(self.caches, row, [req.slot])
                    self._poll_jit("prefill", (1, Lb))

            req.n_cached = L
            self.n_prefill_tokens += n
            self.n_prefix_hit_tokens += hit
            m = self.metrics
            m.counter("prefill_tokens_total",
                      "tokens pushed through prefill").inc(n)
            if hit:
                m.counter("tail_prefill_tokens_total",
                          "prefill tokens behind a prefix-cache hit").inc(n)
            m.counter("prefix_hit_tokens_total",
                      "prompt/resume tokens served from cached pages").inc(
                          hit)
            self._observe_packing(n, Lb)
            self.kv.register_upto(req.rid, prefix, L)  # index full pages
            with span("engine.prefill.readback"):
                req.tokens.append(int(tok[0]))
                if req.t_first is None:
                    req.t_first = self.clock()

    def _decode_batch(self, batch: List[Request]) -> None:
        """One decode step over the running set, padded to a bucket."""
        n = len(batch)
        nb = self.sv.decode_bucket(n)
        span = self.span
        with span("engine.decode", rows=n, bucket=nb):
            with span("engine.decode.build"):
                tok = np.zeros((nb, 1), np.int32)
                pos = np.full((nb, 1), -1, np.int32)
                for i, req in enumerate(batch):
                    tok[i, 0] = req.tokens[-1]  # the newest generated token
                    pos[i, 0] = req.n_cached    # ... at the next position
                # pad rows point at slot 0 (paged) or the last slot
                # (contiguous): their positions are -1, so writes drop and
                # their (masked) attention output is discarded
                slots = np.zeros((nb,), np.int32)
                slots[:n] = [r.slot for r in batch]

            if self.sv.layout == "paged":
                self._sync_tables(batch)
                # the pages the decode attention walks (each row up to its
                # newest position) against the bucket's whole block table:
                # their ratio is the share of the table the walk visits
                ps = self.sv.page_size
                self.metrics.counter(
                    "paged_decode_pages_live_total",
                    "KV pages holding a decode row's positions").inc(
                        sum(r.n_cached // ps + 1 for r in batch))
                self.metrics.counter(
                    "paged_decode_pages_table_total",
                    "block-table pages of the decode buckets").inc(
                        nb * self.sv.pages_per_seq)
                with span("engine.decode.dispatch"):
                    nxt, self.caches = self._decode(
                        self.params, jnp.asarray(tok), self.caches,
                        jnp.asarray(pos), self._tbl, jnp.asarray(slots))
                    self._strip_tables()
                    self._poll_jit("decode", (nb, 1))
            else:
                with span("engine.decode.dispatch"):
                    rows = [r.slot for r in batch] \
                        + [self.sv.max_batch - 1] * (nb - n)  # pads: no write
                    sub = gather_rows(self.caches, rows)
                    nxt, sub = self._decode(
                        self.params, jnp.asarray(tok), sub, jnp.asarray(pos))
                    # scatter only the active rows back (a pad row may
                    # alias an active slot, and duplicate scatter indices
                    # would race)
                    self.caches = scatter_rows(
                        self.caches, gather_rows(sub, np.arange(n)),
                        rows[:n])
                    self._poll_jit("decode", (nb, 1))
            self._observe_packing(n, nb)
            self.metrics.counter("decode_tokens_total",
                                 "tokens emitted by decode steps").inc(n)
            with span("engine.decode.readback"):
                # the step's ONE sanctioned device->host sync: token readback
                nxt = np.asarray(nxt)  # repro: ignore[host-sync-in-hot-path]
            with span("engine.decode.apply"):
                ps = self.sv.page_size
                for i, req in enumerate(batch):
                    req.n_cached += 1
                    req.tokens.append(int(nxt[i]))
                    if self.sv.layout == "paged" and req.n_cached % ps == 0:
                        # a generated-token page just filled: index it so
                        # preempted or follow-up requests sharing this
                        # prefix can hit it
                        self.kv.register_upto(req.rid, req.prefix,
                                              req.n_cached)
                self.n_decode_tokens += n

    # ------------------------------------------------------- stop/resume --
    def snapshot(self) -> Dict:
        """Freeze the engine at a step boundary: every request's progress,
        the scheduler's queues/slots, the page pool's full bookkeeping, the
        device KV pool and block tables (pulled to host numpy), and the
        engine counters.  `InferenceEngine.restore(snap)` builds a fresh
        engine that continues *bit-identically* — restored requests emit
        exactly the tokens the uninterrupted run would have (the device
        pool is captured verbatim, so nothing is recomputed).

        Call between steps (never from inside a step callback).  The dict
        is in-memory/same-process state: config objects are held by
        reference and the prefix index carries Python content hashes, which
        are only stable across processes with PYTHONHASHSEED pinned — to
        persist across processes, pickle it from a pinned interpreter."""
        def _req(req: Request) -> Dict:
            d = {f.name: getattr(req, f.name)
                 for f in dataclasses.fields(Request)}
            d["prompt"] = np.array(d["prompt"], np.int32)
            d["tokens"] = list(d["tokens"])
            return d

        sch = self.scheduler
        return {
            "cfg": self.cfg, "rt": self.rt, "sv": self.sv,
            "requests": {rid: _req(r) for rid, r in self._all.items()},
            "finished": [r.rid for r in self._finished],
            "waiting": [r.rid for r in sch.waiting],
            "running": list(sch.running),          # insertion order
            "free_slots": list(sch._free_slots),   # heap layout, verbatim
            "admit_counter": sch._admit_counter,
            "n_preemptions": sch.n_preemptions,
            "kv": self.kv.state(),
            "caches": jax.tree.map(np.asarray, self.caches),
            "tbl": np.asarray(self._tbl),
            "budget": self._budget if self._ragged is not None else None,
            "next_rid": self._next_rid,
            "counters": {
                "n_steps": self.n_steps,
                "n_decode_tokens": self.n_decode_tokens,
                "n_prefill_tokens": self.n_prefill_tokens,
                "n_prefix_hit_tokens": self.n_prefix_hit_tokens,
                "n_tokens_packed": self.n_tokens_packed,
                "n_tokens_wasted": self.n_tokens_wasted,
                "t_start": self.t_start,
            },
        }

    @classmethod
    def restore(cls, snap: Dict, params=None, seed: int = 0,
                clock=time.time, telemetry: Optional[Telemetry] = None
                ) -> "InferenceEngine":
        """Build an engine from a `snapshot()` and resume where it stopped.
        Weights are NOT in the snapshot — pass the same `params` (or the
        same `seed`, which re-inits them deterministically).  The restored
        engine's step functions are fresh jits: their first calls compile
        (first-seen shapes, counted as compiles), but the zero
        steady-state-recompile guarantee holds from there."""
        eng = cls(snap["cfg"], snap["rt"], snap["sv"], params=params,
                  seed=seed, clock=clock, telemetry=telemetry)
        eng._load_snapshot(snap)
        return eng

    def _load_snapshot(self, snap: Dict) -> None:
        reqs: Dict[int, Request] = {}
        for rid, d in snap["requests"].items():
            d = dict(d)
            d["prompt"] = np.array(d["prompt"], np.int32)
            d["tokens"] = list(d["tokens"])
            reqs[rid] = Request(**d)
        self._all = reqs
        self._finished = [reqs[r] for r in snap["finished"]]
        sch = self.scheduler
        sch.waiting = deque(reqs[r] for r in snap["waiting"])
        sch.running = {r: reqs[r] for r in snap["running"]}
        sch._free_slots = list(snap["free_slots"])
        sch._admit_counter = snap["admit_counter"]
        sch.n_preemptions = snap["n_preemptions"]
        self.kv.load_state(snap["kv"])
        if self._ragged is not None and snap["budget"] is not None \
                and snap["budget"] != self._budget:
            # match the source engine's (possibly grown) budget so the plan
            # packs identically; the signature compiles on first use
            self._budget = snap["budget"]
        self.caches = jax.tree.map(jnp.asarray, snap["caches"])
        self._strip_tables()
        if self.sv.layout == "paged":
            self._tbl = jnp.asarray(snap["tbl"])
            # empty version map => _sync_tables re-uploads rows for running
            # requests on their next batch; correct either way, since
            # versions key on (slot, page ids)
            self._tbl_ver = {}
        self._next_rid = snap["next_rid"]
        c = snap["counters"]
        self.n_steps = c["n_steps"]
        self.n_decode_tokens = c["n_decode_tokens"]
        self.n_prefill_tokens = c["n_prefill_tokens"]
        self.n_prefix_hit_tokens = c["n_prefix_hit_tokens"]
        self.n_tokens_packed = self._last_packed = c["n_tokens_packed"]
        self.n_tokens_wasted = self._last_wasted = c["n_tokens_wasted"]
        self.t_start = c["t_start"]
        sch.check_invariants()
        self.kv.check_invariants()

    # ----------------------------------------------------------- profile --
    def profile(self, reps: int = 3) -> Dict:
        """Attribute one full-context decode step's cost: the whole jit'd
        step is timed against the attention op alone (the same kernels.ops
        dispatch the model traces), so a perf regression can be pinned on
        attention vs the GEMM/rest of the step.  Call when idle — the probe
        steps write through (stale) block tables and scratch the pool.
        The result lands in ``stats()["profile"]``."""
        import time as _time

        def _best_us(fn):
            jax.block_until_ready(fn())
            ts = []
            for _ in range(reps):
                t0 = _time.perf_counter()
                jax.block_until_ready(fn())
                ts.append((_time.perf_counter() - t0) * 1e6)
            return float(min(ts))

        nb = self.sv.max_batch
        cfg, sv = self.cfg, self.sv
        tok = jnp.zeros((nb, 1), jnp.int32)
        pos = jnp.full((nb, 1), sv.max_ctx - 1, jnp.int32)
        last = jnp.full((nb,), sv.max_ctx - 1, jnp.int32)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((nb, cfg.n_heads, cfg.hd)),
                        jnp.bfloat16)

        if self._ragged is not None:
            # ragged mode: the step IS the ragged jit — time it at the
            # budget, all-padding rows (writes drop, pool untouched)
            T = self._budget
            rtok = jnp.zeros((1, T), jnp.int32)
            rpos = jnp.full((1, T), -1, jnp.int32)
            rslots = jnp.full((T,), -1, jnp.int32)
            remit = jnp.full((nb,), -1, jnp.int32)

            def step():
                nxt, self.caches = self._ragged(
                    self.params, rtok, self.caches, rpos, self._tbl,
                    rslots, remit)
                self._strip_tables()
                return nxt
        elif sv.layout == "paged":
            def step():
                nxt, self.caches = self._decode(
                    self.params, tok, self.caches, pos, self._tbl,
                    jnp.zeros((nb,), jnp.int32))
                self._strip_tables()
                return nxt
        else:
            rows = list(range(nb))

            def step():
                sub = gather_rows(self.caches, rows)
                nxt, sub = self._decode(self.params, tok, sub, pos)
                self.caches = scatter_rows(self.caches, sub, rows)
                return nxt

        # time the step first: it donates the cache pool, so the attention
        # probe must capture its pool references afterwards
        step_us = _best_us(step)

        from repro.models.attention import _cache_read, attention_core
        # the first layer's attention cache: a paged pool stays stacked and
        # is read at layer 0, a contiguous cache is sliced
        attn = next((blk["attn"] for blk in self.caches["rep"].values()
                     if "attn" in blk), None)
        if attn is not None and sv.layout != "paged":
            attn = jax.tree.map(lambda l: l[0], attn)
        if attn is None:
            # SSM/LRU stack: no attention blocks to attribute — the whole
            # step is GEMM + recurrence
            self._profile = {
                "decode_step_us": round(step_us, 1),
                "attn_us": 0.0,
                "gemm_other_us": round(step_us, 1),
                "attn_frac": 0.0,
                "at_step": self.n_steps,
            }
            self._profile_step = self.n_steps
            self.tm.jit_watch.absorb()
            return self._profile
        if sv.layout == "paged":
            from repro.kernels import ops
            from repro.serving.kv_pages import paged_read

            tbl = self._tbl[:nb]
            if self._ragged is not None:
                T = self._budget
                qT = jnp.asarray(
                    rng.standard_normal((T, cfg.n_heads, cfg.hd)),
                    jnp.bfloat16)
                tslots = jnp.zeros((T,), jnp.int32)
                tpos = jnp.full((T,), sv.max_ctx - 1, jnp.int32)

                def attn_op():
                    return ops.ragged_paged_attention(
                        qT, attn["k"], attn["v"], self._tbl, tslots, tpos,
                        0, attn.get("k_scale"), attn.get("v_scale"),
                        window=cfg.local_window)
            elif self.rt.paged_attn == "fused":
                def attn_op():
                    return ops.paged_decode_attention(
                        q, attn["k"], attn["v"], tbl, last, 0,
                        attn.get("k_scale"), attn.get("v_scale"),
                        window=cfg.local_window)
            else:
                def attn_op():
                    kf, vf, kpos = paged_read(dict(attn, tbl=tbl, layer=0),
                                              last, cfg.n_kv_heads)
                    return attention_core(
                        q[:, None], kf, vf, q_positions=last[:, None],
                        k_positions=kpos, window=cfg.local_window,
                        impl="full", chunk_q=self.rt.attn_chunk_q)
        else:
            attn = {k_: v_[:nb] for k_, v_ in attn.items() if k_ != "pos"}
            # every cached slot marked valid: the probe times a full-window
            # attention regardless of how much real state the run left
            # behind (kpos carries the ring size, which is < max_ctx for
            # sliding-window configs)
            kpos = jnp.broadcast_to(
                jnp.arange(attn["kpos"].shape[1], dtype=jnp.int32),
                attn["kpos"].shape)
            attn.pop("kpos")

            def attn_op():
                kf, vf = _cache_read(attn)
                return attention_core(
                    q[:, None], kf, vf, q_positions=last[:, None],
                    k_positions=kpos, window=cfg.local_window,
                    impl="full", chunk_q=self.rt.attn_chunk_q)

        attn_us = _best_us(jax.jit(attn_op)) * cfg.n_layers
        self._profile = {
            "decode_step_us": round(step_us, 1),
            "attn_us": round(attn_us, 1),
            "gemm_other_us": round(max(step_us - attn_us, 0.0), 1),
            "attn_frac": round(min(attn_us / step_us, 1.0), 4)
            if step_us else None,
            "at_step": self.n_steps,
        }
        self._profile_step = self.n_steps
        # the probe calls above may have compiled new signatures (a probe
        # batch can hit an unvisited bucket): re-baseline the sentinel so
        # those compiles don't masquerade as the next real step's recompile
        self.tm.jit_watch.absorb()
        return self._profile

    # ------------------------------------------------------------- stats --
    def stats(self) -> Dict:
        retired = [r for r in self._all.values() if r.t_finish is not None]
        # latency aggregates describe *clean* finishes only — a storm of
        # instantly-cancelled requests must not drag p50 toward zero
        done = [r for r in retired if r.outcome == OK]
        outcomes: Dict[str, int] = {}
        for r in retired:
            out = r.outcome or ERROR
            outcomes[out] = outcomes.get(out, 0) + 1
        lat = [r.t_finish - r.t_visible for r in done]
        # `is not None`, not truthiness: a t_first of exactly 0.0 (fake
        # clocks, epoch-zero traces) is a real first-token time
        ttft = [r.t_first - r.t_visible for r in done
                if r.t_first is not None]
        wall = (self.clock() - self.t_start) \
            if self.t_start is not None else 0.0
        # every derived latency field degrades to None with zero finished
        # requests — callers see requests_finished: 0 and no fake numbers
        pct = (lambda xs, q: float(np.percentile(xs, q)) if xs else None)
        mean = (lambda xs: float(np.mean(xs)) if xs else None)
        demand = self.n_prefill_tokens + self.n_prefix_hit_tokens
        capacity = self.n_tokens_packed + self.n_tokens_wasted
        return {
            "layout": self.sv.layout,
            "step_mode": self.sv.step,
            **({"token_budget": self._budget}
               if self._ragged is not None else {}),
            "padding_tokens_wasted": self.n_tokens_wasted,
            "token_utilization": (self.n_tokens_packed / capacity
                                  if capacity else None),
            "requests_finished": len(done),
            "requests_retired": len(retired),
            "outcomes": outcomes,
            "requests_preempted": self.scheduler.n_preemptions,
            "steps": self.n_steps,
            "prefill_tokens": self.n_prefill_tokens,
            "tokens_prefilled_saved": self.n_prefix_hit_tokens,
            "prefix_hit_rate": (self.n_prefix_hit_tokens / demand
                                if demand else 0.0),
            "prefix_cache": {
                "enabled": (self.sv.layout == "paged"
                            and self.sv.prefix_cache),
                "lookups": self.kv.n_lookups,
                "hit_tokens": self.kv.n_hit_tokens,
                "evictions": self.kv.n_evictions,
            },
            "decode_tokens": self.n_decode_tokens,
            "wall_s": wall,
            "decode_tok_per_s": self.n_decode_tokens / wall if wall else None,
            "latency_p50_s": pct(lat, 50),
            "latency_p95_s": pct(lat, 95),
            "latency_mean_s": mean(lat),
            "ttft_p50_s": pct(ttft, 50),
            "ttft_p95_s": pct(ttft, 95),
            "ttft_mean_s": mean(ttft),
            "kv_pages_high_water": getattr(self.kv, "high_water", 0),
            "kv_pool_bytes": self.kv_pool_bytes,
            "step_temp_bytes": dict(self.step_temp_bytes),
            "paged_attn": self.rt.paged_attn
            if self.sv.layout == "paged" else None,
            "metrics": self.metrics.snapshot(),
            "recompiles": self.tm.jit_watch.snapshot(),
            **({"profile": self._profile,
                "profile_at_step": self._profile_step}
               if self._profile else {}),
        }

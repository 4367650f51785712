"""Slot-based continuous-batching serving engine on the paged KV layout.

A fixed decode batch of ``max_slots`` sequences advances one token per step;
finished sequences retire and their slots are immediately refilled from the
queue.  KV storage goes through :class:`repro.fabric.PagedKVCache`: each
slot's time axis is divided into fixed-size pages (``page_size`` timesteps =
a burst of lines through the fabric).  Under ``FabricConfig.paged_pool``
(the default) the pages live in one **shared physical pool** per
full-attention leaf — free-list allocation at admission and decode growth,
true reclamation at retirement, per-slot logical→physical page table as a
decode-step operand (gather-based attention) — so short and long sequences
share HBM and ``kv.occupancy`` measures real frames.  Admission installs
each wave's page-aligned KV extents through one ``prefill/*`` write burst
(1 network call per dtype; per-layer splice as the off-geometry fallback)
instead of the seed engine's full ``t_max`` splice-copy.  Per-slot
positions are first-class in the decode
path (``models.common._cache_write`` and friends), so slots at different
depths coexist in one batched step — the production pattern behind
vLLM-style serving, on top of the Medusa KV layout engine
(``cfg.resolved_fabric``).

The decode step is the burst scheduler's first production consumer: a
:class:`repro.fabric.BurstScheduler` instance per step hoists every
full-attention leaf's port-major conversion into one shared read burst,
runs attention in port-major space, and restores line-major caches through
one write burst — 1 read + 1 write network invocation per dtype per step
(``fabric_stats``), with the ``serve_fsdp`` weight stream riding the same
read burst.  Bit-identical to the per-layer path.  The bursts ride the
fabric's machine-word lane folding (``FabricConfig.word_fold``) and, on the
medusa fabric with kernels enabled, lower as one fused Pallas launch per
direction per dtype (``fabric_stats.words_folded`` / ``.kernel_bursts``).

Under the **fused-gather contract** (``FabricConfig.fused_gather``, auto-on
with the pool) the pool's logical→physical indirection moves into those
bursts: each engine step plans its live frames host-side
(:func:`repro.models.common.page_live_plan`, bucketed to bound retraces)
and the KV streams become sparse-extent — the networks bank only the
frames the page table maps, so decode traffic scales with live tokens
instead of pool capacity (``fabric_stats.words_live`` /
``.gather_fused_bursts``); the gather-after-burst form stays as the
fallback (``fused_gather=False``) and the bit-parity reference.

**Graceful degradation under oversubscription** (``FabricConfig.preempt``):
requests carry priority classes and optional SLO deadlines, and when a
higher-priority request would otherwise wait on a full pool the engine
preempts live slots — victims picked lowest-priority first, then
most-pages, then LRU — and parks them in a host swap space.  Eviction and
re-admission are fabric traffic like everything else: ``swap/<slot>/*``
sparse-extent streams ride the read network's fused page-table gather out
and the write network's scatter back in
(:meth:`repro.fabric.PagedKVCache.swap_out` / ``swap_in``), parity-checked
end to end, so a preempted request resumes bit-identically.  The vLLM-style
swap-vs-recompute choice (``preempt="recompute"``, or automatically when
the swap space is full or nothing was decoded yet) drops the pages and
re-prefills the sequence so far instead.  Swapped requests re-admit ahead
of the queue.  A :class:`repro.runtime.fault_tolerance.FaultInjector`
plugs into the same path: injected pool exhaustion backs admission off a
step, corrupted swap bursts are caught by the parity word and retried, and
a mid-step failure rolls the engine back to its pre-step snapshot and
replays (``fabric_stats.faults_recovered``).

**Admission control under production-shaped load** closes the scheduling
layer above the fabric: every request is stamped with an ``arrival_step``
at :meth:`submit` (the clock for queue wait, TTFT and aging), the submit
queue is bounded (``max_queue`` — overflow sheds with backpressure instead
of growing without bound), **aging** (``aging=K`` steps per class) raises a
waiting request's *effective* priority so the strict ``(-priority,
deadline, arrival)`` order can no longer starve low classes indefinitely,
and SLO-aware **load shedding** rejects a request at admission the moment
its deadline is provably unmeetable given pool headroom and queue depth —
counted (``requests_shed``/``shed_deadline``/``shed_queue_full``) instead
of missing silently at retirement, with the deadline-miss census split
into ``slo_missed_served`` / ``slo_missed_shed`` by exit path.  The
production-shaped traffic harness driving all of this lives in
:mod:`repro.serving.traffic` (seeded generator, ``MetricsRecorder``
lifecycle stamps, in-process replica router) with the
``launch/loadgen.py`` CLI on top.

**Spans** (``jax.profiler.TraceAnnotation``) mark each step's host phases
in the profiler's own trace, on the device planes' clock: ``serve.step``
round :meth:`step`; inside it ``serve.admit`` (one ``serve.prefill`` per
fresh request, metadata ``rid`` and ``prompt_len``, and for a
mixture-of-experts model ``moe_rows``, the ``prompt_len * top_k`` expert
assignments the prompt routes, computed on the host; then ``serve.install``
for the wave's burst), ``serve.plan`` (the live-frame plan, metadata
``live`` and ``bucket`` frames), ``serve.decode`` (the jitted step's
dispatch), ``serve.sample`` (the host waiting on the argmax) and
``serve.commit``.  With no profiler running a span records nothing and no
compiled program changes.  ``Request.admitted_s`` stamps the host clock
(``time.perf_counter``) as a fresh request's prefill starts.

Decoder-only families (dense/moe/ssm/hybrid/vlm); greedy sampling.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.fabric import (BurstScheduler, Fabric, PagedKVCache,
                          SchedulerStats, SwapRecord, make_pool_mesh,
                          shard_plan)
from repro.fabric.scheduler import FRAME_SENTINEL
from repro.models import api
from repro.models import common as cm
from repro.models import lm
from repro.models import moe as moe_mod


def _lead_prod(flat) -> int:
    """Product of a flattened pool leaf's leading (layer-stack) axes."""
    reps = 1
    for s in flat.shape[:-3]:
        reps *= s
    return reps


@dataclasses.dataclass(eq=False)           # identity equality: the prompt
class Request:                             # array makes field-eq ambiguous
    rid: int
    prompt: np.ndarray                     # [prompt_len] int32
    max_new_tokens: int
    priority: int = 0                      # higher preempts strictly lower
    deadline: Optional[int] = None         # SLO: retire by this engine step
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    arrival_step: int = -1                 # engine step at submit() — the
    #                                        clock for queue wait and aging
    shed_reason: Optional[str] = None      # set when load-shed, never served
    admitted_s: Optional[float] = None     # time.perf_counter() as its
    #                                        prefill starts (fresh admission)
    _seq: int = dataclasses.field(default=0, repr=False)   # submit order


@dataclasses.dataclass
class _Swapped:
    """A preempted request parked in the host swap space.  ``record`` is
    the fabric-staged KV image (swap arm) or ``None`` (recompute arm:
    re-admission re-prefills ``prompt + generated[:-1]``)."""

    req: Request
    record: Optional[SwapRecord]
    pos: int                               # next write position at eviction
    token: int                             # the pending decode token


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, max_slots: int, t_max: int,
                 page_size: int = 0, paged_pool: Optional[bool] = None,
                 pool_pages: int = 0, prefill_burst: Optional[bool] = None,
                 fused_gather: Optional[bool] = None, pool_shards: int = 0,
                 collective: Optional[str] = None,
                 preempt: Optional[str] = None,
                 swap_space_pages: Optional[int] = None,
                 check_pool: bool = False, fault_injector=None,
                 spec_decode_k: int = 0, draft_fn=None,
                 aging: int = 0, max_queue: int = 0, recorder=None):
        assert cfg.family != "audio", "engine covers decoder-only families"
        self.cfg = cfg
        # Medusa-heads speculative decoding (spec_decode_k > 0): every step
        # the model's k draft heads propose a candidate branch per slot and
        # verify_step() accepts its longest prefix against the target's
        # committed argmax — the committed token stream is the dense
        # engine's, bit for bit, because commits only ever come from the
        # real unembedding (row 0 of the step logits).  ``draft_fn(req,
        # committed) -> [k tokens]`` overrides the model heads (tests use an
        # oracle/adversarial proposer); with draft heads, params grow a
        # "draft" entry (auto-initialized when absent).
        self.spec_k = int(spec_decode_k)
        self.draft_fn = draft_fn
        self._model_draft = self.spec_k > 0 and draft_fn is None
        if self._model_draft and "draft" not in params:
            params = dict(params)
            params["draft"] = cm.draft_head_params(
                jax.random.PRNGKey(0x5BEC),
                dataclasses.replace(cfg, spec_heads=self.spec_k),
                cfg.param_dtype)
        if self._model_draft and params["draft"]["w"].shape[0] < self.spec_k:
            raise ValueError(
                f"spec_decode_k={self.spec_k} wants at least that many "
                f"draft heads; params carry "
                f"{params['draft']['w'].shape[0]}")
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rejected = 0
        self._draft_queue: Dict[int, List[int]] = {}
        self.params = params
        self.max_slots = max_slots
        self.t_max = t_max
        # pool-sharded lowering (FabricConfig.pool_shards): the pool axis of
        # every full-attention leaf shards over a `pool` device-mesh axis
        # and the fused sparse bursts become per-shard gathers bridged by
        # one collective (repro.fabric.sharded) — the engine runs unchanged
        # otherwise; 0 inherits the model config's setting
        fab_cfg = cfg.resolved_fabric
        shards = pool_shards or fab_cfg.pool_shards
        if shards > 1 or collective is not None:
            fab_cfg = dataclasses.replace(
                fab_cfg, pool_shards=shards,
                collective=collective or fab_cfg.collective).validate()
        self.pool_shards = shards = fab_cfg.pool_shards
        mesh = make_pool_mesh(shards) if shards > 1 else None
        self.fabric = Fabric(fab_cfg, mesh=mesh)
        # cache depth rounds up so every full-attention leaf's line count
        # divides N and the whole cache moves through the step's shared
        # burst; positions beyond t_max are masked, so this is free capacity
        n = self.fabric.n_ports
        self.t_alloc = -(-t_max // n) * n
        ps = page_size or min(cfg.resolved_fabric.page_size, self.t_alloc)
        self.page_size = ps
        # shared physical page pool (FabricConfig.paged_pool, default on):
        # full-attention leaves become [pool_pages, ps, Hkv, D] regions
        # reached through the per-slot page table; families without
        # full-attention leaves (pure SSM/recurrent) have nothing to pool
        entries = lm.paged_entries(cfg)
        self.paged = ((cfg.resolved_fabric.paged_pool if paged_pool is None
                       else paged_pool) and bool(entries))
        if self.paged:
            pages_per_slot = -(-self.t_alloc // ps)
            pool_pages = pool_pages or max_slots * pages_per_slot
            # the pool rides the decode step's shared burst as one line
            # stream, so its frame count rounds up to a multiple of N; under
            # the sharded lowering it must also split into `shards` equal
            # contiguous page blocks (PartitionSpec("pool") ownership)
            while (pool_pages * ps) % n or pool_pages % shards:
                pool_pages += 1
        else:
            pool_pages = 0
        self.prefill_burst = prefill_burst
        # fused page-table gather (FabricConfig.fused_gather, default on
        # with the pool): the decode step's bursts bank only the frames the
        # page table maps — the engine plans the live set host-side each
        # step and passes it as operands, so network traffic scales with
        # live tokens instead of pool capacity.  Needs a fabric that banks
        # KV at all; the gather-after-burst form stays as the fallback.
        self.fused = ((cfg.resolved_fabric.fused_gather_on
                       if fused_gather is None else fused_gather)
                      and self.paged and self.fabric.banks_kv)
        if shards > 1 and not self.fused:
            raise ValueError(
                f"pool_shards={shards} needs the fused-gather pool contract "
                f"(paged pool + a fabric that banks KV) — the sharded "
                f"lowering is the sparse burst's collective form")
        # live-plan lengths quantize to whole page-of-lines buckets so the
        # jitted step retraces per occupancy *bucket*, not per page; sharded,
        # the bucket also keeps every rep's line total divisible into
        # `shards` blocks of whole N-groups (lcm, so 1 shard is unchanged)
        self.live_bucket = n * math.lcm(ps, shards)
        self.kv = PagedKVCache(
            api.init_cache(cfg, max_slots, self.t_alloc,
                           pool_pages=pool_pages, page_size=ps),
            max_slots, self.t_alloc, ps, pool_pages=pool_pages,
            paged_entries=entries if self.paged else (), fabric=self.fabric,
            fused_gather=self.fused, pool_shards=shards)
        # distinct leading rep counts over the paged leaves — the sharded
        # step carries one (fetch, place) plan per rep count
        self._shard_reps = sorted({
            max(1, _lead_prod(lm._flat_frames(self.kv.caches[kind][i]["k"])))
            for kind, i in entries}) if (self.paged and shards > 1) else []
        self.pos = np.zeros((max_slots,), np.int32)      # next write position
        self.active: List[Optional[Request]] = [None] * max_slots
        self.tokens = np.zeros((max_slots, 1), np.int32)
        self.queue: List[Request] = []
        # the step's [B, V] logits, left on device (readers pay the copy)
        self.last_logits: Optional[jax.Array] = None
        # pool mode: pages reserved per live slot for its full reach
        # (prompt + generation) — admission is the only allocation gate, so
        # decode growth can never exhaust the pool mid-flight
        self._page_reserve: dict = {}
        # preemption policy (FabricConfig.preempt): "swap" parks victims in
        # the host swap space over the fabric, "recompute" drops their pages
        # and re-prefills on re-admission, "off" is the seed head-of-line
        # gate.  Needs the page pool — dense reservations have nothing to
        # reclaim mid-flight.
        pre = fab_cfg.preempt if preempt is None else preempt
        if pre not in ("swap", "recompute", "off"):
            raise ValueError(f"preempt must be 'swap', 'recompute' or "
                             f"'off', got {pre!r}")
        self.preempt = pre if self.paged else "off"
        self.swap_space_pages = (fab_cfg.swap_space_pages
                                 if swap_space_pages is None
                                 else swap_space_pages)
        self.check_pool = check_pool
        self.fault_injector = fault_injector
        self.kv.fault_injector = fault_injector
        self._swapped: Dict[int, _Swapped] = {}      # rid → parked request
        self._admitted_at: dict = {}                 # slot → admission step
        self._swap_pages_used = 0
        self._submit_seq = 0
        self._step_count = 0
        # anti-starvation aging: every `aging` steps a candidate waits past
        # its arrival_step, its *effective* priority rises one class, so the
        # strict (-priority, deadline, arrival) order can no longer starve
        # low classes indefinitely under sustained high-priority churn.
        # 0 = off (the PR 7 strict order, exactly).
        if aging < 0:
            raise ValueError(f"aging must be >= 0 steps/class, got {aging}")
        self.aging = aging
        # bounded submit queue: submit() sheds (backpressure) once this many
        # requests are already queued.  0 = unbounded (the seed behaviour).
        if max_queue < 0:
            raise ValueError(f"max_queue must be >= 0, got {max_queue}")
        self.max_queue = max_queue
        # lifecycle observer (duck-typed, e.g. serving.traffic.
        # MetricsRecorder): record_admit/record_first_token/record_retire/
        # record_shed, all (req, step)-shaped — None = no observation
        self.recorder = recorder

        # one scheduler instance per decode step: per-step KV banking (and
        # the serve_fsdp weight stream) runs as one read + one write network
        # burst per dtype.  ``fabric_stats`` accumulates at trace time, so
        # after the first step it reads as the per-step traffic census
        # (plus one eager prefill burst per admission wave).
        self.fabric_stats = SchedulerStats()

        # MoE dispatch accounting (burst streams + the runtime-exact
        # tokens_dropped counter) routes to the same per-step stats: the
        # sink must be ambient at trace time (repro.models.moe.dispatch_stats)
        draft = self._model_draft
        if self.paged and self.fused and shards > 1:
            def _step(p, tok, caches, pos, page_table, live_idx, expand,
                      dense_pos, shard_plans):
                sched = BurstScheduler(self.fabric, stats=self.fabric_stats)
                with moe_mod.dispatch_stats(self.fabric_stats):
                    return api.decode_fn(
                        p, tok, caches, pos, cfg, sched=sched,
                        page_table=page_table, page_size=ps,
                        t_depth=self.t_alloc,
                        live_plan=(live_idx, expand, dense_pos),
                        shard_plans=shard_plans, draft=draft)
        elif self.paged and self.fused:
            def _step(p, tok, caches, pos, page_table, live_idx, expand,
                      dense_pos):
                sched = BurstScheduler(self.fabric, stats=self.fabric_stats)
                with moe_mod.dispatch_stats(self.fabric_stats):
                    return api.decode_fn(
                        p, tok, caches, pos, cfg, sched=sched,
                        page_table=page_table, page_size=ps,
                        t_depth=self.t_alloc,
                        live_plan=(live_idx, expand, dense_pos), draft=draft)
        elif self.paged:
            def _step(p, tok, caches, pos, page_table):
                sched = BurstScheduler(self.fabric, stats=self.fabric_stats)
                with moe_mod.dispatch_stats(self.fabric_stats):
                    return api.decode_fn(p, tok, caches, pos, cfg,
                                         sched=sched, page_table=page_table,
                                         page_size=ps, t_depth=self.t_alloc,
                                         draft=draft)
        else:
            def _step(p, tok, caches, pos):
                sched = BurstScheduler(self.fabric, stats=self.fabric_stats)
                with moe_mod.dispatch_stats(self.fabric_stats):
                    return api.decode_fn(p, tok, caches, pos, cfg,
                                         sched=sched, draft=draft)

        self._decode = jax.jit(_step)
        # the prefill compiles once per prompt length: an eager call would
        # re-trace and re-lower its layer scan (a new closure each call) on
        # every admission while the device waits
        t_alloc = self.t_alloc

        def _prefill(p, tokens):
            return api.prefill_fn(p, {"tokens": tokens}, cfg, t_alloc)

        self._prefill = jax.jit(_prefill)

    @property
    def caches(self):
        """The batched cache pytree (lives inside the paged wrapper)."""
        return self.kv.caches

    # -- admission -----------------------------------------------------------
    def submit(self, req: Request) -> str:
        """Enqueue a request; returns ``"queued"`` or ``"shed"``.

        Never-servable requests still raise (a prompt the cache can't hold,
        or — in pool mode — a reserved reach larger than the whole pool,
        which would gate the head of the queue forever); a deadlined one
        counts ``slo_missed_shed`` before the raise, so no exit path is
        uncounted.  Two admission-control gates shed instead of queueing
        (``req.shed_reason`` set, census counted, ``done`` marked so
        drivers drain):

        * **backpressure** — the bounded submit queue (``max_queue``) is
          full (``shed_queue_full``);
        * **SLO load shedding** — the deadline is provably unmeetable:
          even admitted *this* step the request cannot retire by its
          deadline, or — with preemption off, so pages and slots free only
          at retirement — the earliest live-slot retirement plus the
          request's own service floor already overshoots it
          (``shed_deadline``).  Rejecting up front beats missing silently
          at retirement.
        """
        if len(req.prompt) + 1 > self.t_max:
            self._count_shed(req, None)        # counted even though raised
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                f"cannot decode within t_max={self.t_max}")
        if self.kv.paged:
            reach = min(len(req.prompt) + req.max_new_tokens, self.t_max)
            need = self.kv.table.pages_for(reach)
            if need > self.kv.pool.n_pages:
                self._count_shed(req, None)
                raise ValueError(
                    f"request {req.rid}: reach of {reach} tokens reserves "
                    f"{need} pages but the pool holds {self.kv.pool.n_pages}"
                    f" — it would block the queue forever")
        req.arrival_step = self._step_count
        if self.max_queue and len(self.queue) >= self.max_queue:
            self._shed(req, "queue_full")
            return "shed"
        if req.deadline is not None and self._provably_unmeetable(req):
            self._shed(req, "deadline")
            return "shed"
        req._seq = self._submit_seq
        self._submit_seq += 1
        self.queue.append(req)
        return "queued"

    # -- SLO-aware load shedding ---------------------------------------------
    def _earliest_retire(self, req: Request, admit_step: int) -> int:
        """The provably earliest step ``req`` can retire if (re-)admitted at
        ``admit_step``: one committed token per engine step, plus the
        prefill's first token for fresh requests, capped by the cache depth
        (the ``pos + 1 >= t_max`` retirement arm) — exact, not heuristic,
        so shedding on it can never reject a meetable request."""
        g = len(req.generated)
        # fresh install appends the prefill argmax AND decodes in the same
        # step (+2); a swap-in resumes with its pending token (+1)
        first_step_tokens = 2 if g == 0 else 1
        by_tokens = req.max_new_tokens - g - first_step_tokens
        by_depth = self.t_max - len(req.prompt) - g - first_step_tokens
        return admit_step + max(0, min(by_tokens, by_depth))

    def _provably_unmeetable(self, req: Request) -> bool:
        """True when ``req.deadline`` cannot be met under ANY schedule.  The
        base proof assumes immediate admission; with preemption off the
        admission floor tightens — slots and pages free only at retirement,
        so when none are available now, the earliest admission is one step
        past the earliest *exact* live retirement (queue depth and pool
        headroom can only push it later, never earlier)."""
        admit = self._step_count
        if self.preempt == "off" and self.aging == 0:
            live = [s for s in range(self.max_slots)
                    if self.active[s] is not None]
            blocked = len(live) == self.max_slots
            if self.kv.paged and not blocked:
                reach = min(len(req.prompt) + req.max_new_tokens, self.t_max)
                blocked = (self._pool_headroom()
                           < self.kv.table.pages_for(reach))
            if blocked and live:
                admit = 1 + min(
                    self._earliest_retire(self.active[s], self._step_count)
                    for s in live)
        return self._earliest_retire(req, admit) > req.deadline

    def _count_shed(self, req: Request, reason: Optional[str]) -> None:
        stats = self.fabric_stats
        stats.requests_shed += 1
        if reason == "queue_full":
            stats.shed_queue_full += 1
        elif reason == "deadline":
            stats.shed_deadline += 1
        if req.deadline is not None:
            stats.slo_missed_shed += 1

    def _shed(self, req: Request, reason: str) -> None:
        """Reject ``req`` at admission with a counted reason — the request
        is marked done-without-output so drivers drain, and the deadline
        miss (if any) lands in ``slo_missed_shed`` instead of vanishing."""
        self._count_shed(req, reason)
        req.shed_reason = reason
        req.done = True
        if self.recorder is not None:
            self.recorder.record_shed(req, self._step_count, reason)

    def _shed_unmeetable_queued(self) -> None:
        """Admission-time recheck: a queued (or parked) request whose
        deadline became provably unmeetable while it waited is shed *now*
        — so a deadlined request can never sit in the queue past its
        deadline, and the drain census has no silent residue.  Parked
        victims release their swap space."""
        for req in [r for r in self.queue if r.deadline is not None]:
            if (self._earliest_retire(req, self._step_count) > req.deadline):
                self.queue.remove(req)
                self._shed(req, "deadline")
        for rid, sw in list(self._swapped.items()):
            req = sw.req
            if req.deadline is None:
                continue
            if self._earliest_retire(req, self._step_count) > req.deadline:
                del self._swapped[rid]
                if sw.record is not None:
                    self._swap_pages_used -= sw.record.mapped
                self._shed(req, "deadline")

    def _eff_priority(self, req: Request) -> int:
        """Effective priority under anti-starvation aging: the raw class
        plus one for every ``aging`` steps waited since arrival.  Both
        admission rank and preemption eligibility use it, so an aged
        request is not just admitted ahead of fresh higher classes — it
        can preempt them, and they cannot evict it back (its age only
        grows), which bounds every request's wait."""
        if not self.aging or req.arrival_step < 0:
            return req.priority
        return req.priority + (self._step_count - req.arrival_step) // self.aging

    def _rank(self, req: Request):
        """Admission order: effective priority class first (aging boosts
        queued wait — raw priority exactly when ``aging == 0``), earliest
        SLO deadline next, submit order last (FIFO within a class —
        uniform priorities reduce to the seed's queue order exactly)."""
        dl = float("inf") if req.deadline is None else req.deadline
        return (-self._eff_priority(req), dl, req._seq)

    def _candidates(self) -> list:
        """Admissible work, best first.  Swapped requests re-admit ahead of
        everything still queued in their priority class — their submit
        stamp predates it (their pages were taken, not their turn) — but a
        higher class still outranks them, so a parked victim can never
        head-of-line-block the very traffic that preempted it."""
        cands = list(self._swapped.values()) + list(self.queue)
        return sorted(cands, key=lambda c: self._rank(
            c.req if isinstance(c, _Swapped) else c))

    def _admit(self) -> None:
        """Fill slots from the swap space and the queue in priority order:
        prefill each prompt, then install the whole wave's page-aligned KV
        extents through ONE write-network flush (``prefill/*`` streams —
        ``fabric_stats.prefill_bursts``), with the per-layer splice as the
        off-geometry fallback; swap-ins restore eagerly (one ``swap/*``
        flush per slot).  Pool mode gates on free pages (head-of-line
        within the priority order; retirement reclaims) — and when the best
        candidate outranks live work, preempts victims instead of waiting
        (:meth:`_make_room`).  An injected pool-exhaustion fault backs the
        whole wave off for the step."""
        with TraceAnnotation("serve.admit"):
            self._shed_unmeetable_queued()
            if (self.kv.paged and self.fault_injector is not None
                    and self.fault_injector.pool_exhausted(self._step_count)):
                return
            wave: list = []
            protected: set = set()     # slots filled this wave: not victims
            while True:
                cands = self._candidates()
                if not cands:
                    break
                cand = cands[0]
                req = cand.req if isinstance(cand, _Swapped) else cand
                free = [s for s in range(self.max_slots)
                        if self.active[s] is None]
                if self.kv.paged:
                    # reserve the request's full reach (prompt + generation,
                    # capped by the cache depth) so decode growth can never
                    # exhaust the pool mid-flight: admission is the only gate
                    reach = min(len(req.prompt) + req.max_new_tokens,
                                self.t_max)
                    need = self.kv.table.pages_for(reach)
                    if not free or self._pool_headroom() < need:
                        if not self._make_room(req, need, protected,
                                               have_slot=bool(free)):
                            break        # wait for pages to be reclaimed
                        free = [s for s in range(self.max_slots)
                                if self.active[s] is None]
                    self._page_reserve[free[0]] = need
                elif not free:
                    break
                slot = free[0]
                protected.add(slot)
                self._install(cand, slot, wave)
            if wave:
                with TraceAnnotation("serve.install"):
                    self.kv.admit_wave(wave, stats=self.fabric_stats,
                                       burst=self.prefill_burst)

    def _install(self, cand, slot: int, wave: list) -> None:
        """Land one candidate in ``slot``: fresh requests prefill into the
        wave; swapped requests either restore over the fabric (swap arm) or
        re-prefill everything decoded so far (recompute arm) — both resume
        the exact pre-eviction state (cache = ``prompt + generated[:-1]``,
        the last token still pending decode)."""
        req0 = cand.req if isinstance(cand, _Swapped) else cand
        if self.aging and self._eff_priority(req0) > req0.priority:
            self.fabric_stats.aging_promotions += 1
        if isinstance(cand, _Swapped):
            req = cand.req
            del self._swapped[req.rid]
            self.active[slot] = req
            self.pos[slot] = cand.pos
            self.tokens[slot, 0] = cand.token
            if cand.record is not None:
                self.kv.swap_in(slot, cand.record, stats=self.fabric_stats)
                self._swap_pages_used -= cand.record.mapped
            else:
                full = np.concatenate([np.asarray(req.prompt, np.int32),
                                       np.asarray(req.generated[:-1],
                                                  np.int32)])
                _, req_cache = self._prefill(
                    self.params, jnp.asarray(full)[None, :])
                wave.append((slot, req_cache, len(full)))
        else:
            req = cand
            self.queue.remove(req)
            req.admitted_s = time.perf_counter()
            meta = {"rid": req.rid, "prompt_len": len(req.prompt)}
            if self.cfg.moe is not None:
                meta["moe_rows"] = len(req.prompt) * self.cfg.moe.top_k
            with TraceAnnotation("serve.prefill", **meta):
                prompt = jnp.asarray(req.prompt)[None, :]
                logits, req_cache = self._prefill(self.params, prompt)
                # page remap: only the pages the prompt occupies move
                wave.append((slot, req_cache, len(req.prompt)))
                self.active[slot] = req
                self.pos[slot] = len(req.prompt)
                first = int(np.argmax(np.asarray(logits[0, -1])))
            req.generated.append(first)
            self.tokens[slot, 0] = first
            if self.recorder is not None:
                self.recorder.record_first_token(req, self._step_count)
        if self.recorder is not None:
            self.recorder.record_admit(req, self._step_count)
        self._admitted_at[slot] = self._step_count
        # draft branches are a per-tenure cache: a slot changing hands (or
        # a request resuming after eviction) starts with a drained branch
        self._draft_queue.pop(slot, None)

    # -- preemption ----------------------------------------------------------
    def _make_room(self, req: Request, need: int, protected: set,
                   have_slot: bool) -> bool:
        """Evict strictly-lower-priority live slots until ``req`` has a
        slot and ``need`` pages of headroom.  Victim order: lowest priority
        first, then most mapped pages (fewest evictions), then oldest
        admission (LRU).  All-or-nothing: if even every eligible victim
        wouldn't make room, nothing is evicted."""
        if self.preempt == "off":
            return False
        # effective (aged) priorities on both sides: an aged candidate can
        # evict fresher high classes, and once admitted its own growing age
        # shields it from them — without aging this is raw priority exactly
        victims = [s for s in range(self.max_slots)
                   if self.active[s] is not None and s not in protected
                   and (self._eff_priority(self.active[s])
                        < self._eff_priority(req))]
        victims.sort(key=lambda s: (self._eff_priority(self.active[s]),
                                    -self.kv.pool.mapped(s),
                                    self._admitted_at.get(s, 0)))
        headroom = self._pool_headroom()
        chosen = []
        for s in victims:
            if have_slot and headroom >= need:
                break
            # freeing s returns its mapped pages AND retires its reserve
            headroom += max(self.kv.pool.mapped(s),
                            self._page_reserve.get(s, 0))
            have_slot = True
            chosen.append(s)
        if not (have_slot and headroom >= need):
            return False
        for s in chosen:
            self._preempt_slot(s)
        return True

    def _preempt_slot(self, slot: int) -> None:
        """Evict one live slot.  Swap arm: stage its KV image out over the
        fabric (``swap/*`` gather streams) into the host swap space.
        Recompute arm — chosen by config, when the swap-space cap is
        reached, or when nothing has been decoded yet (re-prefilling the
        prompt is the same work with no swap-space cost) — just drops the
        pages."""
        req = self.active[slot]
        use_swap = self.preempt == "swap" and len(req.generated) > 1
        if use_swap and self.swap_space_pages:
            if (self._swap_pages_used + self.kv.pool.mapped(slot)
                    > self.swap_space_pages):
                use_swap = False
        if use_swap:
            record = self.kv.swap_out(slot, stats=self.fabric_stats)
            self._swap_pages_used += record.mapped
        else:
            record = None
            self.kv.free(slot)
        self._swapped[req.rid] = _Swapped(
            req=req, record=record, pos=int(self.pos[slot]),
            token=int(self.tokens[slot, 0]))
        self.active[slot] = None
        self._page_reserve.pop(slot, None)
        self._admitted_at.pop(slot, None)
        self.fabric_stats.preemptions += 1

    def _pool_headroom(self) -> int:
        """Free pages not spoken for by live slots' unexpanded reaches."""
        return self.kv.pool.free_pages - sum(
            max(0, need - self.kv.pool.mapped(s))
            for s, need in self._page_reserve.items())

    # -- one engine step -----------------------------------------------------
    def step(self) -> int:
        """Admit + one batched decode step; returns #active sequences.

        With a fault injector attached, the engine snapshots its full state
        before the step; an injected mid-step failure rolls back to that
        snapshot and replays the step (``fabric_stats.faults_recovered``) —
        the replay is deterministic, so recovery is bit-exact.  With
        ``check_pool`` the free-list conservation invariant runs after
        every step."""
        with TraceAnnotation("serve.step"):
            step_no = self._step_count
            snap = (self._snapshot() if self.fault_injector is not None
                    else None)
            try:
                n_live = self._step_inner(step_no)
            except RuntimeError:
                if snap is None:
                    raise
                self._restore(snap)
                self.fabric_stats.faults_recovered += 1
                n_live = self._step_inner(step_no)
            self._step_count = step_no + 1
            if self.check_pool and self.kv.paged:
                self.kv.pool.check()
            return n_live

    def _step_inner(self, step_no: int) -> int:
        self._admit()
        if self.fault_injector is not None:
            self.fault_injector.check(step_no)     # mid-step failure seam
        live = [s for s in range(self.max_slots) if self.active[s] is not None]
        if not live:
            return 0
        args = self._decode_args()
        with TraceAnnotation("serve.decode"):
            logits, new_caches = self._decode(*args)
        self.kv.update(new_caches)
        with TraceAnnotation("serve.sample"):
            self.last_logits = logits[:, 0]
            # commits only ever read row 0 — the real unembedding — so the
            # token stream is the dense engine's regardless of spec_decode_k
            nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
            drafts = None
            if self._model_draft:
                drafts = np.asarray(jnp.argmax(
                    logits[:, 1:1 + self.spec_k], axis=-1), np.int32)
        with TraceAnnotation("serve.commit"):
            for s in live:
                req = self.active[s]
                self.pos[s] += 1
                self.kv.extend(s, int(self.pos[s]))
                req.generated.append(int(nxt[s]))
                self.tokens[s, 0] = int(nxt[s])
                if self.spec_k:
                    self.verify_step(s, req, int(nxt[s]),
                                     None if drafts is None else drafts[s])
                if (len(req.generated) >= req.max_new_tokens
                        or self.pos[s] + 1 >= self.t_max):
                    req.done = True
                    if req.deadline is not None and step_no > req.deadline:
                        self.fabric_stats.slo_missed_served += 1
                    if self.recorder is not None:
                        self.recorder.record_retire(req, step_no)
                    self.active[s] = None
                    # return the slot's pages (true reclamation, pool mode);
                    # stale frames are masked by the per-slot positions and
                    # overwritten on the next admission
                    self.kv.free(s)
                    self._page_reserve.pop(s, None)
                    self._admitted_at.pop(s, None)
                    self._draft_queue.pop(s, None)
        return len([s for s in range(self.max_slots)
                    if self.active[s] is not None])

    def _decode_args(self) -> tuple:
        """The jitted decode step's operands for the current batch state."""
        with TraceAnnotation("serve.plan") as span:
            args = (self.params, jnp.asarray(self.tokens), self.kv.caches,
                    jnp.asarray(self.pos))
            if self.paged and self.fused:
                live_idx, expand, dense_pos = cm.page_live_plan(
                    self.kv.pool.table, self.page_size, self.t_alloc,
                    self.fabric.n_ports, bucket=self.live_bucket)
                # frames the step's bursts move, of the bucket they walk
                span.set_metadata(
                    live=int(np.count_nonzero(live_idx != FRAME_SENTINEL)),
                    bucket=len(live_idx))
                args += (self.kv.page_table_device(), jnp.asarray(live_idx),
                         jnp.asarray(expand), jnp.asarray(dense_pos))
                if self.pool_shards > 1:
                    # host-side split of the live set by owning shard: one
                    # fetch/place plan per distinct leaf rep count (the bucket
                    # capacity quantizes to whole pages to bound retraces)
                    frames = self.kv.pool.n_pages * self.page_size
                    args += ({
                        reps: shard_plan(live_idx, frames, self.pool_shards,
                                         self.fabric.n_ports, reps=reps,
                                         cap_bucket=self.page_size).operands()
                        for reps in self._shard_reps},)
            elif self.paged:
                args += (self.kv.page_table_device(),)
            return args

    def decode_step_text(self) -> str:
        """Optimized HLO text of the decode step for the current batch
        state (compiled here, or found in the compilation cache): what the
        device runs, e.g. to check that the Pallas burst kernels are in it
        (``tpu_custom_call`` on a TPU)."""
        return self._decode.lower(*self._decode_args()).compile().as_text()

    # -- speculative decoding -------------------------------------------------
    def verify_step(self, slot: int, req: Request, committed: int,
                    drafts) -> None:
        """Verify one level of the slot's draft branch against the target's
        committed token (longest-matching-prefix acceptance, unrolled one
        token per engine step).  The slot's candidate branch prefix rides
        the step's existing fused page-table gather — all k candidates
        share the committed prefix, so the ``gather=`` streams that banked
        the slot's live frames for the target ARE the branch gather; no new
        kernel, and the census's per-step ``words_live`` is the gathered
        branch traffic.  A match pops the branch head
        (``spec_accepted``); a mismatch discards the remaining branch
        (``spec_rejected`` — the committed argmax is itself the correction
        token, so nothing needs re-decoding); a drained branch takes on k
        fresh proposals from the draft heads (or ``draft_fn``)."""
        q = self._draft_queue.get(slot)
        if q:
            if q[0] == committed:
                self.spec_accepted += 1
                q.pop(0)
            else:
                self.spec_rejected += len(q)
                q.clear()
        if not self._draft_queue.get(slot):
            if self.draft_fn is not None:
                prop = self.draft_fn(req, committed)
            else:
                prop = [] if drafts is None else [int(x) for x in drafts]
            prop = list(prop)[:self.spec_k]
            if prop:
                self._draft_queue[slot] = prop
                self.spec_proposed += len(prop)

    @property
    def spec_acceptance(self) -> float:
        """Fraction of proposed draft tokens the target verified."""
        return self.spec_accepted / max(1, self.spec_proposed)

    @property
    def step_count(self) -> int:
        """Engine steps taken so far — the clock every lifecycle stamp,
        deadline and aging computation is measured in."""
        return self._step_count

    @property
    def drained(self) -> bool:
        """No live, queued or parked work left."""
        return (not self.queue and not self._swapped
                and all(r is None for r in self.active))

    @property
    def slo_misses(self) -> int:
        """Total deadline misses across every exit path: late retirements
        (``slo_missed_served``) plus deadlined requests shed at admission
        or from the queue (``slo_missed_shed``).  The pre-harness counter
        only saw the first kind."""
        return (self.fabric_stats.slo_missed_served
                + self.fabric_stats.slo_missed_shed)

    def pending_census(self) -> str:
        """Why-can't-anything-advance diagnosis: per-class queue depths
        over live, queued and parked work, pool headroom, and swap-space
        occupancy — the stall story ``run_to_completion`` raises with."""
        def by_class(reqs):
            depth: Dict[int, int] = {}
            for r in reqs:
                depth[r.priority] = depth.get(r.priority, 0) + 1
            return ("{" + ", ".join(f"class{p}: {n}" for p, n in
                                    sorted(depth.items())) + "}"
                    if depth else "{}")
        live = [r for r in self.active if r is not None]
        parked = [w.req for w in self._swapped.values()]
        pool = (f"pool headroom {self._pool_headroom()} of "
                f"{self.kv.pool.n_pages} pages "
                f"({self.kv.pool.free_pages} free)" if self.kv.paged
                else "pool off (dense reservation)")
        cap = self.swap_space_pages or "unbounded"
        return (f"live {by_class(live)}, queued {by_class(self.queue)}, "
                f"swapped {by_class(parked)}; {pool}; "
                f"swap space {self._swap_pages_used} pages used (cap {cap})")

    def run_to_completion(self, max_steps: int = 10_000) -> None:
        """Step until every submitted request retires.  Raises — rather
        than silently returning with work stranded — when ``max_steps``
        runs out first, naming per-class queue depths, pool headroom and
        swap occupancy so the stall is diagnosable."""
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue and not self._swapped:
                return
        pending = (sum(r is not None for r in self.active) + len(self.queue)
                   + len(self._swapped))
        raise RuntimeError(
            f"run_to_completion: {max_steps} steps exhausted with {pending} "
            f"requests still pending — {self.pending_census()} — the "
            f"workload does not fit, or admission is starved")

    # -- fault recovery ------------------------------------------------------
    def _snapshot(self) -> dict:
        """The engine's full pre-step state.  Device arrays are immutable
        (the cache pytree is captured by reference); host state — request
        bookkeeping, page table, free lists, counters — is copied.  Request
        objects are shared with the caller, so only their mutable tail
        (``generated`` length, ``done``) is recorded."""
        reqs = [(r, len(r.generated), r.done) for r in
                (list(self.queue) + [w.req for w in self._swapped.values()]
                 + [r for r in self.active if r is not None])]
        pool = self.kv.pool
        return dict(
            caches=self.kv.caches,
            pos=self.pos.copy(), tokens=self.tokens.copy(),
            active=list(self.active), queue=list(self.queue),
            swapped=dict(self._swapped),
            reserve=dict(self._page_reserve),
            admitted=dict(self._admitted_at),
            swap_used=self._swap_pages_used,
            submit_seq=self._submit_seq,
            last_logits=self.last_logits,
            table_used=self.kv.table.used.copy(),
            dirty=self.kv._dirty.copy(),
            kv_counters=(self.kv.tokens_moved, self.kv.tokens_moved_dense,
                         self.kv.prefill_bursts, self.kv.prefill_splices),
            pool=None if pool is None else (
                pool.table.copy(),
                [list(s) for s in pool._free_by_shard], pool._rr,
                pool.pages_allocated, pool.pages_reclaimed,
                pool.pages_swapped_out, pool.pages_swapped_in),
            stats=dataclasses.replace(self.fabric_stats),
            spec=(self.spec_proposed, self.spec_accepted, self.spec_rejected,
                  {s: list(q) for s, q in self._draft_queue.items()}),
            reqs=reqs)

    def _restore(self, snap: dict) -> None:
        """Roll back to the pre-step snapshot (restore-from-last-consistent-
        state).  ``fabric_stats`` is restored field-in-place — the jitted
        step closed over the instance, so its identity must survive."""
        self.kv.update(snap["caches"])
        self.pos[:] = snap["pos"]
        self.tokens[:] = snap["tokens"]
        self.active = snap["active"]
        self.queue = snap["queue"]
        self._swapped = snap["swapped"]
        self._page_reserve = snap["reserve"]
        self._admitted_at = snap["admitted"]
        self._swap_pages_used = snap["swap_used"]
        self._submit_seq = snap["submit_seq"]
        self.last_logits = snap["last_logits"]
        self.kv.table.used[:] = snap["table_used"]
        self.kv._dirty[:] = snap["dirty"]
        (self.kv.tokens_moved, self.kv.tokens_moved_dense,
         self.kv.prefill_bursts, self.kv.prefill_splices) = snap["kv_counters"]
        if snap["pool"] is not None:
            pool = self.kv.pool
            (table, free, rr, alloc, reclaimed, s_out, s_in) = snap["pool"]
            pool.table[:] = table
            pool._free_by_shard = [list(s) for s in free]
            pool._rr = rr
            pool.pages_allocated = alloc
            pool.pages_reclaimed = reclaimed
            pool.pages_swapped_out = s_out
            pool.pages_swapped_in = s_in
        for f in dataclasses.fields(SchedulerStats):
            setattr(self.fabric_stats, f.name, getattr(snap["stats"], f.name))
        (self.spec_proposed, self.spec_accepted, self.spec_rejected,
         queues) = snap["spec"]
        self._draft_queue = {s: list(q) for s, q in queues.items()}
        for r, n_gen, done in snap["reqs"]:
            del r.generated[n_gen:]
            r.done = done

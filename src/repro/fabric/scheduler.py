"""Burst scheduler: many logical streams, one network invocation per step.

The paper's burst machinery (§III-C: MaxBurstLen-deep banks, per-port
head/tail pointers, interference-free progress — modelled cycle-by-cycle in
:mod:`repro.core.burst`) exists so that *independent* traffic shares one
physical transposition network.  This module is the framework-level
generalisation: consumers (KV read, KV write, weight stream, MoE expert
dispatch) declare logical streams against a shared :class:`Fabric`; at each
step the scheduler merges every queued stream into one burst per direction
and dtype, runs the read (resp. write) network once per burst, and hands
each consumer its slice back.

Packing (``pack="packed"``, the default)
----------------------------------------
The words of a line move independently through the network (the transpose
acts on the (line, word-index) axes; the word payload rides along), so a
stream of ``k*N`` lines with ``W`` payload elements per word is *exactly*
the same traffic as ``N`` lines with ``k*W`` payload elements — the line
groups fold into the word axis.  Streams sharing a dtype therefore
normalise to ``[N, N, k_i*W_i]`` tiles and concatenate along the word axis
into one ``[N, N, W_total]`` burst: the network moves **zero padding**, and
each stream's ``(offset, words)`` extent within the burst is recorded on
its :class:`PortSpec` — the framework form of the paper's per-port
head/tail pointers into the shared deep-narrow banks.  ``pack="pad"`` keeps
the old pad-to-widest line-axis concatenation for A/B benchmarking.
Streams of different dtypes cannot share a burst bit-identically, so the
scheduler keeps one burst per dtype and direction either way.

Machine-word lane folding (``word_fold``)
-----------------------------------------
Payloads travel as machine words (same-width unsigned-integer views), and on
packed bursts adjacent narrow words additionally *fold* into wider machine
words before the network runs: bf16/u16 pairs ride u32 lanes, and under x64
pairs/quads ride u64 — halving/quartering the lane count every exchange
stage touches, for the same total bits.  This is the framework form of the
paper's premise that the unit moves whole ``W_line``-bit lines per cycle
(§III): the network never cares what a "word" is, so the scheduler picks the
widest machine word the dtype and stream geometry allow.  The fold factor is
per dtype group — the largest ``f ≤ word_fold`` (``"auto"`` = 4) every
member stream supports, where a stream supports ``f`` when ``f`` divides its
per-group word count (fold adjacent words of a line group, applied as part
of the packing bitcast) or its group count (fold corresponding words of
adjacent groups; the word-axis order inside a stream's extent is a scheduler
internal).  Odd word counts therefore degrade the group to a narrower fold,
never to an error, and the unfold on arrival is an exact bitcast — parity is
guaranteed because the networks are pure word movement.  ``pack="pad"``
folds too — on its padded word axis (the factor must divide the padded
width ``w_max``), so the pack A/B isolates packing from lane width.  At
``word_fold=1`` the pad layout is byte-for-byte the PR 1 baseline (raw
payload dtype, no integer view).  On XLA:CPU the fold is
roughly wall-clock-neutral (the widening view costs what the lane savings
recoup); it exists to model TPU lane packing, where a u32/u64 lane is the
unit the VPU actually moves — and it halves/quarters the elements every
select the exchange network emits touches.

Issue/commit pipeline (§III-C double buffer)
--------------------------------------------
``flush()`` is split into :meth:`issue` (dispatch the queued bursts through
the network) and :meth:`commit` (adopt the results).  The pipeline is one
deep: after ``issue()`` the *next* burst's streams may be enqueued while
the consumer computes on the previous ``commit()``'s results — under JAX's
async dispatch (and inside ``jit``, under XLA's scheduler) the issued
transfer genuinely overlaps consumer compute, which is the paper's
input/output double buffer expressed once for every consumer.  ``flush()``
remains as ``issue(); commit()`` for synchronous callers.

Sparse-extent streams (the fused page-table gather)
---------------------------------------------------
A paged KV pool's consumer only needs the frames its page table maps, so a
stream may be enqueued with an explicit frame-index operand:
``enqueue_read(..., gather=idx)`` names the live lines of a larger backing
stream (sentinel entries — indices past the backing extent — read as zero
frames), and ``enqueue_write(..., scatter=idx, into=pool_lines)`` lands the
moved lines back at their indexed pool rows (sentinels drop, untouched rows
never move).  The burst then carries ``len(idx)`` frames instead of the
pool's — decode traffic scales with live tokens, not pool capacity.  On the
unrolled path the gather lowers as a take feeding the shared packed burst
(still one network call per dtype, the indexed lines packed next to the
dense streams); on the kernelized medusa fabric each sparse stream lowers
through the fused gather/scatter burst kernel with the indices as a
scalar-prefetched operand (one launch per stream — indirection + exchange
fused, no materialized full-pool intermediate).  Both lowerings are
bit-identical to the gather-after-burst form by construction (the networks
are pure word movement, and take commutes with them).

``stats`` distinguishes ``flushes`` (issue/commit cycles) from
``network_calls`` (one per direction and dtype present in a burst) and
counts moved vs padded word-axis elements, which is exactly the contrast
``benchmarks/fabric_unified.py`` measures against per-consumer
:class:`Fabric` calls.  ``words_live``/``gather_fused_bursts`` single out
the sparse-extent traffic (see :class:`SchedulerStats`).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import PortSpec
from repro.fabric.fabric import Fabric


@dataclasses.dataclass
class SchedulerStats:
    """Traffic accounting for a :class:`BurstScheduler`.

    ``flushes`` counts issue/commit cycles (a ``flush()`` is one);
    ``network_calls`` counts actual read-/write-network invocations — one
    per (direction, dtype) group present in a burst, so a flush carrying
    bf16 reads, f32 reads and bf16 writes is 1 flush but 3 network calls.
    ``words_moved``/``words_padded`` count word-axis elements carried by the
    network: moved is the payload consumers asked for, padded is the zero
    fill the ``pack="pad"`` layout adds (always 0 under ``pack="packed"``).
    ``words_folded`` counts the word-axis elements machine-word folding
    removed from the network's lane view (they ride inside wider machine
    words instead — a fold of 2 folds away half of a burst's elements), so
    ``words_moved - words_folded`` is the post-fold lane traffic the network
    actually touches (for the pad layout the fold rides the padded width,
    so folded counts include padding riding wider lanes).  ``words_written``
    is the write direction's share of ``words_moved`` (a fused decode step
    writes its fresh frames only, so its share does not grow with the live
    frames its reads carry).  ``kernel_bursts``
    counts the network calls that lowered through the fused single-kernel
    burst path (:meth:`repro.fabric.Fabric.read_burst` with kernels
    enabled).  ``prefill_bursts`` counts admission waves the serving engine
    installed through one shared write burst (``prefill/*`` streams — see
    :meth:`repro.fabric.PagedKVCache.admit_wave`) instead of per-layer
    splices.

    ``words_live`` counts the word-axis elements carried for sparse-extent
    (gather/scatter-indexed) streams — the fused page-table contract's
    traffic, which scales with live frames; a fused decode step shows
    ``words_live > 0`` where the gather-after-burst fallback moves the
    whole pool as ordinary ``words_moved`` with ``words_live == 0``.
    ``gather_fused_bursts`` counts the network calls that carried at least
    one sparse-extent stream (on the kernelized path, the fused
    gather/scatter launches themselves) — the printed census can now tell
    fused from fallback decode.

    ``words_cross_shard``/``collective_calls`` single out the pool-sharded
    lowering (``FabricConfig.pool_shards > 1``): each sharded sparse burst
    is one ``collective_call`` (the exchange hop between the per-shard
    fused gathers), and ``words_cross_shard`` counts the word-axis elements
    of the exchange buffer's off-diagonal blocks — the words that
    physically leave their owning shard, including bucket padding (the
    collective moves whole padded buckets; the diagonal block stays local).
    ``words_cross_shard < words_moved`` is the locality win the sharded
    bench cells assert: with round-robin page striping roughly ``(S-1)/S``
    of the live traffic crosses, never all of it.

    The graceful-degradation counters cover the serving engine's
    oversubscription path: ``preemptions`` counts victim slots evicted so a
    higher-priority request could run; ``swap_bursts``/``swap_out_words``/
    ``swap_in_words`` count the ``swap/*`` sparse-extent streams that stage
    a victim's live frames to host memory over the read network and restore
    them over the write network (swap traffic is burst traffic — counted,
    packed and bit-exact like every other stream); ``bursts_retried``
    counts swap transfers re-run after an end-to-end parity-word mismatch
    (injected corruption); ``faults_recovered`` counts engine steps that
    rolled back to the last consistent state and replayed after an
    injected mid-step failure.

    The admission-control counters extend the graceful-degradation census
    into the scheduling layer above the fabric: ``requests_shed`` counts
    requests rejected at admission instead of missing silently —
    ``shed_queue_full`` of them bounced off the bounded submit queue
    (backpressure), ``shed_deadline`` were load-shed because their SLO
    deadline was provably unmeetable given pool headroom and queue depth.
    ``slo_missed_served`` / ``slo_missed_shed`` split the deadline-miss
    census by exit path: a deadlined request that retires late counts
    *served*, one that exits any other way (shed at submit, shed from the
    queue once provably unmeetable, rejected as never-servable) counts
    *shed* — every deadlined request is counted at exactly one exit, so the
    two sum to the true miss count (the old ``slo_misses`` counted only
    late retirements).  ``aging_promotions`` counts admissions where
    anti-starvation aging had boosted the candidate's effective priority
    above its raw class (queued wait divided by the engine's ``aging``
    quantum) — the census evidence that the fairness mechanism, not raw
    rank, got the request in.

    ``tokens_dropped`` counts token→expert assignments the MoE capacity
    dispatch dropped (rank past the static per-expert capacity — their
    scatter indices became sentinels and the residual passed through).
    Unlike the trace-time word counters it is runtime-exact: drop counts
    are data-dependent, so a traced ``moe_apply`` accumulates them through
    a debug callback that fires once per executed dispatch (per layer, per
    step), never once per trace.  Before this counter a dropped token was
    indistinguishable from a routed one in every census.
    """
    streams_served: int = 0
    flushes: int = 0
    network_calls: int = 0
    words_moved: int = 0
    words_written: int = 0
    words_padded: int = 0
    words_folded: int = 0
    words_live: int = 0
    words_cross_shard: int = 0
    kernel_bursts: int = 0
    gather_fused_bursts: int = 0
    prefill_bursts: int = 0
    collective_calls: int = 0
    preemptions: int = 0
    swap_bursts: int = 0
    swap_out_words: int = 0
    swap_in_words: int = 0
    bursts_retried: int = 0
    faults_recovered: int = 0
    requests_shed: int = 0
    shed_queue_full: int = 0
    shed_deadline: int = 0
    slo_missed_served: int = 0
    slo_missed_shed: int = 0
    aging_promotions: int = 0
    tokens_dropped: int = 0

    @property
    def calls_saved(self) -> int:
        return self.streams_served - self.network_calls


@dataclasses.dataclass
class _Queued:
    spec: PortSpec
    payload: jax.Array            # lines [L, N, *rest] or banked [G, N, N, *rest]
    rest_shape: Tuple[int, ...]
    width: int                    # prod(rest) — payload elements per word
    groups: int                   # line groups (L // N, resp. G)
    # sparse extent (fused page-table gather): reads carry `gather` frame
    # indices into the payload's line axis; writes carry `scatter` target
    # rows plus the pool stream `into` they land in
    gather: Optional[jax.Array] = None
    scatter: Optional[jax.Array] = None
    into: Optional[jax.Array] = None
    # pool-sharded sparse extent: `(fetch, place, k_tot)` from
    # ``repro.fabric.sharded.shard_plan`` — the stream lowers as per-shard
    # fused gathers bridged by one collective instead of a single-device
    # gather (reads: payload is the sharded pool stream [R, F, N, *rest];
    # writes: payload is banked and `into` is the sharded pool stream)
    shard: Optional[Tuple] = None

    @property
    def sparse(self) -> bool:
        return (self.gather is not None or self.scatter is not None
                or self.shard is not None)


class BurstScheduler:
    """Batch queued read/write streams through one network call per burst.

    ``pack`` defaults to the fabric's :attr:`FabricConfig.pack` and
    ``word_fold`` to its :attr:`FabricConfig.word_fold`; pass an external
    :class:`SchedulerStats` to accumulate traffic accounting across
    scheduler instances (e.g. one instance per traced decode step).
    """

    def __init__(self, fabric: Fabric, pack: Optional[str] = None,
                 word_fold=None, stats: Optional[SchedulerStats] = None):
        self.fabric = fabric
        self.pack = pack or fabric.config.pack
        if self.pack not in ("packed", "pad"):
            raise ValueError(f"unknown burst packing {self.pack!r}")
        self.word_fold = (fabric.config.word_fold if word_fold is None
                          else word_fold)
        if self.word_fold not in ("auto", 1, 2, 4):
            raise ValueError(f"word_fold must be 'auto', 1, 2 or 4, "
                             f"got {self.word_fold!r}")
        self.stats = stats if stats is not None else SchedulerStats()
        self._reads: List[_Queued] = []
        self._writes: List[_Queued] = []
        self._inflight: Optional[Dict[str, jax.Array]] = None

    # -- enqueue ---------------------------------------------------------------
    def _check_name(self, name: str) -> None:
        # commit() keys results by stream name; a duplicate (even read vs
        # write) would silently shadow one result
        if any(q.spec.name == name for q in self._reads + self._writes):
            raise ValueError(
                f"stream {name!r} already queued for this burst; give each "
                f"logical port a distinct name (e.g. 'kv_read'/'kv_write')")

    def _extent(self, queue: List[_Queued], dtype) -> int:
        """Word-axis offset of the next stream within its dtype group."""
        return sum(q.spec.words for q in queue
                   if jnp.dtype(q.payload.dtype) == dtype)

    def enqueue_read(self, name: str, lines: jax.Array,
                     gather: Optional[jax.Array] = None,
                     shard: Optional[Tuple] = None) -> PortSpec:
        """Queue a line stream ``[L, N, *rest]`` (L a multiple of N) for the
        read network.  Returns the :class:`PortSpec` keying the result, with
        the stream's packed-burst ``(offset, words)`` extent filled in.

        ``gather`` makes the stream sparse-extent (the fused page-table
        gather): ``lines`` is the full backing pool and ``gather [K]``
        (K a multiple of N; entries ``>= L`` are sentinels reading as zero
        frames) names the live lines — the burst carries only those, and the
        result is the banked ``[K//N, N, N, *rest]`` of the addressed
        frames.  The spec's ``words`` is the live extent; ``pool_words``
        records what the gather-after-burst fallback would have moved.

        ``shard = (fetch, place, k_tot)`` (from
        :func:`repro.fabric.sharded.shard_plan`) is the pool-sharded form of
        ``gather``: ``lines`` is the rep-major pool stream ``[R, F, N,
        *rest]`` with its frame axis sharded over the ``pool`` mesh axis,
        and the stream lowers as per-shard fused gathers bridged by one
        collective — same banked ``[k_tot//N, N, N, *rest]`` result, bit
        for bit."""
        n = self.fabric.n_ports
        self._check_name(name)
        if shard is not None:
            if gather is not None:
                raise ValueError(f"stream {name!r}: shard= and gather= are "
                                 f"mutually exclusive lowerings")
            if lines.ndim < 3 or lines.shape[2] != n:
                raise ValueError(
                    f"stream {name!r}: sharded read wants the rep-major pool "
                    f"stream [R, F, N, ...] for N={n}, got {lines.shape}")
            fetch, place, k_tot = shard
            s = fetch.shape[0]
            if k_tot % (s * n):
                raise ValueError(
                    f"stream {name!r}: k_tot={k_tot} must split into {s} "
                    f"shard blocks of whole N={n} groups")
            rest = tuple(lines.shape[3:])
            width = _prod(rest)
            groups = k_tot // n
            spec = PortSpec(
                name=name, direction="read", words=groups * width,
                offset=self._extent(self._reads, jnp.dtype(lines.dtype)),
                gathered=True,
                pool_words=lines.shape[0] * lines.shape[1] * width // n)
            self._reads.append(_Queued(spec, lines, rest, width, groups,
                                       shard=shard))
            return spec
        if lines.ndim < 2 or lines.shape[1] != n or lines.shape[0] % n:
            raise ValueError(
                f"stream {name!r}: want [k*N, N, ...] lines for N={n}, "
                f"got {lines.shape}")
        rest = tuple(lines.shape[2:])
        width = _prod(rest)
        if gather is not None:
            if gather.ndim != 1 or gather.shape[0] % n:
                raise ValueError(
                    f"stream {name!r}: gather indices must be [k*N] for "
                    f"N={n}, got {gather.shape}")
            groups = gather.shape[0] // n
        else:
            groups = lines.shape[0] // n
        words = groups * width
        spec = PortSpec(
            name=name, direction="read", words=words,
            offset=self._extent(self._reads, jnp.dtype(lines.dtype)),
            gathered=gather is not None,
            pool_words=(lines.shape[0] // n) * width if gather is not None
            else 0)
        self._reads.append(_Queued(spec, lines, rest, width, groups,
                                   gather=gather))
        return spec

    def enqueue_write(self, name: str, banked: jax.Array,
                      scatter: Optional[jax.Array] = None,
                      into: Optional[jax.Array] = None,
                      shard: Optional[Tuple] = None) -> PortSpec:
        """Queue a banked buffer ``[G, N, N, *rest]`` for the write network.

        ``scatter``/``into`` make the stream sparse-extent: the write
        network reassembles the banked frames' lines and each lands at its
        indexed row of the pool stream ``into [L, N, *rest]`` (sentinel
        indices ``>= L`` drop — padding rows are free; rows the indices
        never touch keep their frames without moving).  The committed
        result is the updated pool stream.

        ``shard = (fetch, place, k_tot)`` is the pool-sharded form of
        ``scatter``: ``into`` is the rep-major pool stream ``[R, F, N,
        *rest]`` sharded over the ``pool`` mesh axis, and each banked frame
        reaches its owning shard through one collective before the local
        fused scatter lands it."""
        n = self.fabric.n_ports
        if banked.ndim < 3 or banked.shape[1] != n or banked.shape[2] != n:
            raise ValueError(
                f"stream {name!r}: want [G, N, N, ...] banked for N={n}, "
                f"got {banked.shape}")
        self._check_name(name)
        if shard is not None:
            if scatter is not None:
                raise ValueError(f"stream {name!r}: shard= and scatter= are "
                                 f"mutually exclusive lowerings")
            if into is None:
                raise ValueError(f"stream {name!r}: sharded write needs the "
                                 f"pool stream to land in (into=)")
            if into.ndim != banked.ndim or into.shape[2] != n \
                    or into.shape[3:] != banked.shape[3:]:
                raise ValueError(
                    f"stream {name!r}: sharded scatter target {into.shape} "
                    f"does not match banked frames {banked.shape} "
                    f"(want rep-major [R, F, N, ...])")
            fetch, _, k_tot = shard
            if k_tot != banked.shape[0] * n:
                raise ValueError(
                    f"stream {name!r}: plan k_tot={k_tot} != banked line "
                    f"count {banked.shape[0] * n}")
            rest = tuple(banked.shape[3:])
            width = _prod(rest)
            spec = PortSpec(
                name=name, direction="write", words=banked.shape[0] * width,
                offset=self._extent(self._writes, jnp.dtype(banked.dtype)),
                gathered=True,
                pool_words=into.shape[0] * into.shape[1] * width // n)
            self._writes.append(_Queued(spec, banked, rest, width,
                                        banked.shape[0], into=into,
                                        shard=shard))
            return spec
        if (scatter is None) != (into is None):
            raise ValueError(
                f"stream {name!r}: sparse writes need both scatter indices "
                f"and the pool stream to land in (into=)")
        rest = tuple(banked.shape[3:])
        width = _prod(rest)
        if scatter is not None:
            if scatter.ndim != 1 or scatter.shape[0] != banked.shape[0] * n:
                raise ValueError(
                    f"stream {name!r}: scatter indices {scatter.shape} must "
                    f"match the banked line count {banked.shape[0] * n}")
            if into.shape[1:] != banked.shape[2:] or into.ndim != banked.ndim - 1:
                raise ValueError(
                    f"stream {name!r}: scatter target {into.shape} does not "
                    f"match banked lines {banked.shape}")
        words = banked.shape[0] * width
        spec = PortSpec(
            name=name, direction="write", words=words,
            offset=self._extent(self._writes, jnp.dtype(banked.dtype)),
            gathered=scatter is not None,
            pool_words=(into.shape[0] // n) * width if scatter is not None
            else 0)
        self._writes.append(_Queued(spec, banked, rest, width,
                                    banked.shape[0], scatter=scatter,
                                    into=into))
        return spec

    # -- the issue/commit pipeline ---------------------------------------------
    def issue(self) -> None:
        """Dispatch the queued traffic through the networks (one read and one
        write invocation per dtype present) and clear the queues, so the next
        burst's streams can be enqueued while this one is in flight.  The
        pipeline is one deep: a second :meth:`issue` before :meth:`commit`
        is an ordering error."""
        if self._inflight is not None:
            raise RuntimeError(
                "issue() with a burst already in flight; commit() the "
                "previous burst first (the pipeline is one deep)")
        out: Dict[str, jax.Array] = {}
        out.update(self._run_direction(self._reads, read=True))
        out.update(self._run_direction(self._writes, read=False))
        self._reads, self._writes = [], []
        self._inflight = out
        self.stats.flushes += 1

    def commit(self) -> Dict[str, jax.Array]:
        """Adopt the in-flight burst's results, keyed by stream name."""
        if self._inflight is None:
            raise RuntimeError("commit() without a matching issue()")
        out, self._inflight = self._inflight, None
        return out

    def flush(self) -> Dict[str, jax.Array]:
        """Synchronous form: ``issue()`` immediately followed by ``commit()``."""
        self.issue()
        return self.commit()

    # -- burst construction ----------------------------------------------------
    def _run_direction(self, queue: List[_Queued],
                       read: bool) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        n = self.fabric.n_ports
        by_dtype: Dict[object, List[_Queued]] = {}
        for q in queue:
            by_dtype.setdefault(jnp.dtype(q.payload.dtype), []).append(q)
        for dtype, streams in by_dtype.items():
            self.stats.streams_served += len(streams)
            sparse = [q for q in streams if q.sparse]
            for q in sparse:
                self.stats.words_live += q.groups * n * n * q.width
            sharded = [q for q in streams if q.shard is not None]
            if sharded:
                # pool-sharded lowering: each stream is its own two-hop
                # collective burst (per-shard fused gathers + one exchange);
                # dense streams of the dtype still share one packed burst
                for q in sharded:
                    out[q.spec.name] = self._run_sparse_sharded(q, read)
                streams = [q for q in streams if q.shard is None]
                sparse = [q for q in streams if q.sparse]
                if not streams:
                    continue
            if sparse and self.fabric.burst_kernelized_for(dtype):
                # fused lowering: each sparse stream is one gather/scatter
                # burst kernel launch (indices ride as a prefetched operand
                # — indirection + exchange in one kernel); dense streams of
                # the dtype still share one packed burst
                for q in sparse:
                    out[q.spec.name] = self._run_sparse_kernel(q, read)
                streams = [q for q in streams if not q.sparse]
                if not streams:
                    continue
            elif sparse:
                # unrolled lowering: gathers become takes feeding the shared
                # burst (the network still runs once per dtype, on live
                # frames only); scatters land after the network returns
                self.stats.gather_fused_bursts += 1
                streams = [self._materialize_gather(q) for q in streams]
            self.stats.network_calls += 1
            if self.pack == "packed":
                res = self._run_packed(streams, read)
            else:
                res = self._run_padded(streams, read)
            for q in streams:
                if q.scatter is not None:
                    res[q.spec.name] = q.into.at[q.scatter].set(
                        res[q.spec.name], mode="drop")
            out.update(res)
        return out

    def _count_moved(self, elems: int, read: bool) -> None:
        self.stats.words_moved += elems
        if not read:
            self.stats.words_written += elems

    def _materialize_gather(self, q: _Queued) -> _Queued:
        """Unrolled-path form of a sparse read: the frame gather lowers as a
        take (sentinels fill zero frames) whose result joins the shared
        burst like any dense stream.  Non-gather streams pass through."""
        if q.gather is None:
            return q
        taken = jnp.take(q.payload, q.gather, axis=0, mode="fill",
                         fill_value=0)
        return dataclasses.replace(q, payload=taken, gather=None)

    def _sparse_fold(self, q: _Queued) -> int:
        """Fold factor for one sparse-extent stream on the kernel path:
        within-line only (the index operand addresses whole frames, so the
        fold must divide the frame's word count)."""
        return self._fold_factor(q.payload.dtype, lambda f: q.width % f == 0)

    def _run_sparse_kernel(self, q: _Queued, read: bool) -> jax.Array:
        """One sparse-extent stream through the fused gather/scatter burst
        kernel: the pool stream (and, for writes, the scatter target) is
        viewed as machine words, the indices ride the launch prefetched,
        and only the live frames move."""
        n = self.fabric.n_ports
        fold = self._sparse_fold(q)
        elems = q.groups * n * n * q.width
        self.stats.network_calls += 1
        self.stats.kernel_bursts += 1
        self.stats.gather_fused_bursts += 1
        self._count_moved(elems, read)
        self.stats.words_folded += elems - elems // fold
        wide = (machine_word_dtype(
            jnp.dtype(q.payload.dtype).itemsize * fold) if fold > 1 else None)

        def view(x, lead_ndim):
            flat = x.reshape(x.shape[:lead_ndim] + (q.width,))
            if fold == 1:
                return _int_view(flat)
            return jax.lax.bitcast_convert_type(
                flat.reshape(flat.shape[:-1] + (q.width // fold, fold)), wide)

        if read:
            lines = view(q.payload, 2)                     # [L, N, w/f]
            banked = self.fabric.read_burst(lines, indices=q.gather)
            out = (_un_view(banked, q.payload.dtype) if fold == 1
                   else _unfold_view(banked, q.payload.dtype))
            return out.reshape((q.groups, n, n) + q.rest_shape)
        banked = view(q.payload, 3)                        # [G, N, N, w/f]
        into = view(q.into, 2)                             # [L, N, w/f]
        moved = self.fabric.write_burst(banked, indices=q.scatter, into=into)
        out = (_un_view(moved, q.payload.dtype) if fold == 1
               else _unfold_view(moved, q.payload.dtype))
        return out.reshape(q.into.shape)

    def _run_sparse_sharded(self, q: _Queued, read: bool) -> jax.Array:
        """One pool-sharded sparse stream through the two-hop collective
        lowering (:meth:`repro.fabric.Fabric.read_burst_sharded` /
        :meth:`~repro.fabric.Fabric.write_burst_sharded`): every shard runs
        the fused gather/scatter kernel on the frames it owns and one
        collective bridges them.  Machine-word folding applies exactly as on
        the single-device kernel path (within-line, the indices address
        whole frames), so the collective also moves ``1/fold`` the lanes."""
        n = self.fabric.n_ports
        fetch, place, k_tot = q.shard
        s, _, cap = fetch.shape
        fold = self._sparse_fold(q)
        elems = q.groups * n * n * q.width
        self.stats.network_calls += 1
        self.stats.collective_calls += 1
        self.stats.gather_fused_bursts += 1
        if self.fabric.burst_kernelized_for(q.payload.dtype):
            self.stats.kernel_bursts += 1
        self._count_moved(elems, read)
        self.stats.words_folded += elems - elems // fold
        # the exchange moves whole padded buckets; the diagonal stays local
        self.stats.words_cross_shard += s * (s - 1) * cap * n * q.width
        wide = (machine_word_dtype(
            jnp.dtype(q.payload.dtype).itemsize * fold) if fold > 1 else None)

        def view(x):
            flat = x.reshape(x.shape[:3] + (q.width,))
            if fold == 1:
                return _int_view(flat)
            return jax.lax.bitcast_convert_type(
                flat.reshape(flat.shape[:-1] + (q.width // fold, fold)), wide)

        if read:
            stream = view(q.payload)                       # [R, F, N, w/f]
            banked = self.fabric.read_burst_sharded(stream, fetch, place,
                                                    k_tot)
            out = (_un_view(banked, q.payload.dtype) if fold == 1
                   else _unfold_view(banked, q.payload.dtype))
            return out.reshape((q.groups, n, n) + q.rest_shape)
        banked = view(q.payload)                           # [G, N, N, w/f]
        into = view(q.into)                                # [R, F, N, w/f]
        moved = self.fabric.write_burst_sharded(banked, fetch, place, into)
        out = (_un_view(moved, q.payload.dtype) if fold == 1
               else _unfold_view(moved, q.payload.dtype))
        return out.reshape(q.into.shape)

    def _fold_factor(self, dtype, supports) -> int:
        """The one fold-policy choke point: the largest ``f ≤ word_fold``
        for which an ``f``-words-wide machine word exists (u64 needs x64)
        and the caller's geometry predicate ``supports(f)`` holds; 1 = no
        folding (bool/complex payloads never fold — bitcast rejects them).
        The packed, pad and sparse-kernel paths differ only in the
        predicate."""
        cap = 4 if self.word_fold == "auto" else int(self.word_fold)
        dt = jnp.dtype(dtype)
        if (cap == 1 or jnp.issubdtype(dt, jnp.bool_)
                or jnp.issubdtype(dt, jnp.complexfloating)):
            return 1
        for f in (4, 2):
            if (f <= cap and machine_word_dtype(dt.itemsize * f) is not None
                    and supports(f)):
                return f
        return 1

    def _group_fold(self, streams: List[_Queued]) -> int:
        """Fold factor for one packed dtype group: every member stream's
        geometry must divide — ``f`` divides the per-group word count (fold
        within the line group) or the group count (fold across groups)."""
        return self._fold_factor(
            streams[0].payload.dtype,
            lambda f: all(q.width % f == 0 or q.groups % f == 0
                          for q in streams))

    def _run_packed(self, streams: List[_Queued],
                    read: bool) -> Dict[str, jax.Array]:
        """Word-axis packing: fold each stream's group axis into the word
        axis (``[k*N, N, W] ≡ [N, N, k*W]`` — words of a line move
        independently), concatenate along words, run the network once on the
        ``[N, N, W_total]`` tile, and slice each stream's extent back.

        Payloads travel as machine words: the networks are pure word
        movement (block swaps/selects/gathers, no arithmetic), so each
        stream is bitcast to the same-width unsigned integer for the
        transfer and back on arrival — bit-exact by construction, and it
        keeps the burst off XLA:CPU's slow-path bf16 concatenate/select
        kernels.  Under ``word_fold`` the bitcast widens instead: adjacent
        narrow words fold into one u32/u64 machine word — the same bits in
        ``1/fold`` the lanes through every exchange stage — applied per
        stream as part of the packing view (within the line group, or
        across groups when the width is odd), with an exact unfolding
        bitcast on arrival.  The burst runs through the fabric's
        first-class burst path: one fused kernel launch per direction per
        dtype when kernels are enabled."""
        n = self.fabric.n_ports
        fold = self._group_fold(streams)
        tiles = []
        for q in streams:
            tiles.append(_pack_tile(q, n, fold))
            elems = q.groups * n * n * q.width
            self._count_moved(elems, read)
            self.stats.words_folded += elems - elems // fold
        burst = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=-1)
        moved = (self.fabric.read_burst(burst) if read
                 else self.fabric.write_burst(burst))
        if self.fabric.burst_kernelized_for(burst.dtype):
            self.stats.kernel_bursts += 1
        out: Dict[str, jax.Array] = {}
        # extents recomputed over the streams actually packed: when the
        # kernelized sparse streams peel off into their own fused launches,
        # the dense remainder's enqueue-time offsets no longer describe this
        # burst (for an unpeeled group they coincide with the spec extents)
        off = 0
        for q in streams:
            piece = moved[:, :, off // fold: (off + q.spec.words) // fold]
            off += q.spec.words
            out[q.spec.name] = _unpack_tile(piece, q, n, read, fold)
        return out

    def _padded_fold(self, streams: List[_Queued], w_max: int) -> int:
        """Fold factor for one pad-layout dtype group: every stream is
        padded to ``w_max`` words, so the factor just has to divide
        ``w_max``.  At 1 the pad path keeps its raw payload dtype, so the
        PR 1 baseline measurement is unchanged."""
        return self._fold_factor(streams[0].payload.dtype,
                                 lambda f: w_max % f == 0)

    def _run_padded(self, streams: List[_Queued],
                    read: bool) -> Dict[str, jax.Array]:
        """Pad-to-widest fallback (``pack="pad"``): streams concatenate along
        the line axis after zero-padding narrower words to the widest — the
        network moves the padding, which is what packed mode eliminates.
        Under ``word_fold`` the padded word axis folds into wider machine
        words before the network runs, same as the packed layout, so the
        pack A/B isolates the packing effect from the lane width."""
        n = self.fabric.n_ports
        out: Dict[str, jax.Array] = {}
        w_max = max(q.width for q in streams)
        fold = self._padded_fold(streams, w_max)
        wide = (machine_word_dtype(
            jnp.dtype(streams[0].payload.dtype).itemsize * fold)
            if fold > 1 else None)
        flat = []
        for q in streams:
            lead = q.payload.shape[:2] if read else q.payload.shape[:3]
            x = q.payload.reshape(lead + (q.width,))
            lines = q.payload.shape[0] * (1 if read else n)
            self._count_moved(lines * n * q.width, read)
            self.stats.words_padded += lines * n * (w_max - q.width)
            if q.width < w_max:
                pad = [(0, 0)] * (x.ndim - 1) + [(0, w_max - q.width)]
                x = jnp.pad(x, pad)
            if fold > 1:
                elems = lines * n * w_max          # lane view incl. padding
                self.stats.words_folded += elems - elems // fold
                x = jax.lax.bitcast_convert_type(
                    x.reshape(x.shape[:-1] + (w_max // fold, fold)), wide)
            flat.append(x)
        burst = jnp.concatenate(flat, axis=0)
        moved = self.fabric.read(burst) if read else self.fabric.write(burst)
        # split back: stream i covers groups [off, off + L_i/N) (read) or
        # lines [off, off + G_i*N) (write)
        off = 0
        for q in streams:
            count = (q.payload.shape[0] // n if read
                     else q.payload.shape[0] * n)
            piece = moved[off:off + count]
            off += count
            if fold > 1:
                piece = _unfold_view(piece, q.payload.dtype)
            piece = piece[..., :q.width]
            out[q.spec.name] = piece.reshape(piece.shape[:-1] + q.rest_shape)
        return out


# Sparse-extent sentinel: any index >= the backing stream's line count reads
# as a zero frame (take mode="fill") and drops on scatter (mode="drop").
# Producers (engine live plans, admission, tests) and consumers (kernels,
# fabric, scheduler) share this one value so it stays >= every pool's lines.
FRAME_SENTINEL = 2 ** 30


_WORD_VIEW = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def machine_word_dtype(itemsize: int):
    """The unsigned machine word of ``itemsize`` bytes, or None if the
    platform doesn't move one (u64 exists only under x64 — without it jax
    canonicalizes uint64 away, so float64 payloads and 8-byte folds skip
    the integer-view fast path)."""
    if itemsize == 8 and not jax.config.read("jax_enable_x64"):
        return None
    return _WORD_VIEW.get(itemsize)


def _int_view(x: jax.Array) -> jax.Array:
    """Same-width unsigned-integer view of a payload (identity for ints,
    for widths without a same-size unsigned view, and for dtypes bitcast
    rejects — bool and complex)."""
    if (jnp.issubdtype(x.dtype, jnp.integer)
            or jnp.issubdtype(x.dtype, jnp.bool_)
            or jnp.issubdtype(x.dtype, jnp.complexfloating)):
        return x
    wide = machine_word_dtype(jnp.dtype(x.dtype).itemsize)
    return x if wide is None else jax.lax.bitcast_convert_type(x, wide)


def _un_view(x: jax.Array, dtype) -> jax.Array:
    """Undo :func:`_int_view` on arrival."""
    return x if x.dtype == jnp.dtype(dtype) else (
        jax.lax.bitcast_convert_type(x, dtype))


def _pack_tile(q: _Queued, n: int, fold: int) -> jax.Array:
    """One stream → its ``[N, N, words/fold]`` extent of the packed burst.

    ``fold == 1``: the line groups fold into the word axis behind a
    same-width integer view.  ``fold > 1``: the bitcast widens instead —
    adjacent words of a line group (when ``fold`` divides the stream's
    width), or corresponding words of adjacent groups (word-major tile
    order, when ``fold`` divides the group count)."""
    g, w = q.groups, q.width
    flat = q.payload.reshape(g, n, n, w)
    if fold == 1:
        return _int_view(flat).transpose(1, 2, 0, 3).reshape(n, n, -1)
    wide = machine_word_dtype(jnp.dtype(q.payload.dtype).itemsize * fold)
    if w % fold == 0:
        folded = jax.lax.bitcast_convert_type(
            flat.reshape(g, n, n, w // fold, fold), wide)
        return folded.transpose(1, 2, 0, 3).reshape(n, n, -1)
    grouped = flat.transpose(1, 2, 3, 0).reshape(n, n, w, g // fold, fold)
    return jax.lax.bitcast_convert_type(grouped, wide).reshape(n, n, -1)


def _unpack_tile(piece: jax.Array, q: _Queued, n: int, read: bool,
                 fold: int) -> jax.Array:
    """Inverse of :func:`_pack_tile`: the stream's slice of the moved burst
    (``[N, N, words/fold]``) back to the consumer's layout — banked
    ``[G, N, N, *rest]`` for reads, lines ``[G*N, N, *rest]`` for writes."""
    g, w = q.groups, q.width
    lead = (g, n, n) if read else (g * n, n)
    if fold == 1:
        out = piece.reshape(n, n, g, w).transpose(2, 0, 1, 3)
        return _un_view(out, q.payload.dtype).reshape(lead + q.rest_shape)
    if w % fold == 0:
        out = piece.reshape(n, n, g, w // fold).transpose(2, 0, 1, 3)
        return _unfold_view(out, q.payload.dtype).reshape(lead + q.rest_shape)
    out = _unfold_view(piece.reshape(n, n, w, g // fold), q.payload.dtype)
    return out.transpose(3, 0, 1, 2).reshape(lead + q.rest_shape)


def _unfold_view(x: jax.Array, dtype) -> jax.Array:
    """Bitcast a folded machine-word array back to ``dtype``, flattening the
    ``fold``-sized axis the bitcast appends into the last dimension."""
    y = jax.lax.bitcast_convert_type(x, dtype)
    return y.reshape(y.shape[:-2] + (y.shape[-2] * y.shape[-1],))


def _prod(shape: Tuple[int, ...]) -> int:
    p = 1
    for s in shape:
        p *= s
    return p

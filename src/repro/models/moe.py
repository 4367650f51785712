"""Mixture-of-Experts FFN: dropless grouped experts, or capacity dispatch.

Ports-as-experts is the Medusa mapping (DESIGN.md §3.2): the router evenly
partitions token bandwidth across experts with a *static* capacity (paper
observation 1 — even bandwidth partitioning), and the expert all-to-all can
run either on XLA's native all-to-all (the "crossbar") or on the Medusa ring
schedule (N-1 ``ppermute`` rotations — ``repro/parallel/collectives.py``).

Which path runs follows from the configuration.  When ``capacity_factor >=
n_experts / top_k`` the capacity holds every assignment of the call and can
never drop, so :func:`moe_apply` routes **dropless**: the ``T*k``
assignments are stably sorted by expert, each token's row lands in its
expert's contiguous run, and the SwiGLU runs as three grouped matmuls
(``jax.lax.ragged_dot``) over exactly those ``T*k`` rows — no ``[E, C]``
slots, no drop accounting, nothing sent to the host.  Below that factor the
**capacity** path runs: tokens are ranked within their expert by the same
stable sort; tokens past capacity are dropped (their residual passes
through — standard capacity-factor semantics) and counted.

Payload movement rides the fabric's burst contract.  Dropless, dispatch is
a gather-indexed read of each sorted row's token and combine a
gather-indexed read of each assignment's sorted row.  With capacity,
token→expert is the same logical→physical indirection as a page table, so
dispatch is a scatter-indexed write into the ``[E, C]`` expert slots
(capacity drops become sentinel rows, exactly like page-scatter sentinels)
and combine is a gather-indexed read per assignment.  Every one is a
:class:`BurstScheduler` sparse-extent stream sharing the packed/fold/kernel
lowering and counted in :class:`SchedulerStats`.  ``payload="route"`` keeps
the bare ``fabric.route`` gathers as the bit-parity reference of either
layout (``tests/test_moe_fabric.py``, ``tests/test_moe_dropless.py``).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.fabric.fabric import Fabric
from repro.fabric.scheduler import (BurstScheduler, FRAME_SENTINEL,
                                    SchedulerStats)
from repro.models.common import dense_init
from repro.parallel.sharding import shard

#: module-level stats sink: the serving engine traces ``moe_apply`` deep
#: inside its jitted step, so it routes accounting here (see
#: :func:`dispatch_stats`) instead of threading a kwarg through every layer.
_DISPATCH_STATS: Optional[SchedulerStats] = None


@contextlib.contextmanager
def dispatch_stats(stats: Optional[SchedulerStats]):
    """Route the traffic accounting of every ``moe_apply`` traced inside the
    block to ``stats``.  Must be active at *trace* time: word counters
    accumulate once per trace (the scheduler convention), while the
    data-dependent ``tokens_dropped`` counter is captured into a debug
    callback that fires per execution."""
    global _DISPATCH_STATS
    prev, _DISPATCH_STATS = _DISPATCH_STATS, stats
    try:
        yield
    finally:
        _DISPATCH_STATS = prev


def moe_params(key, cfg, dtype) -> dict:
    m = cfg.moe
    ks = jax.random.split(key, 4)
    e, d, f = m.n_experts_padded, cfg.d_model, m.expert_d_ff
    return {
        "router": dense_init(ks[0], d, m.n_experts, jnp.float32),
        "w_gate": jax.vmap(lambda k: dense_init(k, d, f, dtype))(
            jax.random.split(ks[1], e)),
        "w_up": jax.vmap(lambda k: dense_init(k, d, f, dtype))(
            jax.random.split(ks[2], e)),
        "w_out": jax.vmap(lambda k: dense_init(k, f, d, dtype))(
            jax.random.split(ks[3], e)),
    }


def _count_dropped(stats: Optional[SchedulerStats], keep: jax.Array) -> None:
    """Accumulate the capacity-drop count into ``stats.tokens_dropped``.
    Concrete (eager) counts add directly; traced counts register a debug
    callback so the counter stays runtime-exact under jit/scan."""
    if stats is None:
        return
    drops = keep.size - jnp.sum(keep, dtype=jnp.int32)
    if isinstance(drops, jax.core.Tracer):
        def _add(d, _s=stats):
            _s.tokens_dropped += int(d)
        jax.debug.callback(_add, drops)
    else:
        stats.tokens_dropped += int(drops)


def _burst_dispatch(fabric: Fabric, xt: jax.Array, tok: jax.Array,
                    keep: jax.Array, slot: jax.Array, ec: int,
                    stats: Optional[SchedulerStats]) -> jax.Array:
    """Dispatch as one sparse-extent write burst: the per-assignment token
    buffer ``xt[tok] [T*k, d]`` is viewed as frames ``[T*k, N, d/N]`` and
    scatter-indexed into the zeroed ``[E*C, d]`` slot pool (dropped
    assignments carry sentinel rows, which the write network drops like any
    page-scatter sentinel; slots no assignment reaches keep their zeros).
    Bit-identical to the masked ``fabric.route`` gather by construction —
    both move copies, never arithmetic."""
    n = fabric.n_ports
    d = xt.shape[1]
    xa = xt[tok]                                            # [T*k, d]
    sidx = jnp.where(keep, slot, FRAME_SENTINEL).astype(jnp.int32)
    pad = -xa.shape[0] % n
    if pad:
        xa = jnp.concatenate([xa, jnp.zeros((pad, d), xa.dtype)])
        sidx = jnp.concatenate(
            [sidx, jnp.full((pad,), FRAME_SENTINEL, jnp.int32)])
    lines = xa.reshape(-1, n, d // n)
    banked = lines.reshape(-1, n, n, d // n).swapaxes(1, 2)
    ec_pad = ec + (-ec % n)
    into = jnp.zeros((ec_pad, n, d // n), xt.dtype)
    sched = BurstScheduler(fabric, stats=stats)
    sched.enqueue_write("moe/dispatch", banked, scatter=sidx, into=into)
    pool = sched.flush()["moe/dispatch"]                    # [EC_pad, N, d/N]
    return pool.reshape(ec_pad, d)[:ec]


def _burst_gather(fabric: Fabric, rows: jax.Array, idx: jax.Array,
                  name: str, stats: Optional[SchedulerStats]) -> jax.Array:
    """``rows[idx]`` as one sparse-extent read burst: ``rows [R, d]`` is the
    backing line stream (each row one frame of ``N`` ports) and ``idx [K]``
    names the frame each output row gathers; sentinel indices gather zero
    frames, matching a masked route."""
    n = fabric.n_ports
    r, d = rows.shape
    k_tot = idx.shape[0]
    if r % n:
        rows = jnp.concatenate([rows, jnp.zeros((-r % n, d), rows.dtype)])
    lines = rows.reshape(-1, n, d // n)
    gidx = idx.astype(jnp.int32)
    if k_tot % n:
        gidx = jnp.concatenate(
            [gidx, jnp.full((-k_tot % n,), FRAME_SENTINEL, jnp.int32)])
    sched = BurstScheduler(fabric, stats=stats)
    sched.enqueue_read(name, lines, gather=gidx)
    banked = sched.flush()[name]                            # [K/N, N, N, d/N]
    return banked.swapaxes(1, 2).reshape(-1, d)[:k_tot]


def routes_dropless(m) -> bool:
    """Whether the capacity of ``m`` (a :class:`MoEConfig`) holds every
    assignment of any call: ``capacity_factor >= n_experts / top_k``."""
    return m.capacity_factor * m.top_k >= m.n_experts


def _dropless_experts(p, xt: jax.Array, top_e: jax.Array, m,
                      fabric: Fabric, payload: str,
                      stats: Optional[SchedulerStats]) -> jax.Array:
    """Every assignment's expert output ``[T*k, d]``, in assignment order.

    A stable sort of the ``T*k`` assignments by expert gives each expert a
    contiguous run of rows (``group_sizes`` rows; dead padded experts get
    empty runs).  Dispatch gathers each sorted row's token, the SwiGLU runs
    as grouped matmuls over the runs (float32 accumulation), and combine
    gathers each assignment's row back out of the sorted order."""
    kt = top_e.size
    a = top_e.reshape(-1)
    order = jnp.argsort(a, stable=True).astype(jnp.int32)   # sorted -> asg
    src_tok = order // m.top_k
    back = jnp.zeros((kt,), jnp.int32).at[order].set(
        jnp.arange(kt, dtype=jnp.int32))                    # asg -> sorted
    sizes = jnp.bincount(a, length=m.n_experts_padded).astype(jnp.int32)
    if payload == "burst":
        xs = _burst_gather(fabric, xt, src_tok, "moe/dispatch", stats)
    else:
        xs = fabric.route(xt, src_tok)
    # the sorted rows shard as the capacity path's slots do (expert_cap)
    xs = shard(xs, "expert_cap", "d_model")

    def grouped(lhs, w):
        return jax.lax.ragged_dot(lhs, w, sizes,
                                  preferred_element_type=jnp.float32)

    h = jax.nn.silu(grouped(xs, p["w_gate"])) * grouped(xs, p["w_up"])
    h = shard(h, "expert_cap", None)
    y = grouped(h.astype(xt.dtype), p["w_out"]).astype(xt.dtype)
    y = shard(y, "expert_cap", "d_model")
    if payload == "burst":
        return _burst_gather(fabric, y, back, "moe/combine", stats)
    return fabric.route(y, back)


def moe_apply(p, x: jax.Array, cfg, stats: Optional[SchedulerStats] = None,
              payload: Optional[str] = None) -> jax.Array:
    """``x [B, S, d]`` → MoE FFN output, top-k routing: dropless when
    :func:`routes_dropless`, else with capacity.

    With ``moe.pad_to`` set, the expert dim is padded with dead experts the
    router can never select (logits only cover the real experts); capacity is
    computed over real experts so semantics are unchanged — only the EP
    sharding divisibility improves.

    ``payload`` selects how dispatch/combine move the activations:
    ``"burst"`` (the default whenever the fabric banks and ``d_model`` splits
    across its ports) lowers both as :class:`BurstScheduler` sparse-extent
    streams; ``"route"`` is the bare ``fabric.route`` gather reference.  The
    two are bit-identical — ``tests/test_moe_fabric.py`` and
    ``tests/test_moe_dropless.py`` hold the line across the pack×fold×kernel
    matrix.  ``stats`` (or an ambient :func:`dispatch_stats` context)
    receives the burst accounting plus, on the capacity path, the
    runtime-exact ``tokens_dropped`` counter.
    """
    m = cfg.moe
    fabric = Fabric.for_model(cfg)
    e_pad = m.n_experts_padded
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    if stats is None:
        stats = _DISPATCH_STATS
    if payload is None:
        payload = ("burst" if fabric.banks_kv and d % fabric.n_ports == 0
                   else "route")

    logits = (xt.astype(jnp.float32) @ p["router"])               # [T, E_real]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)                  # [T, k]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    w = top_p.reshape(-1)[:, None].astype(x.dtype)

    if routes_dropless(m):
        gathered = _dropless_experts(p, xt, top_e, m, fabric, payload, stats)
        out = (gathered * w).reshape(t, m.top_k, d).sum(axis=1)
        return out.reshape(b, s, d)

    a = top_e.reshape(-1)                                         # [T*k]
    tok = jnp.arange(t * m.top_k) // m.top_k
    # rank within expert via stable sort (even static partition -> capacity)
    order = jnp.argsort(a, stable=True)
    a_sorted = a[order]
    first = jnp.searchsorted(a_sorted, a_sorted, side="left")
    rank_sorted = jnp.arange(t * m.top_k) - first
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    cap = int(t * m.top_k * m.capacity_factor / m.n_experts) or 1
    keep = rank < cap
    slot = jnp.where(keep, a * cap + rank, e_pad * cap)           # drop→OOB
    _count_dropped(stats, keep)

    if payload == "burst":
        # dispatch rides the write network: one scatter-indexed sparse
        # burst lands each kept assignment's frame in its expert slot
        # (fused scatter kernel on the medusa fabric, take+network+scatter
        # unrolled elsewhere — the same lowering the page pool uses).
        buf = _burst_dispatch(fabric, xt, tok, keep, slot, e_pad * cap,
                              stats)
    else:
        # route reference: payload moves through gathers only — the
        # scatter touches 4-byte indices, never the d-wide activations.
        inv = jnp.full((e_pad * cap,), t * m.top_k, jnp.int32)
        inv = inv.at[slot].set(jnp.arange(t * m.top_k, dtype=jnp.int32),
                               mode="drop")                       # [E*C]
        slot_valid = inv < t * m.top_k
        src_tok = jnp.clip(inv // m.top_k, 0, t - 1)
        buf = jnp.where(slot_valid[:, None], fabric.route(xt, src_tok), 0)
    buf = buf.reshape(e_pad, cap, d)
    buf = shard(buf, "experts", "expert_cap", "d_model")

    # expert FFN (swiglu), experts sharded over the model axis (EP)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = shard(h, "experts", "expert_cap", None)
    y = jnp.einsum("ecf,efd->ecd", h, p["w_out"])
    y = shard(y, "experts", "expert_cap", "d_model").reshape(e_pad * cap, d)

    # combine: gather per assignment, weight, and reduce over the (static,
    # consecutive) top-k axis by reshape+sum — no scatter-add.
    if payload == "burst":
        gathered = _burst_gather(fabric, y,
                                 jnp.where(keep, slot, FRAME_SENTINEL),
                                 "moe/combine", stats)
    else:
        gathered = jnp.where(keep[:, None],
                             fabric.route(y, jnp.clip(slot, 0,
                                                      e_pad * cap - 1)),
                             0)
    out = (gathered * w).reshape(t, m.top_k, d).sum(axis=1)
    return out.reshape(b, s, d)


def aux_load_balance_loss(p, x: jax.Array, cfg) -> jax.Array:
    """Switch-style load-balance auxiliary loss (fraction x probability).

    ``frac`` counts **every top-k assignment** — the router actually in use
    dispatches top-k, so the load fraction is the share of all ``T*k``
    assignments each expert receives (it sums to 1, and the loss floors at 1
    under a perfectly balanced router, exactly as in the top-1 Switch form).
    The old ``argmax`` form only counted first choices, so an expert fed
    exclusively by second choices looked idle to the loss while running at
    full capacity.
    """
    m = cfg.moe
    t = x.shape[0] * x.shape[1]
    logits = x.reshape(t, -1).astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_e = jax.lax.top_k(probs, m.top_k)[1]                      # [T, k]
    frac = jnp.mean(jax.nn.one_hot(top_e, m.n_experts), axis=(0, 1))
    imp = jnp.mean(probs, axis=0)
    return m.n_experts * jnp.sum(frac * imp)

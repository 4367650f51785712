"""Device-mesh lowering of the fabric: the sharded physical page pool.

The paper's core observation — many narrow accelerator ports funneling into
one wide DRAM bus — reappears one level up at multi-device scale: many
per-slot decode streams funneling into one shared KV pool.  This module
shards that pool over a ``pool`` mesh axis and lowers the sparse-extent
bursts (``Fabric.read_burst(..., indices=)`` / ``write_burst(..., indices=,
into=)``) inside ``shard_map`` as a **two-hop collective**:

1. *local hop* — each shard runs the fused page-table gather on the frames
   it owns (the PR-5 scalar-prefetched burst kernel, per shard, on its
   ``frames/S`` block of the pool's line stream);
2. *exchange hop* — ONE ``lax.all_to_all`` (or ``ring_all_to_all`` — N-1
   ``ppermute`` rotations, selectable via :attr:`FabricConfig.collective`)
   delivers every gathered frame to the shard that requested it.

The exchange network's butterfly stages and the collective's rotation steps
are the same algebra — both are static permutations of whole machine words —
so the lowering is bit-identical to the single-device sparse burst by
construction: the local gathers produce exactly ``take(pool, indices)``
restricted to each shard's rows, the collective is a pure permutation of
those lines, and the requesting shard's placement scatter restores the
request order before the banked reshape.

Ownership is **contiguous-block by physical page**: shard ``s`` owns pages
``[s * P/S, (s+1) * P/S)`` — exactly what ``PartitionSpec("pool")`` on the
leaf's page axis means to jax (:func:`pool_partition_spec`), so the sharded
arrays and the plan agree without any relayout.  Traffic *balance* comes
from the allocator instead: :class:`repro.fabric.PagePool` stripes page
allocation round-robin across the shard blocks (``n_shards``), so a decode
step's live frames spread evenly over shards.

The host side of the split lives in :func:`shard_plan`: given a step's live
frame list it buckets every requested frame by (requesting shard, owning
shard), pads each bucket to a shared ``cap`` with sentinels, and emits the
``fetch``/``place`` index operands both burst directions reuse (reads
deliver pool→ports, writes ports→pool, through the same buckets).  The
off-diagonal buckets are the words that physically cross shards —
``SchedulerStats.words_cross_shard``; with round-robin striping they are
``(S-1)/S`` of the live traffic, always less than ``words_moved``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.fabric.scheduler import FRAME_SENTINEL as _SENTINEL

POOL_AXIS = "pool"


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Host-side plan of one step's cross-shard traffic (one per distinct
    leaf rep count; both burst directions reuse it).

    ``fetch [S(owner), S(requestor), cap]`` — for each owning shard, the
    *local* line-stream rows it sends each requestor (sentinel = padding:
    reads gather zero frames, writes drop).  ``place [S(requestor),
    S(owner), cap]`` — for each requesting shard, the *local* output row of
    each received line (sentinel drops).  ``cap`` is the padded bucket
    size, a multiple of N so every shard's local gather keeps the burst
    index contract.  ``cross_frames``/``local_frames`` count the live
    (non-padding) requests that cross shards vs stay local — the host-side
    census behind the bench's locality split."""

    fetch: np.ndarray
    place: np.ndarray
    k_tot: int
    cap: int
    cross_frames: int
    local_frames: int

    @property
    def n_shards(self) -> int:
        return self.fetch.shape[0]

    def operands(self):
        """The plan's device operands ``(fetch, place)`` (int32)."""
        return jnp.asarray(self.fetch), jnp.asarray(self.place)


def shard_plan(live_idx, frames: int, n_shards: int, n_ports: int,
               reps: int = 1, cap_bucket: int = 0) -> ShardPlan:
    """Split a sparse burst's frame-index list by owning shard (host-side).

    ``live_idx [K]`` are per-pool physical frame indices (entries
    ``>= frames`` are sentinels requesting nothing), ``frames`` the per-rep
    pool frame count, ``reps`` the leaf's leading layer-stack factor (the
    request list is rep-major, matching
    :func:`repro.models.common.pool_rep_indices`).  Output row ``j`` of the
    ``k_tot = reps*K`` line stream is assigned to requesting shard
    ``j // (k_tot/S)`` — the contiguous block ``PartitionSpec("pool")``
    gives it.  ``cap_bucket`` rounds the bucket capacity up (beyond the
    mandatory multiple of N) to bound retrace churn, mirroring the engine's
    live-plan bucketing."""
    idx = np.asarray(live_idx, np.int64)
    s = int(n_shards)
    if s < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if frames % s:
        raise ValueError(f"pool frame count {frames} must divide into "
                         f"{s} equal shard blocks")
    k_tot = int(reps) * idx.shape[0]
    if k_tot % (s * n_ports):
        raise ValueError(
            f"sharded burst needs {reps}*{idx.shape[0]} request lines to "
            f"split into {s} shard blocks of whole N={n_ports} groups — "
            f"bucket the live plan to a multiple of S*N")
    f_loc = frames // s
    k_loc = k_tot // s
    tiled = np.tile(idx, int(reps))                      # rep-major [k_tot]
    out_rows = np.nonzero(tiled < frames)[0]             # sentinels skip
    f = tiled[out_rows]
    rep = out_rows // idx.shape[0]
    owner = f // f_loc
    row_loc = rep * f_loc + f % f_loc                    # local line row
    req = out_rows // k_loc
    place_loc = out_rows % k_loc                         # local output row
    # stable-sort by (req, owner) to slot each request into its bucket
    key = req * s + owner
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    _, start, counts = np.unique(key_s, return_index=True,
                                 return_counts=True)
    slot = np.arange(key_s.shape[0]) - np.repeat(start, counts)
    cap = max(int(counts.max()) if counts.size else 0, 1)
    cap = -(-cap // n_ports) * n_ports
    if cap_bucket:
        cap = -(-cap // cap_bucket) * cap_bucket
    fetch = np.full((s, s, cap), _SENTINEL, np.int32)
    place = np.full((s, s, cap), _SENTINEL, np.int32)
    ro, rq = owner[order], req[order]
    fetch[ro, rq, slot] = row_loc[order]
    place[rq, ro, slot] = place_loc[order]
    cross = int((owner != req).sum())
    return ShardPlan(fetch=fetch, place=place, k_tot=k_tot, cap=cap,
                     cross_frames=cross,
                     local_frames=int(out_rows.shape[0]) - cross)


def pool_partition_spec(leaf_ndim: int):
    """The ``PartitionSpec`` of a pool-backed KV leaf ``[lead...,
    n_pages, page_size, Hkv, D]``: the page axis shards over ``pool``,
    everything else replicates.  Derived from the leaf rank alone — the
    page axis is always fourth from the end."""
    from jax.sharding import PartitionSpec as P
    if leaf_ndim < 4:
        raise ValueError(f"pool leaf needs [*, pages, page, H, D], "
                         f"rank {leaf_ndim} is too small")
    spec = [None] * leaf_ndim
    spec[leaf_ndim - 4] = POOL_AXIS
    return P(*spec)


def make_pool_mesh(n_shards: int):
    """A 1-D ``("pool",)`` mesh over the first ``n_shards`` devices."""
    devices = jax.devices()
    if len(devices) < n_shards:
        raise RuntimeError(
            f"pool mesh needs {n_shards} devices, have {len(devices)} — "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_shards} before any jax import")
    return jax.sharding.Mesh(np.asarray(devices[:n_shards]), (POOL_AXIS,))


def _exchange(x: jax.Array, collective: str) -> jax.Array:
    """One inter-shard hop: block ``j`` of ``x [S, ...]`` to shard ``j``."""
    from repro.parallel.collectives import ring_all_to_all, xla_all_to_all
    if collective == "ring":
        return ring_all_to_all(x, POOL_AXIS)
    return xla_all_to_all(x, POOL_AXIS)


def sharded_read_burst(fabric, stream: jax.Array, fetch: jax.Array,
                       place: jax.Array, k_tot: int) -> jax.Array:
    """Sparse read burst over the sharded pool: ``stream [R, F, N, W]``
    (page-major frames, pool axis sharded) → banked ``[k_tot//N, N, N, W]``
    (request order, sharded over groups) — bit-identical to the
    single-device ``Fabric.read_burst(lines, indices=)`` on the flattened
    ``[R*F, N, W]`` stream with rep-tiled indices.

    Two hops inside ``shard_map``: each shard fuse-gathers the rows
    ``fetch`` names from its local block (the PR-5 kernel when enabled),
    un-banks them to exchange order, runs one collective, and the
    requesting shard places the received lines at their output rows."""
    from jax.sharding import PartitionSpec as P
    n = fabric.n_ports
    s, _, cap = fetch.shape
    k_loc = k_tot // s
    collective = fabric.config.collective

    def body(loc, f, pl):
        lines = loc.reshape((-1,) + loc.shape[-2:])      # [R*F/S, N, W]
        banked = fabric.read_burst(lines, indices=f.reshape(s * cap))
        send = banked.swapaxes(1, 2).reshape(s, cap, n, -1)
        recv = _exchange(send, collective)               # [S(owner), cap, N, W]
        out = jnp.zeros((k_loc,) + recv.shape[-2:], recv.dtype)
        out = out.at[pl.reshape(s * cap)].set(
            recv.reshape(s * cap, n, -1), mode="drop")
        return out.reshape(k_loc // n, n, n, -1).swapaxes(1, 2)

    return jax.shard_map(
        body, mesh=fabric.mesh,
        in_specs=(P(None, POOL_AXIS), P(POOL_AXIS), P(POOL_AXIS)),
        out_specs=P(POOL_AXIS), check_vma=False)(stream, fetch, place)


def sharded_write_burst(fabric, banked: jax.Array, fetch: jax.Array,
                        place: jax.Array, into: jax.Array) -> jax.Array:
    """Write direction of :func:`sharded_read_burst`: banked live frames
    ``[k_tot//N, N, N, W]`` land at their pool rows of ``into [R, F, N,
    W]`` — the same ``fetch``/``place`` buckets run in reverse (each
    requestor sends its updated lines to the owning shard, which runs the
    fused scatter kernel into its local block).  Returns the updated
    stream; rows the indices never touch keep their frames without moving.
    This is also the disaggregation primitive: a prefill writer targeting a
    remote shard's pool is exactly this lowering."""
    from jax.sharding import PartitionSpec as P
    n = fabric.n_ports
    s, _, cap = fetch.shape
    collective = fabric.config.collective

    def body(bank_loc, into_loc, f, pl):
        k_loc = bank_loc.shape[0] * n
        lines = bank_loc.swapaxes(1, 2).reshape(k_loc, n, -1)
        send = jnp.take(lines, pl.reshape(s * cap), axis=0, mode="fill",
                        fill_value=0).reshape(s, cap, n, -1)
        recv = _exchange(send, collective)               # [S(req), cap, N, W]
        bank_recv = recv.reshape(s * cap // n, n, n, -1).swapaxes(1, 2)
        pool_lines = into_loc.reshape((-1,) + into_loc.shape[-2:])
        out = fabric.write_burst(bank_recv, indices=f.reshape(s * cap),
                                 into=pool_lines)
        return out.reshape(into_loc.shape)

    return jax.shard_map(
        body, mesh=fabric.mesh,
        in_specs=(P(POOL_AXIS), P(None, POOL_AXIS), P(POOL_AXIS),
                  P(POOL_AXIS)),
        out_specs=P(None, POOL_AXIS), check_vma=False)(
            banked, into, fetch, place)

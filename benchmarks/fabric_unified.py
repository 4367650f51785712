"""Unified burst-scheduled fabric vs per-consumer interconnect calls.

The perf claims measured, on the same 4-stream mixed-width traffic:

* ``per_consumer`` — seed style, one read-network lowering per consumer;
* ``unified_pad`` — PR 1's burst layout (pad-to-widest line-axis concat; the
  network moves the padding);
* ``unified_pad_fold2`` / ``_fold4`` — the pad layout riding the same
  u32/u64 machine-word lanes as the packed cells (the fold divides the
  padded width), so pad-vs-packed at equal fold isolates the packing
  effect from the lane width;
* ``unified_packed`` — word-axis packing at the default fold
  (``word_fold="auto"``: on this all-bf16 traffic the burst folds into u32
  machine-word lanes), measured on the UNROLLED network so the
  medusa-vs-crossbar headline compares network against network — the
  ``..._kernel`` cells A/B the fused lowering on top (serving decode with
  kernels enabled, the production default, takes that path);
* ``unified_packed_fold1`` / ``_fold2`` / ``_fold4`` — the explicit
  machine-word lane folding axis (PR 3): adjacent narrow words fold into
  u32/u64 machine words behind the packing bitcast, halving/quartering the
  lane count every exchange-stage select touches (``_fold4`` needs x64 for
  the u64 lane and only appears then);
* ``unified_packed_kernel`` (medusa only) — the packed burst lowers through
  ONE fused ``pallas_call`` per direction (``Fabric.read_burst`` /
  ``write_burst`` with kernels enabled) instead of the unrolled per-stage
  HLO chain; measured at fold=1 (the PR 2 configuration, so the cell
  isolates the kernel effect on the op census) plus a ``_fold2_kernel``
  combination cell.

Paged-decode cell family (``decode_*``): one KV pool leaf's end-to-end
decode-step movement — read the pool through the network, reconstruct the
dense per-slot view through the page table, scatter the (round-tripped)
update back, write network home — at low (25%) and high (75%) pool
occupancy:

* ``decode_gather_after_occ{25,75}`` — the fallback contract: the burst
  banks EVERY pool frame, the gather is a consumer-side postprocess on the
  network's output (``words_moved`` = pool frames, occupancy-independent);
* ``decode_fused_occ{25,75}`` — the fused contract
  (``FabricConfig.fused_gather``): sparse-extent streams bank only the
  frames the table maps (``words_moved`` = ``words_live`` ∝ occupancy);
  the medusa ``_kernel`` variants lower the indirection + exchange as one
  Pallas launch with the indices prefetched (vLLM paged-attention style).

Both forms are asserted bit-identical — same dense view, same updated pool
— before timing, which is the acceptance bar for the fused-gather contract.

Pool-sharded cell family (``decode_sharded_{1,2,4,8}dev``): the same
read-burst → write-burst decode round trip with the pool's frame axis
sharded over a ``pool`` device mesh axis (``FabricConfig.pool_shards``) —
each shard fuse-gathers the frames it owns, one collective exchange hop
delivers them to the requesting shard.  Cells record wall-clock plus the
split of ``words_moved`` into ``words_cross_shard`` (off-diagonal exchange
blocks that physically leave their owner, bucket padding included) vs
``words_local`` (the diagonal): with round-robin page striping roughly
``(S-1)/S`` of the live traffic crosses, never all of it, and every shard
count is asserted bit-identical to the 1-device fused gather before timing.
Host platforms re-exec these cells in a subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (device count is
frozen at first jax import).

MoE dispatch cell family (``moe_dispatch_{route,burst}``): one MoE layer's
token→expert dispatch + combine (``repro.models.moe.moe_apply``) with the
data-dependent movement as bare ``fabric.route`` calls (the crossbar
primitive, invisible to the scheduler census — its word counters read zero
by construction) vs as scatter-/gather-indexed sparse-extent streams on the
burst contract (dispatch scatters token lines into the capacity slots,
sentinel rows absorb the drops; combine gathers each assignment's slot
back), asserted bit-identical before timing; the medusa ``_kernel`` variant
lowers both streams through the fused Pallas bursts.  Cells carry the
dispatch/combine word census plus ``tokens_dropped`` (the capacity drops).

Speculative-decode cell family (``decode_spec_k{2,4}``): one serving decode
step with k Medusa draft heads riding along (``decode_fn(draft=True)``,
step logits ``[B, 1+k, V]``) vs the dense step (``decode_spec_dense``);
row 0 is asserted bit-identical to the dense logits first — the draft rows
are pure bookkeeping input, never the commit path.

We lower every form over the same traffic and compare total HLO ops, gather
census, CPU wall time, and the scheduler word census (moved / padded /
folded / fused-kernel bursts), for the medusa and crossbar fabrics.
Semantics are asserted identical before measuring, and the unified forms run
through the issue()/commit() pipeline.

Results append to ``BENCH_fabric.json`` (dir from ``$BENCH_DIR``, default
cwd) — an append-only perf trajectory: each run adds a record carrying its
git SHA, date and axis settings, and prior records survive, so regressions
across PRs stay visible.  A legacy single-run artifact is migrated into the
first record.

    python -m benchmarks.fabric_unified [--pack {packed,pad,both}]
                                        [--fold {1,2,4} ...]
"""

from __future__ import annotations

import argparse
import dataclasses
import datetime
import json
import os
import socket
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import batch_lines
from repro.fabric import BurstScheduler, Fabric, SchedulerStats
from repro.fabric.scheduler import machine_word_dtype
from repro.kernels import ops as kops
from benchmarks.common import emit, time_us, hlo_op_census

N = 8            # ports
D = 64           # KV head_dim (lane width of the kv stream)


def _traffic():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    kv = jax.random.normal(ks[0], (16 * N, N, D), jnp.bfloat16)
    wt = jax.random.normal(ks[1], (8 * N, N, 32), jnp.bfloat16)
    moe = jax.random.normal(ks[2], (4 * N, N, 16), jnp.bfloat16)
    toks = np.arange(4 * 128, dtype=np.int32).reshape(4, 128) % 997
    stage = jnp.asarray(batch_lines(toks, N), jnp.bfloat16)
    return kv, wt, moe, stage


def _enqueue_all(sched, kv, wt, moe, stage):
    sched.enqueue_read("kv_read", kv)
    sched.enqueue_read("weight_stream", wt)
    sched.enqueue_read("moe_dispatch", moe)
    sched.enqueue_read("batch_stage", stage)


def _fns(impl: str, pack: str, fold=1):
    fab = Fabric.make(N, impl, pack=pack, word_fold=fold)

    def per_consumer(kv, wt, moe, stage):
        # seed style: one network call per consumer
        return (fab.read(kv), fab.read(wt), fab.read(moe), fab.read(stage))

    def unified(kv, wt, moe, stage):
        sched = BurstScheduler(fab)
        _enqueue_all(sched, kv, wt, moe, stage)
        sched.issue()                      # transfer overlaps consumer compute
        out = sched.commit()
        return (out["kv_read"], out["weight_stream"], out["moe_dispatch"],
                out["batch_stage"])

    return jax.jit(per_consumer), jax.jit(unified)


def _word_census(impl: str, pack: str, fold, args) -> SchedulerStats:
    stats = SchedulerStats()
    sched = BurstScheduler(Fabric.make(N, impl, pack=pack, word_fold=fold),
                           stats=stats)
    _enqueue_all(sched, *args)
    sched.flush()
    return stats


def _paged_workload(occ_pages: int):
    """One pool-backed KV leaf at a controlled occupancy: ``B`` slots each
    holding ``occ_pages`` of their ``pages_per_slot`` logical pages."""
    from repro.models import common as cm

    b, t_depth, ps = 8, 64, 8
    pages_per_slot = t_depth // ps
    pool_pages = b * pages_per_slot
    frames = pool_pages * ps
    pool = jax.random.normal(jax.random.PRNGKey(3), (frames, N, D),
                             jnp.bfloat16)
    table = np.full((b, pages_per_slot), -1, np.int32)
    nxt = 0
    for s in range(b):
        table[s, :occ_pages] = np.arange(nxt, nxt + occ_pages)
        nxt += occ_pages
    live_idx, expand, dense_pos = cm.page_live_plan(table, ps, t_depth, N)
    phys = cm.page_gather_indices(jnp.asarray(table), ps, t_depth)
    return pool, phys, (jnp.asarray(live_idx), jnp.asarray(expand),
                        jnp.asarray(dense_pos))


def _paged_fns(impl: str, fused: bool):
    """The decode step's per-leaf KV movement: read burst → dense per-slot
    view → scatter the update back → write burst.  ``fused`` selects the
    sparse-extent contract (network moves live frames) vs gather-after
    (network moves the pool)."""
    from repro.models import common as cm

    fab = Fabric.make(N, impl)

    def gather_after(pool, phys):
        sched = BurstScheduler(fab)
        sched.enqueue_read("kv", pool)
        banked = sched.flush()["kv"]
        pm = cm.banked_to_port_major(banked, (pool.shape[0],))
        dense = cm.gather_pool_frames(pm, phys, pm.ndim - 2)
        back = cm.scatter_pool_frames(pm, dense, phys, pm.ndim - 2)
        sched = BurstScheduler(fab)
        sched.enqueue_write("kv_w", cm.port_major_to_banked(back))
        return dense, sched.flush()["kv_w"]

    def fused_fn(pool, plan):
        live_idx, expand, dense_pos = plan
        sched = BurstScheduler(fab)
        sched.enqueue_read("kv", pool, gather=live_idx)
        banked = sched.flush()["kv"]
        pm = cm.banked_to_port_major(banked, (live_idx.shape[0],))
        dense = cm.gather_pool_frames(pm, expand, pm.ndim - 2)
        flat = dense.reshape(dense.shape[:-3]
                             + (dense.shape[-3] * dense.shape[-2],)
                             + dense.shape[-1:])
        compact = cm.gather_pool_frames(flat, dense_pos, flat.ndim - 2)
        sched = BurstScheduler(fab)
        sched.enqueue_write("kv_w", cm.port_major_to_banked(compact),
                            scatter=live_idx, into=pool)
        return dense, sched.flush()["kv_w"]

    return jax.jit(fused_fn) if fused else jax.jit(gather_after)


def _paged_census(impl: str, fused: bool, pool, phys, plan) -> SchedulerStats:
    """Traffic census matching the timed cell: one read AND one write burst
    (the decode step's two directions), so words_moved is what the timed
    function actually carried."""
    stats = SchedulerStats()
    sched = BurstScheduler(Fabric.make(N, impl), stats=stats)
    if fused:
        k = plan[0].shape[0]
        sched.enqueue_read("kv", pool, gather=plan[0])
        sched.enqueue_write("kv_w", jnp.zeros((k // N, N, N, D), pool.dtype),
                            scatter=plan[0], into=pool)
    else:
        sched.enqueue_read("kv", pool)
        sched.enqueue_write(
            "kv_w", jnp.zeros((pool.shape[0] // N, N, N, D), pool.dtype))
    sched.flush()
    return stats


def paged_decode_cells(cells: dict, rows: list) -> None:
    """The ``decode_fused`` vs ``decode_gather_after`` A/B at low/high pool
    occupancy (see module docstring).  Asserts bit-parity of the dense view
    and the written-back pool before timing."""
    for occ_pages, tag in ((2, "occ25"), (6, "occ75")):
        pool, phys, plan = _paged_workload(occ_pages)
        for impl in ("medusa", "crossbar"):
            kops.use_kernels(False)
            ref_dense, ref_pool = _paged_fns(impl, fused=False)(pool, phys)
            variants = [(f"decode_gather_after_{tag}", False, False),
                        (f"decode_fused_{tag}", True, False)]
            if impl == "medusa":
                variants.append((f"decode_fused_{tag}_kernel", True, True))
            for name, fused, kern in variants:
                kops.use_kernels(kern)
                fn = _paged_fns(impl, fused)
                arg = plan if fused else phys
                dense, pool_back = fn(pool, arg)
                assert np.array_equal(np.asarray(dense, np.float32),
                                      np.asarray(ref_dense, np.float32)), (
                    impl, name)
                assert np.array_equal(np.asarray(pool_back, np.float32),
                                      np.asarray(ref_pool, np.float32)), (
                    impl, name)
                stats = _paged_census(impl, fused, pool, phys, plan)
                cell = {"us": time_us(fn, pool, arg, iters=30),
                        "words_moved": stats.words_moved,
                        "words_live": stats.words_live,
                        "gather_fused_bursts": stats.gather_fused_bursts,
                        "kernel_bursts": stats.kernel_bursts}
                cells[f"{impl}/{name}"] = cell
                for key, val in cell.items():
                    rows.append((f"fabric_unified/{impl}/{name}/{key}",
                                 val if key == "us" else None,
                                 "" if key == "us" else val))
    kops.use_kernels(False)


SHARD_COUNTS = (1, 2, 4, 8)
_SHARDED_MARK = "SHARDED_CELLS_JSON:"


def _sharded_workload():
    """Pool-backed KV leaf sized so the sharded exchange's bucket padding
    vanishes at S=8: 4 slots x 32 live pages x 8 timesteps = 1024 live
    frames, 16 per (owner, requestor) bucket — already a whole number of
    N-groups, so ``cap`` needs no rounding and ``words_cross_shard`` lands
    at exactly ``(S-1)/S`` of the live traffic.  Physical pages stripe
    round-robin over the 8 finest shard blocks (``PagePool``'s allocation
    order), so every power-of-two coarsening of the ownership blocks stays
    balanced.  (Undersized buckets instead PAD the exchange — at tiny live
    counts the ``S(S-1)·cap`` floor can exceed the live traffic itself,
    which is the real locality tax of sharding a near-empty pool.)"""
    from repro.models import common as cm

    b, pages_per_slot, occ_pages, ps = 4, 32, 32, 8
    pool_pages = b * pages_per_slot           # 128 — divisible by every S
    frames = pool_pages * ps
    blk = pool_pages // max(SHARD_COUNTS)
    table = np.full((b, pages_per_slot), -1, np.int32)
    for i in range(b * occ_pages):
        s, j = divmod(i, occ_pages)
        table[s, j] = (i % max(SHARD_COUNTS)) * blk + i // max(SHARD_COUNTS)
    live_idx, _, _ = cm.page_live_plan(table, ps, pages_per_slot * ps, N)
    pool = jax.random.normal(jax.random.PRNGKey(7), (frames, N, D),
                             jnp.bfloat16)
    return pool, jnp.asarray(live_idx), frames, ps


def _sharded_fab(n_shards: int, collective: str = "all_to_all") -> Fabric:
    from repro.fabric import make_pool_mesh

    fab = Fabric.make(N, "medusa", pool_shards=n_shards,
                      collective=collective)
    if n_shards > 1:
        fab = dataclasses.replace(fab, mesh=make_pool_mesh(n_shards))
    return fab


def _sharded_step(fab: Fabric, k_tot: int, stats=None):
    """The decode round trip (sparse read burst → sparse write burst) on the
    pool-sharded lowering — or the single-device fused gather when the
    fabric isn't sharded (the 1dev baseline cell)."""
    sharded = fab.config.pool_shards > 1

    def step(pool, *ops):
        sched = BurstScheduler(fab, stats=stats)
        if sharded:
            fetch, place = ops
            shard = (fetch, place, k_tot)
            sched.enqueue_read("kv", pool[None], shard=shard)
            banked = sched.flush()["kv"]
            sched = BurstScheduler(fab, stats=stats)
            sched.enqueue_write("kv_w", banked, shard=shard, into=pool[None])
            return banked, sched.flush()["kv_w"][0]
        (live,) = ops
        sched.enqueue_read("kv", pool, gather=live)
        banked = sched.flush()["kv"]
        sched = BurstScheduler(fab, stats=stats)
        sched.enqueue_write("kv_w", banked, scatter=live, into=pool)
        return banked, sched.flush()["kv_w"]

    return step


def _sharded_cells() -> dict:
    """The ``decode_sharded_{S}dev`` cells; needs ``jax.device_count() >=
    max(SHARD_COUNTS)`` (the caller re-execs under forced host devices
    otherwise).  Asserts every shard count bit-identical to the 1-device
    fused gather, and the locality inequality ``words_cross_shard <
    words_moved`` at every S > 1."""
    from repro.fabric import shard_plan

    pool, live_idx, frames, ps = _sharded_workload()
    cells, ref = {}, None
    for s in SHARD_COUNTS:
        if s == 1:
            ops, k_tot = (live_idx,), int(live_idx.shape[0])
        else:
            plan = shard_plan(np.asarray(live_idx), frames, s, N,
                              cap_bucket=ps)
            ops, k_tot = plan.operands(), plan.k_tot
        fab = _sharded_fab(s)
        stats = SchedulerStats()
        fn = jax.jit(_sharded_step(fab, k_tot, stats=stats))
        banked, back = fn(pool, *ops)   # first call traces → census fills
        got = (np.asarray(banked, np.float32), np.asarray(back, np.float32))
        if ref is None:
            ref = got
        else:
            assert np.array_equal(got[0], ref[0]), f"{s}dev banked mismatch"
            assert np.array_equal(got[1], ref[1]), f"{s}dev pool mismatch"
        cell = {"us": time_us(fn, pool, *ops, iters=10),
                "pool_shards": s,
                "words_moved": stats.words_moved,
                "words_cross_shard": stats.words_cross_shard,
                "words_local": stats.words_moved - stats.words_cross_shard,
                "collective_calls": stats.collective_calls}
        if s > 1:
            assert cell["words_cross_shard"] < cell["words_moved"], cell
        cells[f"medusa/decode_sharded_{s}dev"] = cell
    return cells


def sharded_decode_cells(cells: dict, rows: list) -> None:
    """Collect the sharded cells, re-execing this module in a subprocess
    with forced host devices when this CPU process came up with too few
    (the XLA device count is frozen at first jax import, so it cannot be
    raised in-process).  On an accelerator this process already holds the
    chips, so a child could never reach them: the cells run in-process or
    not at all."""
    want = max(SHARD_COUNTS)
    if jax.device_count() >= want:
        sub = _sharded_cells()
    elif jax.default_backend() != "cpu":
        raise RuntimeError(
            f"sharded decode cells need {want} devices; this "
            f"{jax.default_backend()} process has {jax.device_count()} and "
            f"holds them, so no child process can run the cells — use a "
            f"host with {want} devices, or the CPU")
    else:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count"
                            f"={want}").strip()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.fabric_unified",
             "--sharded-json"],
            env=env, cwd=root, capture_output=True, text=True)
        marks = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith(_SHARDED_MARK)]
        if proc.returncode or not marks:
            raise RuntimeError(
                "sharded bench subprocess failed:\n"
                + proc.stdout[-1000:] + proc.stderr[-2000:])
        sub = json.loads(marks[-1][len(_SHARDED_MARK):])
    for name, cell in sub.items():
        cells[name] = cell
        for key, val in cell.items():
            rows.append((f"fabric_unified/{name}/{key}",
                         val if key == "us" else None,
                         "" if key == "us" else val))


def _moe_cfg(impl: str):
    from repro.configs.base import FabricConfig, ModelConfig, MoEConfig

    return ModelConfig(
        name=f"bench-moe-{impl}", family="moe", n_layers=1, d_model=D,
        n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=256,
        moe=MoEConfig(n_experts=8, top_k=2, expert_d_ff=128,
                      capacity_factor=1.0),
        fabric=FabricConfig(n_ports=N, lane_width=8, impl=impl))


def moe_dispatch_cells(cells: dict, rows: list) -> None:
    """The ``moe_dispatch_route`` vs ``moe_dispatch_burst`` A/B (see module
    docstring).  Bit-parity of the layer output is asserted before timing;
    the burst census runs eagerly so the word counters and the runtime
    ``tokens_dropped`` land in the cell.  ``capacity_factor=1.0`` on a
    random router makes the capacity genuinely bite."""
    from repro.models import moe as moe_mod

    x = jax.random.normal(jax.random.PRNGKey(5), (8, 64, D), jnp.float32)
    for impl in ("medusa", "crossbar"):
        cfg = _moe_cfg(impl)
        p = moe_mod.moe_params(jax.random.PRNGKey(1), cfg, jnp.float32)
        kops.use_kernels(False)
        ref = np.asarray(moe_mod.moe_apply(p, x, cfg, payload="route"))
        variants = [("moe_dispatch_route", "route", False),
                    ("moe_dispatch_burst", "burst", False)]
        if impl == "medusa":       # crossbar bursts never kernelize
            variants.append(("moe_dispatch_burst_kernel", "burst", True))
        for name, payload, kern in variants:
            kops.use_kernels(kern)
            stats = SchedulerStats()
            got = moe_mod.moe_apply(p, x, cfg, stats=stats, payload=payload)
            assert np.array_equal(np.asarray(got), ref), (impl, name)
            fn = jax.jit(lambda xx, _pl=payload: moe_mod.moe_apply(
                p, xx, cfg, payload=_pl))
            cell = {"us": time_us(fn, x, iters=30),
                    "words_moved": stats.words_moved,
                    "words_live": stats.words_live,
                    "kernel_bursts": stats.kernel_bursts,
                    "tokens_dropped": stats.tokens_dropped}
            cells[f"{impl}/{name}"] = cell
            for key, val in cell.items():
                rows.append((f"fabric_unified/{impl}/{name}/{key}",
                             val if key == "us" else None,
                             "" if key == "us" else val))
    kops.use_kernels(False)


def spec_decode_cells(cells: dict, rows: list) -> None:
    """The ``decode_spec_k{2,4}`` vs ``decode_spec_dense`` A/B: one decode
    step on the starcoder2 smoke config, with and without the Medusa draft
    rows appended.  Row 0 of the spec logits is asserted bit-identical to
    the dense step's before timing (same init key → identical base
    params; the draft heads fold their own key)."""
    from repro.configs import get_smoke
    from repro.models import api as mapi

    base = dataclasses.replace(get_smoke("starcoder2-15b"), dtype="float32")
    caches = mapi.init_cache(base, 4, 32)
    tok = jnp.ones((4, 1), jnp.int32)
    ref = None
    for k in (0, 2, 4):
        cfg = dataclasses.replace(base, spec_heads=k,
                                  name=f"{base.name}-speck{k}")
        params = mapi.init_params(cfg, jax.random.PRNGKey(0))
        fn = jax.jit(lambda p_, t_, c_, _cfg=cfg, _d=k > 0:
                     mapi.decode_fn(p_, t_, c_, 8, _cfg, draft=_d)[0])
        logits = fn(params, tok, caches)
        if k == 0:
            ref = np.asarray(logits)
            name = "decode_spec_dense"
        else:
            assert logits.shape[1] == 1 + k, logits.shape
            assert np.array_equal(np.asarray(logits[:, :1]), ref), k
            name = f"decode_spec_k{k}"
        cell = {"us": time_us(fn, params, tok, caches, iters=30),
                "draft_rows": k}
        cells[f"medusa/{name}"] = cell
        for key, val in cell.items():
            rows.append((f"fabric_unified/medusa/{name}/{key}",
                         val if key == "us" else None,
                         "" if key == "us" else val))


def _git_sha() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:
        return "unknown"


def _append_run(path: str, run: dict) -> None:
    """Append-only trajectory: keep every prior run record; migrate a legacy
    single-run (flat dict) artifact into the first record."""
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
        except (OSError, json.JSONDecodeError):
            old = None
        if isinstance(old, dict) and isinstance(old.get("runs"), list):
            history = old["runs"]
        elif isinstance(old, dict):           # legacy flat artifact (PR 2)
            legacy = {"git_sha": "legacy", "date": "unknown",
                      "workload": old.pop("workload", None), "cells": old}
            history = [legacy]
        else:
            # never overwrite a trajectory we can't extend — move the
            # unreadable/unrecognized file aside so the history survives
            aside = path + ".corrupt"
            os.replace(path, aside)
            print(f"# warning: {path} was not a recognized trajectory; "
                  f"moved to {aside}")
    for rec in history:           # backfill pre-metadata records in place
        if rec.get("date") is None:
            rec["date"] = "unknown"
    history.append(run)
    with open(path, "w") as f:
        json.dump({"runs": history}, f, indent=2, sort_keys=True)


def run(packs=("packed", "pad"), folds=(1, 2)) -> list:
    # a fold cell must measure what its name says: drop factors whose
    # machine word doesn't exist for this bf16 traffic (u64 needs x64 —
    # the scheduler would silently degrade the group and mislabel the cell)
    realizable = tuple(f for f in folds
                       if f == 1 or machine_word_dtype(2 * f) is not None)
    for f in folds:
        if f not in realizable:
            print(f"# skipping fold{f} cells: no {2 * f}-byte machine word "
                  f"on this platform (enable x64)")
    folds = realizable
    args = _traffic()
    rows = []
    cells = {}
    kernels_before = kops.kernels_enabled()

    def variants_for(impl):
        out = [("per_consumer", None, 1, False)]
        if "pad" in packs:
            out.append(("unified_pad", "pad", 1, False))
            # fold-aware pad: the baseline layout on the same u32/u64 lanes,
            # isolating the packing effect from the lane width
            for fold in folds:
                if fold > 1:
                    out.append((f"unified_pad_fold{fold}", "pad", fold,
                                False))
        if "packed" in packs:
            # headline cell: the default fabric config (word_fold="auto")
            out.append(("unified_packed", "packed", "auto", False))
            for fold in folds:
                out.append((f"unified_packed_fold{fold}", "packed", fold,
                            False))
            if impl == "medusa":       # crossbar bursts never kernelize
                out.append(("unified_packed_kernel", "packed", 1, True))
                if 2 in folds:
                    out.append(("unified_packed_fold2_kernel", "packed", 2,
                                True))
        return out

    try:
        for impl in ("medusa", "crossbar"):
            kops.use_kernels(False)
            ref = _fns(impl, "packed")[0](*args)
            for name, pack, fold, kern in variants_for(impl):
                kops.use_kernels(kern)
                per, uni = _fns(impl, pack or "packed", fold)
                fn = per if pack is None else uni
                for x, y in zip(ref, fn(*args)):
                    assert np.array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32)), (impl,
                                                                       name)
                census = hlo_op_census(fn, *args)
                gathers = (census.get("gather", 0)
                           + census.get("dynamic-slice", 0)
                           + census.get("scatter", 0))
                cell = {"us": time_us(fn, *args, iters=50),
                        "total_hlo_ops": sum(census.values()),
                        "gather_ops": gathers}
                if pack is not None:
                    stats = _word_census(impl, pack, fold, args)
                    cell["network_calls"] = stats.network_calls
                    cell["words_moved"] = stats.words_moved
                    cell["words_padded"] = stats.words_padded
                    cell["words_folded"] = stats.words_folded
                    cell["kernel_bursts"] = stats.kernel_bursts
                else:
                    cell["network_calls"] = 4
                    cell["words_moved"] = sum(
                        int(np.prod(a.shape)) for a in args)
                    cell["words_padded"] = 0
                    cell["words_folded"] = 0
                    cell["kernel_bursts"] = 0
                cells[f"{impl}/{name}"] = cell
                for key, val in cell.items():
                    rows.append((f"fabric_unified/{impl}/{name}/{key}",
                                 val if key == "us" else None,
                                 "" if key == "us" else val))
        paged_decode_cells(cells, rows)
        sharded_decode_cells(cells, rows)
        moe_dispatch_cells(cells, rows)
        spec_decode_cells(cells, rows)
    finally:
        kops.use_kernels(kernels_before)

    run_record = {
        "git_sha": _git_sha(),
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "hostname": socket.gethostname(),
        "jax": jax.__version__,
        "workload": {"n_ports": N, "streams": 4, "words": [D, 32, 16, 1],
                     "dtype": "bfloat16"},
        "axes": {"packs": list(packs), "folds": list(folds),
                 "x64": bool(jax.config.read("jax_enable_x64"))},
        "cells": cells,
    }
    path = os.path.join(os.environ.get("BENCH_DIR", "."), "BENCH_fabric.json")
    _append_run(path, run_record)

    m, c = cells.get("medusa/unified_packed"), cells.get(
        "crossbar/unified_packed")
    if m and c:
        print(f"# medusa/crossbar unified_packed wall-clock: "
              f"{m['us']:.0f}us / {c['us']:.0f}us = {m['us'] / c['us']:.2f}x")
    mk = cells.get("medusa/unified_packed_kernel")
    if m and mk:
        print(f"# medusa fused-kernel burst HLO ops: "
              f"{mk['total_hlo_ops']} (unrolled {m['total_hlo_ops']})")
    ga = cells.get("medusa/decode_gather_after_occ25")
    fu = cells.get("medusa/decode_fused_occ25")
    if ga and fu:
        print(f"# medusa paged decode @25% occupancy: fused "
              f"{fu['us']:.0f}us / {fu['words_moved']} words vs "
              f"gather-after {ga['us']:.0f}us / {ga['words_moved']} words")
    s1 = cells.get("medusa/decode_sharded_1dev")
    s8 = cells.get(f"medusa/decode_sharded_{max(SHARD_COUNTS)}dev")
    if s1 and s8:
        print(f"# sharded pool decode at {s8['pool_shards']} shards: "
              f"{s8['words_cross_shard']} of {s8['words_moved']} words "
              f"crossed shards "
              f"({s8['words_cross_shard'] / s8['words_moved']:.0%}, "
              f"{s8['words_local']} stayed local); wall {s1['us']:.0f}us "
              f"(1dev) -> {s8['us']:.0f}us "
              f"({s8['pool_shards']}dev, host devices)")
    mr = cells.get("medusa/moe_dispatch_route")
    mb = cells.get("medusa/moe_dispatch_burst")
    if mr and mb:
        print(f"# medusa moe dispatch: burst {mb['us']:.0f}us / "
              f"{mb['words_moved']} words vs route {mr['us']:.0f}us; "
              f"{mb['tokens_dropped']} assignments dropped at capacity")
    k0 = cells.get("medusa/decode_spec_dense")
    k2 = cells.get("medusa/decode_spec_k2")
    if k0 and k2:
        print(f"# spec decode step: k=2 draft rows {k2['us']:.0f}us vs "
              f"dense {k0['us']:.0f}us")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--pack", choices=["packed", "pad", "both"],
                    default="both", help="burst layout(s) to A/B")
    ap.add_argument("--fold", type=int, nargs="*", default=None,
                    choices=[1, 2, 4],
                    help="word_fold factors to sweep (default: 1 2, plus 4 "
                         "under x64)")
    ap.add_argument("--sharded-json", action="store_true",
                    help="run only the decode_sharded_* cells and print "
                         "them as JSON (the forced-device-count subprocess "
                         "re-exec; no BENCH_fabric.json append)")
    a = ap.parse_args()
    if a.sharded_json:
        print(_SHARDED_MARK + json.dumps(_sharded_cells()))
        sys.exit(0)
    folds = tuple(a.fold) if a.fold else (
        (1, 2, 4) if jax.config.read("jax_enable_x64") else (1, 2))
    emit(run(("packed", "pad") if a.pack == "both" else (a.pack,), folds))

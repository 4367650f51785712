"""Decoder-only LM assembler: pattern-scanned blocks over every family.

A model is a tiled ``block_pattern`` (e.g. ``"A"`` dense, ``"LLLLLG"``→
``"LLLLLA"`` gemma3, ``"RRA"`` recurrentgemma, ``"M"`` mamba2).  The pattern
unit is scanned ``reps = n_layers // len(pattern)`` times with stacked
parameters (one compiled body regardless of depth — critical for 80 dry-run
compiles on CPU); remainder layers run unrolled ("tail").

Caches are pytrees stacked the same way, so ``serve_step`` scans decode with
the cache as scan xs/ys.  VLM configs prepend stub patch embeddings.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.fabric.scheduler import FRAME_SENTINEL
from repro.models import common as cm
from repro.models.moe import moe_params, moe_apply
from repro.models.mamba2 import mamba_params, mamba_apply
from repro.models.rglru import rglru_params, rglru_apply
from repro.parallel.sharding import shard


def pattern_unit(cfg: ModelConfig):
    pat = cfg.block_pattern
    reps = cfg.n_layers // len(pat)
    tail = pat[: cfg.n_layers - reps * len(pat)]
    return pat, reps, tail


# ----------------------------------------------------------------------------
# parameters
# ----------------------------------------------------------------------------

def _block_params(key, t: str, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    p = {"norm1": cm.init_norm(ks[0], cfg.d_model, dtype, cfg.norm)}
    if t in ("A", "L"):
        p["attn"] = cm.attention_block_params(ks[1], cfg, dtype)
    elif t == "R":
        p["rec"] = rglru_params(ks[1], cfg, dtype)
    elif t == "M":
        p["mixer"] = mamba_params(ks[1], cfg, dtype)
        return p                      # mamba block has no separate FFN
    else:
        raise ValueError(f"unknown block type {t!r}")
    p["norm2"] = cm.init_norm(ks[2], cfg.d_model, dtype, cfg.norm)
    if cfg.moe is not None:
        p["ffn"] = moe_params(ks[3], cfg, dtype)
    else:
        p["ffn"] = cm.mlp_params(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = cfg.param_dtype
    unit, reps, tail = pattern_unit(cfg)
    k_emb, k_unit, k_tail, k_fin = jax.random.split(key, 4)
    params = {"embed": cm.embed_params(k_emb, cfg, dtype)}
    unit_params = []
    for i, t in enumerate(unit):
        kt = jax.random.fold_in(k_unit, i)
        if reps > 0:
            stacked = jax.vmap(lambda k: _block_params(k, t, cfg, dtype))(
                jax.random.split(kt, reps))
            unit_params.append(stacked)
    params["unit"] = unit_params
    params["tail"] = [_block_params(jax.random.fold_in(k_tail, i), t, cfg, dtype)
                      for i, t in enumerate(tail)]
    params["final_norm"] = cm.init_norm(k_fin, cfg.d_model, dtype, cfg.norm)
    if cfg.spec_heads:
        params["draft"] = cm.draft_head_params(
            jax.random.fold_in(key, 0xD4AF7), cfg, dtype)
    return params


# ----------------------------------------------------------------------------
# caches (decode)
# ----------------------------------------------------------------------------

def _block_cache(t: str, cfg: ModelConfig, batch: int, t_max: int, dtype,
                 pool=None):
    hd = cfg.resolved_head_dim
    if t in ("A", "L"):
        if pool is not None and _full_attn(t, cfg):
            # shared physical page pool: one [n_pages, page_size, Hkv, D]
            # region per full-attention leaf — slots reach their frames
            # through the engine's logical→physical page table, so the
            # leaf has no per-slot batch axis at all
            n_pages, page_size = pool
            return {"k": jnp.zeros((n_pages, page_size, cfg.n_kv_heads, hd),
                                   dtype),
                    "v": jnp.zeros((n_pages, page_size, cfg.n_kv_heads, hd),
                                   dtype)}
        # local layers only ever need the sliding window
        length = min(t_max, cfg.sliding_window) if (
            t == "L" and cfg.sliding_window) else t_max
        return {"k": jnp.zeros((batch, length, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, length, cfg.n_kv_heads, hd), dtype)}
    if t == "R":
        w = (cfg.rglru.lru_width or cfg.d_model)
        return {"conv": jnp.zeros((batch, cfg.rglru.conv_width - 1, w), dtype),
                "h": jnp.zeros((batch, w), jnp.float32)}
    if t == "M":
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        nh = d_in // s.head_dim
        return {"conv": jnp.zeros((batch, s.conv_width - 1,
                                   d_in + 2 * s.d_state), dtype),
                "state": jnp.zeros((batch, nh, s.head_dim, s.d_state),
                                   jnp.float32)}
    raise ValueError(t)


def init_cache(cfg: ModelConfig, batch: int, t_max: int,
               pool_pages: int = 0, page_size: int = 0) -> dict:
    """The batched decode-cache pytree.  With ``pool_pages > 0`` every
    full-attention leaf is backed by a shared physical page pool
    ``[pool_pages, page_size, Hkv, D]`` instead of a dense per-slot
    ``[batch, t_max]`` reservation (ring/recurrent/SSM leaves keep their
    per-slot layout — they are O(window)/O(1) in time)."""
    dtype = cfg.param_dtype
    pool = (pool_pages, page_size) if pool_pages else None
    unit, reps, tail = pattern_unit(cfg)
    unit_caches = []
    for t in unit:
        if reps > 0:
            c = _block_cache(t, cfg, batch, t_max, dtype, pool=pool)
            unit_caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (reps,) + x.shape), c))
    return {"unit": unit_caches,
            "tail": [_block_cache(t, cfg, batch, t_max, dtype, pool=pool)
                     for t in tail]}


def paged_entries(cfg: ModelConfig):
    """The ``(kind, index)`` cache entries the paged pool backs: every
    full-attention layer's ``k``/``v`` (the same set the burst plan banks).
    Ring, recurrent and SSM caches stay dense per-slot."""
    unit, reps, tail = pattern_unit(cfg)
    out = []
    for kind, types in (("unit", unit if reps > 0 else ""), ("tail", tail)):
        for i, t in enumerate(types):
            if _full_attn(t, cfg):
                out.append((kind, i))
    return out


# ----------------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------------

def _sliding_cache_update(cache_kv, k_new, pos, window):
    """Ring-buffer write for local-attention caches (bounded memory at 500k);
    ``pos`` may be scalar or per-row [B] (serving)."""
    slot = pos % cache_kv.shape[1]
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache_kv, k_new, slot,
                                                   axis=1)
    return jax.vmap(lambda c, n, p:
                    jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
                    )(cache_kv, k_new, slot)


def _block_apply(t: str, bp: dict, x, cfg: ModelConfig, *, positions,
                 cache=None, pos=None, kv_chunk=0, pm_cache=None):
    h = cm.apply_norm(x, bp["norm1"], cfg.norm)
    new_cache = None
    if t in ("A", "L"):
        if pm_cache is not None:
            # burst-scheduled decode: this layer's cache arrived port-major
            # from the step's shared read burst; attend/update in that form
            # and hand the fresh K/V frames to the step's write burst.
            qpos = pos[None] if pos.ndim == 0 else pos[:, None]
            h, new_cache = cm.attention_apply_banked(
                bp["attn"], h, cfg, positions=qpos, layer_kind=t,
                cache={"k_pm": pm_cache["k_pm"], "v_pm": pm_cache["v_pm"],
                       "pos": pos})
        elif cache is not None:
            # local layers always use a ring (windowed) cache in decode —
            # bounded memory even at 500k context.
            acache = {"k": cache["k"], "v": cache["v"], "pos": pos,
                      "ring": bool(t == "L" and cfg.sliding_window)}
            h, kv = _attn_cached(bp["attn"], h, cfg, t, acache, kv_chunk)
            new_cache = {"k": kv["k"], "v": kv["v"]}
        else:
            h, _ = cm.attention_apply(bp["attn"], h, cfg, positions=positions,
                                      layer_kind=t, cache=None,
                                      kv_chunk=kv_chunk)
    elif t == "R":
        h, new_cache = rglru_apply(bp["rec"], h, cfg, cache)
    elif t == "M":
        h, new_cache = mamba_apply(bp["mixer"], h, cfg, cache)
        return x + h, new_cache       # mamba block: mixer only
    x = x + h
    h = cm.apply_norm(x, bp["norm2"], cfg.norm)
    if cfg.moe is not None:
        h = moe_apply(bp["ffn"], h, cfg)
    else:
        h = cm.mlp_apply(bp["ffn"], h, cfg.mlp)
    return x + h, new_cache


def _attn_cached(p, x, cfg, layer_kind, cache, kv_chunk):
    """Decode-path attention with either a full or ring (windowed) cache."""
    ring = cache.pop("ring", False)
    cpos = cache["pos"]
    qpos = cpos[None] if cpos.ndim == 0 else cpos[:, None]
    if not ring:
        return cm.attention_apply(p, x, cfg, positions=qpos,
                                  layer_kind=layer_kind, cache=cache,
                                  kv_chunk=kv_chunk)
    # ring cache: positions of slots are pos - window + 1 .. pos (mod window)
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    pos = cache["pos"]
    win = cache["k"].shape[1]
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (x @ p["wk"]).reshape(b, s, hkv, hd)
    v = (x @ p["wv"]).reshape(b, s, hkv, hd)
    q = cm.rope(q, qpos, cfg.rope_theta)
    k = cm.rope(k, qpos, cfg.rope_theta)
    ck = _sliding_cache_update(cache["k"], k, pos, win)
    cv = _sliding_cache_update(cache["v"], v, pos, win)
    slots = jnp.arange(win)
    if pos.ndim == 0:
        slot_pos = pos - ((pos - slots) % win)  # absolute position per slot
        valid = (slot_pos >= 0) & (slot_pos <= pos)
        out = cm.cached_attention(q, ck, cv, pos, slot_pos, valid, 0, cfg)
    else:
        slot_pos = pos[:, None] - ((pos[:, None] - slots[None, :]) % win)
        valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
        # per-row kv positions: fold into the mask (window already enforced
        # by the ring size); use row-wise attention via the generic mask path
        out = _ring_attention_per_row(q, ck, cv, slot_pos, valid, cfg)
    y = out.reshape(b, s, h * hd) @ p["wo"]
    return y, {"k": ck, "v": cv}


def _ring_attention_per_row(q, ck, cv, slot_pos, valid, cfg):
    """Ring-cache decode attention with per-row slot positions (serving)."""
    b, sq, h, d = q.shape
    hkv = ck.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d) * (d ** -0.5)
    s = jnp.einsum("bqhgd,bthd->bhgqt", qg.astype(ck.dtype), ck)
    s = s.astype(jnp.float32)
    s = jnp.where(valid[:, None, None, None, :], s, jnp.float32(-1e30))
    p_attn = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqt,bthd->bqhgd", p_attn.astype(cv.dtype), cv)
    return out.reshape(b, sq, h, d)


def _scan_blocks(params, x, cfg, *, positions, caches=None, pos=None,
                 kv_chunk=0, remat=True, pm_caches=None):
    unit, reps, tail = pattern_unit(cfg)
    pm_unit = pm_caches["unit"] if pm_caches is not None else [None] * len(unit)
    pm_tail = pm_caches["tail"] if pm_caches is not None else [None] * len(tail)

    if reps > 0:
        def body(carry, xs):
            h = carry
            if caches is None:
                unit_p = xs
                new_cs = None
                for t, bp in zip(unit, unit_p):
                    h, _ = _block_apply(t, bp, h, cfg, positions=positions,
                                        kv_chunk=kv_chunk)
            else:
                unit_p, unit_c, unit_pm = xs
                new_cs = []
                for t, bp, c, pmc in zip(unit, unit_p, unit_c, unit_pm):
                    h, nc = _block_apply(t, bp, h, cfg, positions=positions,
                                         cache=c, pos=pos, kv_chunk=kv_chunk,
                                         pm_cache=pmc)
                    new_cs.append(nc)
            return h, new_cs

        if remat and cfg.remat != "none":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat == "dots" else None)
            body = jax.checkpoint(body, policy=policy)
        xs = (tuple(params["unit"]) if caches is None
              else (tuple(params["unit"]), tuple(caches["unit"]),
                    tuple(pm_unit)))
        x, new_unit_caches = jax.lax.scan(body, x, xs)
    else:
        new_unit_caches = None

    new_tail = []
    for i, t in enumerate(tail):
        c = caches["tail"][i] if caches is not None else None
        x, nc = _block_apply(t, params["tail"][i], x, cfg, positions=positions,
                             cache=c, pos=pos, kv_chunk=kv_chunk,
                             pm_cache=pm_tail[i])
        new_tail.append(nc)
    new_caches = (None if caches is None
                  else {"unit": new_unit_caches, "tail": new_tail})
    return x, new_caches


def forward(params, tokens, cfg: ModelConfig, *, patch_embeds=None,
            kv_chunk: int = 0, remat: bool = True):
    """Training / prefill forward → logits [B, S(+P), V]."""
    x = cm.embed_apply(params["embed"], tokens)
    if cfg.n_patches and patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
        x = shard(x, "batch", "seq", "d_model")
    s = x.shape[1]
    positions = jnp.arange(s)
    x, _ = _scan_blocks(params, x, cfg, positions=positions,
                        kv_chunk=kv_chunk, remat=remat)
    x = cm.apply_norm(x, params["final_norm"], cfg.norm)
    return cm.logits_apply(params["embed"], x, cfg)


def _emit_logits(params, x, cfg: ModelConfig, draft: bool) -> jax.Array:
    """Step logits off the final-norm hidden state.  With ``draft`` (and
    draft-head params present) the k Medusa draft heads append their
    proposals along the position axis: ``[B, 1+k, V]`` with row 0 the real
    unembedding — callers that index ``[:, 0]`` (or don't pass ``draft``)
    see exactly the dense logits."""
    logits = cm.logits_apply(params["embed"], x, cfg)
    if draft and "draft" in params:
        logits = jnp.concatenate(
            [logits,
             cm.draft_logits(params["draft"], x, params["embed"], cfg)],
            axis=1)
    return logits


def decode_step(params, token, caches, pos, cfg: ModelConfig, sched=None,
                page_table=None, page_size: int = 0, t_depth: int = 0,
                live_plan=None, shard_plans=None, draft: bool = False):
    """One serving decode step: ``token [B, 1]`` + caches at ``pos`` →
    (logits [B, 1, V], new caches).  KV caches are read through the Medusa
    port-major layout engine (cfg.kv_layout).  With ``draft`` the Medusa
    draft heads ride along: logits become ``[B, 1+k, V]``
    (see :func:`_emit_logits`); cache movement is unchanged.

    With a :class:`repro.fabric.BurstScheduler` (``sched``), every
    full-attention leaf's port-major conversion is hoisted out of the layer
    scan into one shared read burst at the top of the step, attention runs
    (and the new token's K/V is written) in port-major space, the scan
    emits each layer's fresh K/V frames, and one write burst at the bottom
    restores the updated line-major caches, rebuilt from those frames
    outside the scan — 1 read + 1 write network invocation per dtype per
    step instead of 2 conversions per layer, bit-identical because banking
    is a permutation that commutes with the single-timestep update.  Falls
    back to the per-layer path when
    the fabric is not on the port-per-KV-head geometry or a leaf's line
    count does not divide N.

    With ``page_table`` (``int32 [B, pages_per_slot]``, ``-1`` = unmapped),
    the full-attention leaves are shared physical page pools
    (:func:`init_cache` with ``pool_pages``): the step gathers each slot's
    mapped frames through the table — after the read burst, in port-major
    space, so the gather composes with the banked layout — attends on the
    gathered dense view, and scatters the updated frames back before the
    write burst.  Bit-identical to the dense layout: every valid position
    gathers exactly the frame the dense cache would hold.  ``page_size``
    and ``t_depth`` (the dense time depth the gather reconstructs) are
    static step parameters.

    With ``live_plan`` (the ``(live_idx, expand, dense_pos)`` operands from
    :func:`repro.models.common.page_live_plan` — ``FabricConfig.
    fused_gather``), the logical→physical gather is fused into the burst
    contract instead: the scheduler's sparse-extent streams bank ONLY the
    live frames the table maps (indices prefetched into the fused burst
    kernel on the kernelized medusa fabric), so the read network's traffic
    scales with live tokens rather than pool capacity.  The write burst
    then carries only the step's fresh frames — one per slot per layer, at
    ``live_idx[expand[b, pos[b]]]`` — and lands them in the pool, whose
    every other frame stays as it was: bit-identical to both the
    gather-after-burst form and the dense engine.

    With ``shard_plans`` (``{reps: (fetch, place)}`` device operands from
    :func:`repro.fabric.shard_plan`, one per distinct leaf rep count —
    ``FabricConfig.pool_shards > 1``), the fused sparse bursts lower over
    the pool-sharded mesh instead: per-shard fused gathers bridged by one
    collective per stream (:mod:`repro.fabric.sharded`), bit-identical to
    the single-device fused path.  Requires ``live_plan``."""
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[None] if pos.ndim == 0 else pos[:, None]
    phys = (None if page_table is None
            else cm.page_gather_indices(page_table, page_size, t_depth))
    plan = _burst_plan(cfg, caches) if sched is not None else None
    if plan is not None:
        live = live_plan if phys is not None else None
        return _decode_step_scheduled(params, token, caches, pos, positions,
                                      cfg, sched, plan, phys=phys, live=live,
                                      shard_plans=(shard_plans
                                                   if live is not None
                                                   else None), draft=draft)
    if phys is not None:
        return _decode_step_paged_fallback(params, token, caches, pos,
                                           positions, cfg, phys, draft=draft)
    x = cm.embed_apply(params["embed"], token)
    x, new_caches = _scan_blocks(params, x, cfg, positions=positions,
                                 caches=caches, pos=pos, remat=False)
    x = cm.apply_norm(x, params["final_norm"], cfg.norm)
    return _emit_logits(params, x, cfg, draft), new_caches


def _full_attn(t: str, cfg: ModelConfig) -> bool:
    """Full-depth attention layers (ring/recurrent/SSM caches stay on their
    own decode paths — the fabric's small "control" traffic)."""
    return t in ("A", "L") and not (t == "L" and cfg.sliding_window)


def _burst_plan(cfg: ModelConfig, caches):
    """The cache entries a scheduled decode step routes through the shared
    burst: every full-attention ``k``/``v`` leaf, provided the fabric is on
    the port-per-KV-head geometry (leaf head axis == N) and each leaf's
    flattened line count divides N.  Returns ``[(kind, index), ...]`` or
    None to fall back to the per-layer path.  The ``fused`` fabric never
    banks — its consumers contract against the line-major cache directly,
    so scheduling would materialize exactly the copies it elides."""
    fab = cfg.resolved_fabric
    n = fab.n_ports
    if fab.impl == "fused":
        return None
    if n != cfg.n_kv_heads or fab.lane_width != cfg.resolved_head_dim:
        return None
    unit, reps, tail = pattern_unit(cfg)
    plan = []
    for kind, types in (("unit", unit if reps > 0 else ""), ("tail", tail)):
        for i, t in enumerate(types):
            if not _full_attn(t, cfg):
                continue
            leaf = caches[kind][i]["k"]
            lines = 1
            for s in leaf.shape[:-2]:
                lines *= s
            if leaf.shape[-2] != n or lines % n:
                return None
            plan.append((kind, i))
    return plan or None


def _flat_frames(pool: jax.Array) -> jax.Array:
    """Pool leaf ``[lead..., n_pages, page_size, Hkv, D]`` → flattened frame
    axis ``[lead..., F, Hkv, D]`` (F = n_pages * page_size)."""
    return pool.reshape(pool.shape[:-4] + (-1,) + pool.shape[-2:])


def _decode_step_scheduled(params, token, caches, pos, positions,
                           cfg: ModelConfig, sched, plan, phys=None,
                           live=None, shard_plans=None, draft=False):
    """The burst-scheduled decode step (see :func:`decode_step`).

    Burst 1 (read network): every planned KV leaf — and, under
    ``cfg.serve_fsdp``, every streamable weight leaf (the ZeRO-1 weight
    all-gather traffic) — moves through one read invocation per dtype.
    The layer scan attends on the banked views and emits only each layer's
    fresh K/V frames ``[lead?, B, Hkv, D]``.  Burst 2 (write network): the
    updated port-major caches, rebuilt from those frames outside the scan,
    return to line-major.  The issue()/commit() split keeps the transfers
    overlappable with consumer compute under JAX async dispatch / XLA
    scheduling.

    Under the paged pool (``phys`` — per-slot physical frame indices), the
    bursts carry the pool's F frames instead of the dense [B, t] regions;
    the per-slot gather (and the update's scatter) happens in port-major
    space on the network's output, composing with the banked layout.

    With ``live`` (the fused-gather plan — see :func:`decode_step`), the
    gather moves INTO the read burst: each pool leaf becomes a
    sparse-extent stream banking only its live frames, and the dense [B, T]
    view is a cheap relabel of the live-sized output.  On one device the
    write burst is one small dense stream per leaf carrying the fresh
    frames alone, landed at each slot's new frame (sentinels — idle slots,
    padding — drop), so the pool is written ``B x layers`` frames a step
    whatever its occupancy.  Pool-sharded (``shard_plans``), the updated
    view compacts back through the inverse map (``dense_pos``) and the
    sharded sparse write returns every live frame to its owning shard."""
    fab = cfg.resolved_fabric
    n = fab.n_ports
    if live is not None:
        live_idx, expand, dense_pos = live

    def leaf_reps(leaf):
        """The leaf's leading layer-stack factor (1 for tail leaves)."""
        flat = _flat_frames(leaf)
        reps = 1
        for s in flat.shape[:-3]:
            reps *= s
        return reps

    def leaf_gather_idx(leaf, idx):
        """Per-pool frame indices ``idx`` (the step's live frames, or each
        slot's new frame) tiled over the leaf's leading layer axis (unit
        leaves stack reps) into its flattened line stream."""
        flat = _flat_frames(leaf)
        if flat.ndim == 3:                       # tail leaf: [F, N, D]
            return idx
        return cm.pool_rep_indices(idx, leaf_reps(leaf), flat.shape[-3])

    def leaf_shard(leaf):
        """The leaf's ``shard=`` operand tuple: the step's pre-split
        fetch/place plan for its rep count, plus the static line total."""
        reps = leaf_reps(leaf)
        fetch, place = shard_plans[reps]
        return fetch, place, reps * live_idx.shape[0]

    def leaf_stream(leaf):
        """The leaf's rep-major pool line stream ``[R, F, N, D]`` — the
        explicit rep axis keeps page ownership consistent across reps under
        the pool-sharded ``PartitionSpec``."""
        flat = _flat_frames(leaf)
        if flat.ndim == 3:
            return flat[None]
        return flat.reshape((-1,) + flat.shape[-3:])

    # -- burst 1: weight stream + KV banking --------------------------------
    streamed = None
    if cfg.serve_fsdp:
        streamed = _enqueue_weight_stream(sched, params, n)
    for kind, i in plan:
        for leaf_name in ("k", "v"):
            leaf = caches[kind][i][leaf_name]
            if phys is not None:
                if live is not None and shard_plans is not None:
                    sched.enqueue_read(f"{kind}{i}/{leaf_name}",
                                       leaf_stream(leaf),
                                       shard=leaf_shard(leaf))
                    continue
                flat = _flat_frames(leaf)
                sched.enqueue_read(
                    f"{kind}{i}/{leaf_name}", cm.kv_leaf_to_lines(flat),
                    gather=leaf_gather_idx(leaf, live_idx) if live is not None
                    else None)
                continue
            sched.enqueue_read(f"{kind}{i}/{leaf_name}",
                               cm.kv_leaf_to_lines(leaf))
    sched.issue()
    moved = sched.commit()
    if streamed is not None:
        params = _rebuild_weight_stream(moved, *streamed)

    pm = {"unit": [None] * len(caches["unit"]),
          "tail": [None] * len(caches["tail"])}
    pm_pools = {}
    for kind, i in plan:
        if phys is None:
            lead = caches[kind][i]["k"].shape[:-2]
            pm[kind][i] = {
                leaf_name + "_pm": cm.banked_to_port_major(
                    moved[f"{kind}{i}/{leaf_name}"], lead)
                for leaf_name in ("k", "v")}
            continue
        flat_shape = _flat_frames(caches[kind][i]["k"]).shape
        if live is not None:
            # the banked output is live-sized: [lead?, Hkv, L_live, D]
            lead = flat_shape[:-3] + (live_idx.shape[0],)
        else:
            lead = flat_shape[:-2]
        entry = {}
        for leaf_name in ("k", "v"):
            # [lead?, Hkv, F|L_live, D]: each port's frame stream
            pool_pm = cm.banked_to_port_major(
                moved[f"{kind}{i}/{leaf_name}"], lead)
            if live is None:
                pm_pools[(kind, i, leaf_name)] = pool_pm
            # fused: expand relabels the compact live frames to the dense
            # [B, T] view; fallback: full logical→physical gather
            dense_pm = cm.gather_pool_frames(
                pool_pm, expand if live is not None else phys,
                pool_pm.ndim - 2)
            # [lead?, Hkv, B, T, D] → [lead?, B, Hkv, T, D]
            entry[leaf_name + "_pm"] = jnp.moveaxis(dense_pm, -3, -4)
        pm[kind][i] = entry

    x = cm.embed_apply(params["embed"], token)
    x, new_caches = _scan_blocks(params, x, cfg, positions=positions,
                                 caches=caches, pos=pos, remat=False,
                                 pm_caches=pm)

    # -- burst 2: the step's writes → line-major ------------------------------
    # the layer scan emits each layer's fresh frames [lead?, B, Hkv, D]; the
    # fused single-device step writes only those, the other branches
    # rebuild the updated views from them and write those back whole
    fresh_only = live is not None and shard_plans is None
    if fresh_only:
        new_frame = cm.step_frame_indices(live_idx, expand, pos)
    landing = {}
    for kind, i in plan:
        for leaf_name in ("k", "v"):
            name = f"{kind}{i}/{leaf_name}"
            fresh = new_caches[kind][i][leaf_name + "_new"]
            leaf = caches[kind][i][leaf_name]
            if fresh_only:
                banked, landing[name] = _fresh_write_stream(
                    fresh, leaf_gather_idx(leaf, new_frame), n)
                sched.enqueue_write(name, banked)
                continue
            new_pm = cm.pm_cache_write(pm[kind][i][leaf_name + "_pm"],
                                        fresh, pos)
            if phys is not None and live is not None:
                # compact the updated dense view back to live frames and
                # scatter them into the pool through the sharded write
                upd = jnp.moveaxis(new_pm, -4, -3)     # [lead?, Hkv, B, T, D]
                flat = upd.reshape(upd.shape[:-3]
                                   + (upd.shape[-3] * upd.shape[-2],)
                                   + upd.shape[-1:])
                compact = cm.gather_pool_frames(flat, dense_pos,
                                                flat.ndim - 2)
                sched.enqueue_write(name, cm.port_major_to_banked(compact),
                                    shard=leaf_shard(leaf),
                                    into=leaf_stream(leaf))
                continue
            if phys is not None:
                # scatter the updated per-slot frames back into the
                # port-major pool before it returns through the write burst
                pool_pm = pm_pools[(kind, i, leaf_name)]
                upd = jnp.moveaxis(new_pm, -4, -3)
                new_pm = cm.scatter_pool_frames(pool_pm, upd, phys,
                                                pool_pm.ndim - 2)
            sched.enqueue_write(name, cm.port_major_to_banked(new_pm))
    sched.issue()
    lines_back = sched.commit()
    for kind, i in plan:
        shape = caches[kind][i]["k"].shape
        entry = {}
        for leaf_name in ("k", "v"):
            name = f"{kind}{i}/{leaf_name}"
            lines = lines_back[name]
            if fresh_only:
                # land the fresh lines at their frames (sentinels drop);
                # every other frame of the pool stays as it was
                pool = cm.kv_leaf_to_lines(
                    _flat_frames(caches[kind][i][leaf_name]))
                lines = pool.at[landing[name]].set(lines, mode="drop")
            entry[leaf_name] = lines.reshape(shape)
        new_caches[kind][i] = entry

    x = cm.apply_norm(x, params["final_norm"], cfg.norm)
    return _emit_logits(params, x, cfg, draft), new_caches


def _fresh_write_stream(fresh: jax.Array, idx: jax.Array, n: int):
    """A layer stack's fresh frames ``[lead?, B, Hkv, D]`` and their pool
    line indices ``idx`` (rep-major, as :func:`repro.models.common.
    pool_rep_indices` tiles them) → the banked write-network input for one
    dense stream, zero frames padding it to whole N-groups, and the landing
    indices, padded with the sentinel so the padding drops."""
    lines = fresh.reshape((-1,) + fresh.shape[-2:])      # [R*B, N, D]
    pad = (-lines.shape[0]) % n
    lines = jnp.pad(lines, ((0, pad), (0, 0), (0, 0)))
    idx = jnp.pad(idx, (0, pad), constant_values=FRAME_SENTINEL)
    return cm.port_major_to_banked(jnp.swapaxes(lines, 0, 1)), idx


def _decode_step_paged_fallback(params, token, caches, pos, positions,
                                cfg: ModelConfig, phys, draft=False):
    """Per-layer paged decode (unscheduled, off-geometry, or the ``fused``
    fabric): gather each pool into its dense line-major view, run the
    per-layer path unchanged, scatter the updated frames back.  Bit-parity
    with the dense layout for the same reason as the scheduled form."""
    entries = paged_entries(cfg)
    dense_caches = {"unit": list(caches["unit"]), "tail": list(caches["tail"])}
    for kind, i in entries:
        entry = dict(caches[kind][i])
        for leaf_name in ("k", "v"):
            flat = _flat_frames(entry[leaf_name])
            entry[leaf_name] = cm.gather_pool_frames(flat, phys,
                                                     flat.ndim - 3)
        dense_caches[kind][i] = entry
    x = cm.embed_apply(params["embed"], token)
    x, new_caches = _scan_blocks(params, x, cfg, positions=positions,
                                 caches=dense_caches, pos=pos, remat=False)
    for kind, i in entries:
        entry = dict(new_caches[kind][i])
        for leaf_name in ("k", "v"):
            pool = caches[kind][i][leaf_name]
            flat = cm.scatter_pool_frames(_flat_frames(pool),
                                          entry[leaf_name], phys,
                                          pool.ndim - 4)
            entry[leaf_name] = flat.reshape(pool.shape)
        new_caches[kind][i] = entry
    x = cm.apply_norm(x, params["final_norm"], cfg.norm)
    return _emit_logits(params, x, cfg, draft), new_caches


def _enqueue_weight_stream(sched, params, n: int):
    """ZeRO-1 weight streaming (``serve_fsdp``): queue every weight leaf
    whose size divides N² as a single-group line stream in the step's shared
    read burst — the per-step weight all-gather traffic batches with the KV
    reads through one network invocation per dtype.  Leaves that don't fit
    the line geometry stay resident (control traffic)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    streamed = []
    for j, leaf in enumerate(leaves):
        if leaf.size and leaf.size % (n * n) == 0:
            sched.enqueue_read(f"weight_stream/{j}", leaf.reshape(n, n, -1))
            streamed.append(j)
    return leaves, treedef, streamed


def _rebuild_weight_stream(moved, leaves, treedef, streamed):
    """Drain the weight-stream ports: each port reads its own bank back, a
    pure relabel of the banked buffer (the round trip is exact)."""
    leaves = list(leaves)
    for j in streamed:
        banked = moved[f"weight_stream/{j}"]          # [1, N, N, W]
        leaves[j] = jnp.swapaxes(banked[0], 0, 1).reshape(leaves[j].shape)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def prefill(params, tokens, cfg: ModelConfig, t_max: int, *,
            patch_embeds=None, kv_chunk: int = 0):
    """Prefill: forward pass that also installs KV/state caches.

    For the dry-run's ``prefill_32k`` cells we lower this function; caches are
    written line-major (time-contiguous wide lines — the DRAM-friendly layout
    the Medusa read network then re-banks during decode)."""
    b = tokens.shape[0]
    caches = init_cache(cfg, b, t_max)
    x = cm.embed_apply(params["embed"], tokens)
    if cfg.n_patches and patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    positions = jnp.arange(s)

    unit, reps, tail = pattern_unit(cfg)

    def fill_block(t, bp, c, h):
        hn = cm.apply_norm(h, bp["norm1"], cfg.norm)
        if t in ("A", "L"):
            out, kv = cm.attention_apply(bp["attn"], hn, cfg,
                                         positions=positions, layer_kind=t,
                                         cache=None, kv_chunk=kv_chunk)
            length = c["k"].shape[1]
            if length >= s:
                ck = jax.lax.dynamic_update_slice_in_dim(c["k"], kv["k"], 0, 1)
                cv = jax.lax.dynamic_update_slice_in_dim(c["v"], kv["v"], 0, 1)
            else:
                # windowed layer: keep last `length` positions, placed at ring
                # slots p % length — a barrel rotation of the window (the
                # paper's rotation unit applied on the time axis).
                ck = jnp.roll(kv["k"][:, s - length:], s % length, axis=1)
                cv = jnp.roll(kv["v"][:, s - length:], s % length, axis=1)
            nc = {"k": ck, "v": cv}
            h = h + out
            hn = cm.apply_norm(h, bp["norm2"], cfg.norm)
            ffn = (moe_apply(bp["ffn"], hn, cfg) if cfg.moe is not None
                   else cm.mlp_apply(bp["ffn"], hn, cfg.mlp))
            return h + ffn, nc
        # recurrent/ssm: run the full-sequence form, then rebuild the final
        # state by a single-step replay of the last token (cheap, exact).
        if t == "R":
            out, _ = rglru_apply(bp["rec"], hn, cfg, None)
            h2 = h + out
            # final state via one cached step over the last position
            nc = _recover_rec_state(bp, hn, cfg, t)
            hn2 = cm.apply_norm(h2, bp["norm2"], cfg.norm)
            ffn = (moe_apply(bp["ffn"], hn2, cfg) if cfg.moe is not None
                   else cm.mlp_apply(bp["ffn"], hn2, cfg.mlp))
            return h2 + ffn, nc
        out, _ = mamba_apply(bp["mixer"], hn, cfg, None)
        nc = _recover_rec_state(bp, hn, cfg, t)
        return h + out, nc

    if reps > 0:
        def body(carry, xs):
            h = carry
            up, uc = xs
            ncs = []
            for t, bp, c in zip(unit, up, uc):
                h, nc = fill_block(t, bp, c, h)
                ncs.append(nc)
            return h, ncs
        body = jax.checkpoint(body) if cfg.remat != "none" else body
        x, new_unit = jax.lax.scan(body, x,
                                   (tuple(params["unit"]), tuple(caches["unit"])))
    else:
        new_unit = None
    new_tail = []
    for i, t in enumerate(tail):
        x, nc = fill_block(t, params["tail"][i], caches["tail"][i], x)
        new_tail.append(nc)
    x = cm.apply_norm(x, params["final_norm"], cfg.norm)
    logits = cm.logits_apply(params["embed"], x[:, -1:], cfg)
    return logits, {"unit": new_unit, "tail": new_tail}


def _recover_rec_state(bp, hn, cfg, t):
    """Recompute the final recurrent state for cache installation by running
    the (associative-scan / chunked) path on the full sequence and taking the
    last step through the cached single-step form."""
    b = hn.shape[0]
    if t == "R":
        seqlen = hn.shape[1]
        # run the associative scan and keep h_T + the conv tail window
        from repro.models.rglru import _gates, _causal_conv  # noqa
        r = cfg.rglru
        w = r.lru_width or cfg.d_model
        branches = hn @ bp["rec"]["w_branch"]
        xb, _ = jnp.split(branches, [w], axis=-1)
        conv_state = jnp.concatenate(
            [jnp.zeros((b, max(r.conv_width - 1 - seqlen, 0), w), hn.dtype),
             xb[:, -min(r.conv_width - 1, seqlen):]], axis=1)
        xbc, _ = _causal_conv(xb, bp["rec"]["conv_w"], bp["rec"]["conv_b"])
        a, bb = _gates(bp["rec"], xbc, cfg)
        def combine(lhs, rhs):
            al, bl = lhs
            ar, br = rhs
            return al * ar, ar * bl + br
        _, hseq = jax.lax.associative_scan(combine, (a, bb), axis=1)
        return {"conv": conv_state, "h": hseq[:, -1]}
    # mamba: recompute chunk states and keep the final one
    from repro.models.mamba2 import _project, _causal_conv as mconv
    s = cfg.ssm
    x, z, bmat, cmat, dt, d_in, nh = _project(bp["mixer"], hn, cfg)
    conv_in = jnp.concatenate([x, bmat, cmat], axis=-1)
    seqlen = hn.shape[1]
    conv_state = jnp.concatenate(
        [jnp.zeros((b, max(s.conv_width - 1 - seqlen, 0), conv_in.shape[-1]),
                   conv_in.dtype),
         conv_in[:, -min(s.conv_width - 1, seqlen):]], axis=1)
    conv_out, _ = mconv(conv_in, bp["mixer"]["conv_w"], bp["mixer"]["conv_b"])
    x, bmat, cmat = jnp.split(conv_out, [d_in, d_in + s.d_state], axis=-1)
    xh = x.reshape(b, seqlen, nh, s.head_dim).astype(jnp.float32)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + bp["mixer"]["dt_bias"])
    a = -jnp.exp(bp["mixer"]["a_log"])
    da = jnp.exp(dtf * a)
    log_da = jnp.log(jnp.maximum(da, 1e-30))
    cum = jnp.cumsum(log_da, axis=1)
    decay_to_end = jnp.exp(cum[:, -1:][:, 0][:, None] - cum)
    state = jnp.einsum("bjh,bjh,bjn,bjhp->bhpn", decay_to_end, dtf,
                       bmat.astype(jnp.float32), xh)
    return {"conv": conv_state, "state": state}
